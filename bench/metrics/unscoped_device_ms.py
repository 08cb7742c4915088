"""Device time per traced query of the ops that carry no `hs.*` device
scope (the `tf_op` of the op's metadata names none): what the program's
scopes leave unnamed, an eager dispatch's ops or a program without a
scope. Summed per traced query, median over the window's whole queries.
None where the run was not traced or no op started inside a traced
query; 0 only where ops ran and every one carried a scope. On a program
without scopes it reads every op."""

import statistics

from lib import program_spans


def compute(run):
    found = program_spans.load(run)
    if found is None:
        return None
    per_query, ran = [], False
    for lo, hi in program_spans._whole(run, program_spans.QUERY):
        inside = [(d, scopes) for s, d, scopes, _ in found["ops"]
                  if lo <= s < hi]
        ran = ran or bool(inside)
        per_query.append(sum(d for d, scopes in inside if not scopes))
    return 1e3 * statistics.median(per_query) if ran else None
