"""How long the host waits in the SPMD join's one readback, per query:
the program's counter `mesh.join.sync_s` over the window by the queries
completed in it (program counter; the span `hs.mesh.join.sync` shows
the same wait in a capture)."""


def compute(run):
    n = len(run["records"])
    if not n or "mesh.join.sync_s" not in run["counters"]:
        return None
    return 1e3 * run["counters"]["mesh.join.sync_s"] / n
