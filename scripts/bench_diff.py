#!/usr/bin/env python
"""Regression attribution CLI: diff two bench artifacts and print the
ranked attribution tree (`telemetry/diff.py`).

  python scripts/bench_diff.py OLD.json NEW.json
  python scripts/bench_diff.py BENCH_TPCDS_r03.json BENCH_TPCDS_r04.json
  python scripts/bench_diff.py OLD.json NEW.json --json   # machine form
  python scripts/bench_diff.py OLD.json NEW.json --query q64

Artifacts are expected in the canonical schema
(`telemetry/artifact.py`); legacy rounds are migrated IN MEMORY with a
visible note (the attribution is then per-lane only — migrate the
committed file with `python -m hyperspace_tpu.telemetry.artifact
migrate FILE` to make the note part of the record). Driver command
envelopes (`{parsed: ...}`) unwrap automatically.

Exit code: 0 — this tool diagnoses; `scripts/bench_regress.py` gates
(and auto-runs this differ when a gate fails).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# A lint/diff tool over committed artifacts and source: it never needs the
# chip, and pinning the CPU keeps it (and the tests that shell out to it)
# from taking the one process slot a chip allows.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Attribute the wall-clock delta between two bench "
                    "artifacts to telemetry buckets.")
    ap.add_argument("old", help="previous-round artifact path")
    ap.add_argument("new", help="current-round artifact path")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable diff (to_json)")
    ap.add_argument("--query", default=None,
                    help="restrict the report to one query/rung name")
    args = ap.parse_args()

    from hyperspace_tpu.telemetry import artifact, diff

    docs = []
    for path in (args.old, args.new):
        try:
            docs.append(artifact.load(path))
        except artifact.LegacyArtifactError:
            docs.append(artifact.load(path, migrate_legacy=True))
            print(f"bench_diff: note: {os.path.basename(path)} is a "
                  "legacy-schema artifact, migrated in memory",
                  file=sys.stderr)
    old_doc, new_doc = docs

    d = diff.diff_artifacts(old_doc, new_doc,
                            old_name=os.path.basename(args.old),
                            new_name=os.path.basename(args.new))
    if args.query:
        d.queries = [q for q in d.queries if q.name == args.query]
        if not d.queries:
            print(f"bench_diff: no query/rung named {args.query!r} "
                  "in both artifacts", file=sys.stderr)
            return 2
    print(d.to_json() if args.json else d.format_tree())
    return 0


if __name__ == "__main__":
    sys.exit(main())
