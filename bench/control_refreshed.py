"""The control of a cell whose lake took refresh sets after its indexes
were built, at the cell's own size, run by hand:

    python bench/control_refreshed.py --workload <cell> --seeds 1,2,3

`control.py` breaks the guarantee "every column exact" (the next
precision down). Such a cell states one more: "an answer over the base
tables alone is a wrong answer", which is what an index that is not
refreshed gives when nothing reads the appended files. For each seed
this makes the cell's base tables and refresh sets, computes the plain
reference's answer over the whole lake and over the base alone, and
compares the two by the comparison that decides `correct`; then
`control.py`'s own reading over the whole lake. Prints one JSON line per
seed; both `mismatched_rows` have to be above the limit 0. The
benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)


def stale_reading(reference_module, base: dict, whole: dict, query: dict,
                  params: dict) -> dict:
    """{mismatched_rows, rows}: the reference over the base tables alone
    against the reference over the whole lake."""
    from lib import compare

    exact = reference_module.Reference(whole).answer(query, params)
    stale = reference_module.Reference(base).answer(query, params)
    return {"mismatched_rows": compare.mismatched_rows(
                compare.reference_columns(stale),
                compare.SortedRows(compare.reference_columns(exact))),
            "rows": len(next(iter(exact.values())))}


def lake(dataset, config: dict, seed: int, scale: float, tables_used):
    """(base, whole): the tables as the indexes saw them, and as the
    queries do."""
    made = dataset.make(config, seed, scale)
    sets = dataset.refresh_sets(config, seed, scale)
    base = {t: made[t] for t in tables_used}
    return base, {t: dataset.whole(base[t], [s[t] for s in sets])
                  for t in base}


def main(argv=None) -> int:
    import run as bench_run
    from lib import control, plugins

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="1,2,3")
    parser.add_argument("--scale", type=float, default=None)
    args = parser.parse_args(argv)
    manifest = bench_run.load_json(os.path.join(bench_run.ROOT,
                                                "BENCHMARK.json"))
    found = bench_run.resolve(manifest, args.workload)
    config, traffic = found["config"], found["traffic"]
    query, bench_dir = traffic["query"], found["bench_dir"]
    dataset = plugins.load(bench_dir, "datasets", config["dataset"])
    op = plugins.load(bench_dir, "ops", traffic["op"])
    reference = plugins.load(bench_dir, "reference",
                             traffic.get("reference", op.Op.reference))
    scale = args.scale or config["scale_factor"]
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        base, whole = lake(dataset, config, seed, scale, traffic["tables"])
        params = op.Op.control_params(query, dataset, scale, seed)
        stale = stale_reading(reference, base, whole, query, params)
        lossy = control.control_reading(reference, whole, dataset, query,
                                        params)
        ok = ok and stale["mismatched_rows"] > 0 \
            and lossy["mismatched_rows"] > 0
        print(json.dumps({
            "workload": args.workload, "seed": seed, "limit": 0,
            "rows": stale["rows"],
            "base_alone_mismatched_rows": stale["mismatched_rows"],
            "next_precision_down_mismatched_rows": lossy["mismatched_rows"],
            "lake_rows": {t: len(next(iter(c.values())))
                          for t, c in whole.items()}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
