"""The control at a cell's own size (see lib/control.py), run by hand:

    python bench/control.py --workload <cell> --seeds 1,2,3

For each seed: makes the cell's data, computes the reference's answer to
the cell's query over it and over the same data in the next precision
down (float64 through float32, dates through bfloat16), and compares
the two by the comparison that decides `correct`.
Prints one JSON line per seed; every `mismatched_rows` has to be above
the limit 0. The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)


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
    op = plugins.load(bench_dir, "ops", traffic.get("then", traffic["op"]))
    reference = plugins.load(bench_dir, "reference",
                             traffic.get("reference", op.Op.reference))
    scale = args.scale or config["scale_factor"]
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        tables = dataset.make(config, seed, scale)
        params = op.Op.control_params(query, dataset, scale, seed)
        reading = control.control_reading(reference, tables, dataset, query,
                                          params)
        ok = ok and reading["mismatched_rows"] > 0
        print(json.dumps(dict(reading, workload=args.workload, seed=seed,
                              limit=0)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
