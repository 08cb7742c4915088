"""Unified TPC-DS profiling against a PERSISTENT workspace (data +
indexes reused across runs) — consolidates the former prof_tpcds.py /
profile_tpcds.py pair into one script driven by the engine's own
telemetry records instead of ad-hoc monkeypatching.

  python scripts/profile_tpcds.py q64 [--scale 10] [--runs 3]
      [--work /tmp/hs_prof] [--no-fuse] [--rules-off]
      [--mode class|node] [--trace-out trace.json] [--trace-dir DIR]
      [--registry]

Modes (both read the LAST timed run's `QueryMetrics`):
  class  per-PhysicalNode-class SELF seconds + call counts (the q64
         perf dev loop view; default)
  node   the 25 slowest operator INSTANCES, inclusive wall (read
         top-down — times include children)

Plus fusion-stage STATS (dispatch/sync seconds; the registry-backed
`engine.fusion.STATS` view), an optional process trace export in
Chrome trace-event format (`--trace-out`, loads in chrome://tracing /
ui.perfetto.dev), an optional XLA profiler capture for the last run
(`--trace-dir`), and an optional Prometheus registry dump
(`--registry`).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("query", nargs="?", default=None)
    ap.add_argument("--query", dest="query_opt", default="q64",
                    help="query name (compat alias for the positional)")
    ap.add_argument("--scale", type=float, default=10.0)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--work", default="/tmp/hs_prof")
    ap.add_argument("--no-fuse", action="store_true")
    ap.add_argument("--rules-off", action="store_true")
    ap.add_argument("--mode", choices=("class", "node"), default="class")
    ap.add_argument("--trace-out", default=None,
                    help="export engine spans as Chrome trace-event "
                         "JSON to this path")
    ap.add_argument("--trace-dir", default=None,
                    help="XLA profiler capture dir for the last run")
    ap.add_argument("--registry", action="store_true",
                    help="print the Prometheus registry dump at exit")
    args = ap.parse_args()
    query = args.query or args.query_opt

    from hyperspace_tpu import (Hyperspace, HyperspaceConf,
                                HyperspaceSession, telemetry)
    from hyperspace_tpu.engine import fusion
    from hyperspace_tpu.tpcds import QUERIES, generate
    from hyperspace_tpu.tpcds.queries import create_indexes

    if args.trace_out:
        telemetry.enable_tracing()

    work = os.path.join(args.work, f"s{args.scale:g}")
    data_dir = os.path.join(work, "data")
    wh = os.path.join(work, "wh")
    t0 = time.perf_counter()
    paths = generate(data_dir, scale=args.scale)  # reuses existing files
    print(f"generate/reuse: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    conf_map = {"hyperspace.warehouse.dir": wh,
                "spark.hyperspace.index.num.buckets": "32"}
    extra = os.environ.get("BENCH_TPCDS_CONF")
    if extra:
        conf_map.update(json.loads(extra))
    if args.no_fuse:
        conf_map["spark.hyperspace.execution.fusion.enabled"] = "false"
    sess = HyperspaceSession(HyperspaceConf(conf_map))
    hs = Hyperspace(sess)
    dfs = {n: sess.read_parquet(p) for n, p in paths.items()}
    idx_df = hs.indexes()
    existing = set(idx_df["name"]) if len(idx_df) else set()
    t0 = time.perf_counter()
    create_indexes(hs, dfs, queries=[query], skip=existing)
    print(f"index build (skip {len(existing)} existing): "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

    if args.rules_off:
        sess.disable_hyperspace()
    else:
        sess.enable_hyperspace()
    build, _oracle = QUERIES[query]

    build(dfs).collect()  # warm: compiles, file listings, caches
    for k in fusion.STATS:
        fusion.STATS[k] = 0 if isinstance(fusion.STATS[k], int) else 0.0
    walls = []
    metrics = None
    for r in range(args.runs):
        if args.trace_dir and r == args.runs - 1:
            sess.conf.set("spark.hyperspace.trace.dir", args.trace_dir)
        t0 = time.perf_counter()
        out, metrics = build(dfs).collect(with_metrics=True)
        walls.append(time.perf_counter() - t0)
    print(f"rows={out.num_rows} walls={[round(w, 3) for w in walls]}")
    total = sum(walls)

    if args.mode == "class":
        # SELF seconds per operator class over the LAST run, from the
        # recorder's parent/child linkage (the subtraction is
        # `QueryMetrics.summary`'s).
        per_op = metrics.summary()["operators"]
        print(f"\nper-class SELF seconds, last run "
              f"(of {walls[-1]:.3f}s):")
        for name, ent in sorted(per_op.items(),
                                key=lambda kv: -kv[1]["self_s"]):
            print(f"  {name:26s} calls={ent['count']:4d}  "
                  f"self={ent['self_s']:8.3f}s "
                  f"({100 * ent['self_s'] / walls[-1]:4.1f}%)")
    else:
        # Slowest operator INSTANCES, inclusive wall — read top-down.
        records = sorted(metrics.operators, key=lambda op: -op.wall_s)
        print("\nslowest operator instances, last run (INCLUSIVE of "
              "children — read top-down):")
        for op in records[:25]:
            rows = f" rows={op.rows_out}" if op.rows_out is not None else ""
            print(f"{op.wall_s:9.3f}s  {op.label[:110]}{rows}")

    print(f"\nfusion STATS over {args.runs} timed runs "
          f"(total {total:.3f}s): {dict(fusion.STATS)}")
    if args.trace_out:
        info = telemetry.export_trace(args.trace_out)
        print(f"trace: {info['events']} events -> {info['path']} "
              f"(load in chrome://tracing or ui.perfetto.dev)",
              file=sys.stderr)
    if args.registry:
        print("\n" + telemetry.get_registry().to_text())


if __name__ == "__main__":
    main()
