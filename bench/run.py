"""One cell of the benchmark, once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: refuses to start unless jax sees exactly the cell's chips as
TPU devices; makes the data from the seed; builds what the cell's traffic
needs through the program's public API; warms the cell's shapes; measures
for `--seconds`; then compares every answer of the window with the plain
reference. Notes go to earlier lines, one of them (`[bench] where: {...}`,
lib/where.py) saying where the window's time went; the last line of stdout
is the one JSON object the driver reads. With `--trace 0` its metrics are
the cell's end-to-end metrics, with `--trace 1` its per-layer metrics, read
from a profiler trace of the window's first seconds.

Everything that belongs to one configuration, dataset, traffic mix, kind
of traffic, kind of operation or metric is a file found by the name that
`BENCHMARK.json` or a data file gives (see bench/README.md and
lib/plugins.py); this file names none of them.
"""

import time

T0 = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def note(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def fail(msg: str, code: int = 2) -> "SystemExit":
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return SystemExit(code)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(manifest: dict, workload: str, root: str = ROOT) -> dict:
    """The cell, its configuration, its traffic mix and the metrics it
    reports, each from the file the manifest's names lead to."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise fail(f"unknown workload {workload!r}; BENCHMARK.json has "
                   f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    bench_dir = os.path.join(root, manifest["paths"][0])
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "bench_dir": bench_dir,
            "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
            "per_layer": [m for m in manifest["per_layer"] if mine(m)]}


def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise fail(f"no TPU: jax reports platform "
                   f"{devices[0].platform!r}; a cell is measured on the "
                   f"chip or not at all")
    if len(devices) != chips:
        raise fail(f"the cell needs {chips} chip(s), jax reports "
                   f"{len(devices)}")
    return devices


class Tracer:
    """A profiler trace of the window's first seconds: started with the
    window, stopped at the first operation boundary after `seconds` and
    `min_ops` whole operations."""

    def __init__(self, trace_dir: str, seconds: float, min_ops: int):
        self.dir, self.seconds, self.min_ops = trace_dir, seconds, min_ops
        self.active = False
        self.started = None

    def start(self) -> None:
        import jax.profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the bench's spans, not every call
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.started = time.perf_counter()
        self.active = True

    def maybe_stop(self, n_ops: int, force: bool = False) -> None:
        import jax.profiler

        if not self.active:
            return
        now = time.perf_counter()
        if force or (now - self.started >= self.seconds
                     and n_ops >= self.min_ops):
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False
            note(f"trace: {n_ops} ops in {now - self.started:.2f}s, written "
                 f"in {time.perf_counter() - now:.2f}s")


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             manifest=None, root: str = ROOT, scale=None,
             conf_overrides=None, need_chip: bool = True) -> dict:
    """The whole run; returns the result object. `scale`,
    `conf_overrides` and `need_chip` exist for the tests, which rehearse
    a cell at a tiny size on the CPU: the manifest and the command line
    never set them."""
    manifest = manifest or load_json(os.path.join(root, "BENCHMARK.json"))
    found = resolve(manifest, workload, root)
    cell, config, traffic_spec = (found["cell"], found["config"],
                                  found["traffic"])
    try:
        import hyperspace_tpu  # noqa: F401  (x64, the compile cache)
    except ImportError as e:
        raise fail(f"the system under test is not in this checkout: {e}")
    import jax

    devices = require_chips(cell["chips"]) if need_chip else jax.devices()

    from lib import compiles, plugins, spans as spans_mod, trace_reduce, where
    from lib.lake import Deployment, counters

    bench_dir = found["bench_dir"]
    try:
        driver_module = plugins.load(bench_dir, "drivers",
                                     traffic_spec["driver"])
        metric_readers = {
            m["name"]: plugins.load(bench_dir, "metrics", m["name"])
            for m in (found["per_layer"] if trace else found["end_to_end"])}
    except (FileNotFoundError, KeyError) as e:
        raise fail(str(e))

    listener = compiles.CompileListener().listen()
    note(f"cell {workload}: config {cell['config']}, traffic "
         f"{cell['traffic']}, seed {seed}, {seconds}s, trace {int(trace)}; "
         f"{devices[0].device_kind} x{len(devices)}, jax {jax.__version__}; "
         f"compile cache "
         f"{jax.config.jax_compilation_cache_dir or os.environ.get('JAX_COMPILATION_CACHE_DIR')}")
    work = os.path.join(root, ".bench_work", workload, f"seed{seed}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    dep = None
    try:
        dep = Deployment(config, seed, os.path.join(work, "lake"),
                         traffic_spec["tables"], bench_dir, scale=scale,
                         conf_overrides=conf_overrides)
        spans = spans_mod.Spans()
        traffic = driver_module.Driver(traffic_spec, dep, seed, spans)
        traffic.setup()
        traffic.warm()
        tracer = None
        if trace:
            t = traffic_spec.get("trace", {})
            tracer = Tracer(os.path.join(work, "trace"),
                            t.get("seconds", 5.0), t.get("min_ops", 2))
        compiled_before = listener.snapshot()
        counters_before = counters()
        setup_s = time.perf_counter() - T0
        note(f"set-up {setup_s:.2f}s (jax backend compile "
             f"{compiled_before['seconds']['backend']:.2f}s in "
             f"{compiled_before['counts']['backend']} programs, cache loads "
             f"{compiled_before['seconds']['cache_load']:.2f}s)")

        watch = where.Watch().start()
        window = traffic.run_window(seconds, tracer)
        spent = watch.stop()
        failed = traffic.failed
        compiled = compiles.delta(listener.snapshot(), compiled_before)
        counters_after = counters()
        peak = memory_peak_bytes(devices)
        attempted = traffic.n_started - len(traffic.warm_records)

        run = {
            "cell": cell, "config": config, "traffic": traffic_spec,
            "setup_s": setup_s, "window": window,
            "records": traffic.records, "spans": spans,
            "counters": {k: counters_after.get(k, 0) - counters_before.get(k, 0)
                         for k in counters_after},
            "compiled": compiled, "rows": dep.rows, "dataset": dep.dataset,
            "device_kind": devices[0].device_kind, "trace": None,
        }
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        result = {"correct": False, "attempted": attempted, "failed": failed}
        wanted = found["per_layer"] if trace else found["end_to_end"]
        if trace and traffic.records:
            run["trace"] = trace_reduce.reduce(
                trace_reduce.find_xplane(tracer.dir))
            lo, hi = run["trace"]["window"]
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = hi - lo
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(run["trace"]),
                "idle_gaps": trace_reduce.idle_gaps(run["trace"])}
        metrics = {}
        if traffic.records:
            for m in wanted:
                value = metric_readers[m["name"]].compute(run)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        note(f"window: {len(traffic.records)} ops in "
             f"{window['end'] - window['start']:.2f}s; jax compiled for "
             f"{compiled['seconds']['backend']:.3f}s in it "
             f"({compiled['counts']['backend']} programs); peak HBM {peak}")
        slowest = sorted(((r["end"] - r["start"], r["op"])
                          for r in traffic.records), reverse=True)[:3]
        note("slowest ops (s, op): "
             + ", ".join(f"{d:.3f} #{i}" for d, i in slowest))

        # The comparison, after the window and the memory reading; its
        # time is in no metric.
        t_check = time.perf_counter()
        compared = {"failed_ops": [failed, 0]}
        if traffic.records or traffic.warm_records:
            compared.update(traffic.check())
        else:
            compared["answers_compared"] = [0, 1]
        note(f"check: {time.perf_counter() - t_check:.2f}s")
        result["where"] = dict(
            where.window_notes(traffic.records, window, spans), **spent,
            fs=where.fs_type(work),
            stat_us=where.stat_us(os.path.join(work, "lake")))
        note("where: " + json.dumps(result["where"]))
        result["correct"] = bool(
            traffic.records
            and all(_within(name, got, limit)
                    for name, (got, limit) in compared.items()))
        result["compared"] = compared
        return result
    finally:
        if dep is not None:
            dep.close()
        shutil.rmtree(work, ignore_errors=True)


def _within(name: str, got, limit) -> bool:
    # `answers_compared` has to reach its number; every other is a count
    # of faults with the limit 0.
    return got >= limit if name == "answers_compared" else got <= limit


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, (got, limit) in result["compared"].items():
        print(f"[bench] compared {name}: {got} (limit {limit})",
              file=sys.stderr)
    print(f"[bench] correct: {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
