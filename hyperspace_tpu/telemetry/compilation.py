"""Compile observability: THE `jax.jit` wrapper for every engine entry
point.

A retrace storm is invisible in wall-time telemetry — the cost hides
inside whichever dispatch happened to trace — so every jitted entry
point in the package routes through `instrumented_jit(name, scope=...)`
instead of calling `jax.jit` directly (`scripts/check_metrics_coverage.py`
fails the build on `jax.jit` in any spelling outside this module, and on
an `instrumented_jit` whose scope is not in `DEVICE_SCOPES`). The
program's ops carry its device scope in a capture, and each call then
records:

- a `compile` span on the executing thread whenever XLA actually traced
  (its own category — and track — in the Perfetto export), covering
  trace + lowering + backend compile (the first dispatch is dominated
  by them);
- registry counters `compile.{traces,cache_hits,seconds}` plus
  per-entry-point `compile.<name>.traces`, and the jit executable-cache
  series `cache.jit.{hits,misses,entries}`;
- the same counters per-query on the active `QueryMetrics`
  (`metrics.compile` digests them — re-running an identical query must
  show ZERO new traces);
- the retrace CAUSE as a per-query decision event: the shape/dtype
  signature delta against this entry point's previous trace
  (`[compile] retrace {"target": ..., "cause": "shape: f64[100] ->
  f64[200]"}`).

Trace detection uses the one property jit guarantees: the wrapped
Python body executes exactly when XLA traces (a cache hit never re-runs
it). The wrapper pushes a per-thread frame, the body marks it, and the
call site reads the mark after dispatch — nested instrumented jits keep
their own frames.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, Optional

from hyperspace_tpu.telemetry import registry as _registry
from hyperspace_tpu.telemetry import trace as _trace

__all__ = ["instrumented_jit", "REGISTRY", "configure_persistent_cache",
           "persistent_cache_dir", "aot_warmup", "reset_aot_memo",
           "entry_point_costs"]


def entry_point_costs() -> Dict[str, tuple]:
    """{entry point name: (flops, bytes_accessed)} of the last traced
    program per instrumented jit (the memo every dispatch charges)."""
    with _sig_lock:
        return dict(_costs)

# name -> instrumented wrapper (the coverage lint audits the stamps).
REGISTRY: Dict[str, object] = {}

# name -> (flops, bytes_accessed) of the last traced program: XLA's
# own cost analysis, captured at trace time (where the lowering is
# already paid for) and charged on every subsequent dispatch of the
# entry point — the modeled-device-cost half of roofline attribution
# (`QueryMetrics.roofline`; measured wall is the other half).
_costs: Dict[str, tuple] = {}

# name -> last traced signature, PROCESS-wide (not per wrapper): entry
# points that rebuild their jit per call (the mesh step factories) must
# diff against the previous trace of the same NAME, or every trace
# would read "first trace" and a fresh-jit retrace storm would hide its
# cause ("signature unchanged (executable cache dropped)").
_last_sigs: Dict[str, tuple] = {}
_sig_lock = threading.Lock()

_tls = threading.local()

_persistent_lock = threading.Lock()


def persistent_cache_dir() -> Optional[str]:
    """The persistent compilation cache dir jax is using, or None."""
    import jax
    return jax.config.jax_compilation_cache_dir or None


def configure_persistent_cache(conf) -> None:
    """Point JAX's persistent compilation cache at
    `spark.hyperspace.compile.cache.dir` (called at session init, next
    to `transfer.configure`). Every `instrumented_jit` entry point then
    participates for free — jax keys persisted executables below its
    in-memory executable cache — so a FRESH replica pointed at a shared
    cache dir serves its first canonical-shape query from disk instead
    of paying the trace (the PR-3 warm `compile.traces == 0` property,
    surviving process restarts; the restored-from-disk dispatch still
    re-runs the traced body, so it counts as one trace with near-zero
    `compile.seconds` rather than a cache hit). One process-wide
    setting — jax's compilation cache is global, so co-resident
    sessions share it (same caveat as the transfer-engine knobs).

    The ENVIRONMENT outranks the knob: when JAX_COMPILATION_CACHE_DIR is
    set, jax already reads it and the knob is only logged as ignored —
    whoever launches the process places the cache, and no code moves it
    (`_jax_config.py` holds the default dir, and drops jax's
    size/compile-time eligibility floors for every placement, so the
    engine's small bucketed kernels qualify). An unset knob is a
    no-op, not a reset. Counted as
    `compile.persistent_cache.configured`."""
    import os

    path = conf.compile_cache_dir if conf is not None else None
    if not path:
        return
    path = str(path)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        import logging
        logging.getLogger(__name__).info(
            "compile.cache.dir=%s ignored: JAX_COMPILATION_CACHE_DIR=%s "
            "places the cache", path, env_dir)
        return
    import jax
    with _persistent_lock:
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
            _registry.get_registry().counter(
                "compile.persistent_cache.configured").inc()


# Warm-start AOT executables: keys already primed this process (e.g.
# one per (index root, version, predicate shape, cohort bucket) for the
# batched serve lane). The memo makes priming idempotent — a replica
# warming on every index open never re-pays an executed warmup.
_aot_keys: set = set()
_aot_lock = threading.Lock()


def reset_aot_memo() -> None:
    """Forget which warmup keys ran (tests simulating a fresh replica).
    Does NOT drop compiled executables — jax's caches are untouched."""
    with _aot_lock:
        _aot_keys.clear()


def aot_warmup(key: tuple, fn, args_fn) -> bool:
    """Prime a jit entry point for one canonical shape, once per `key`:
    call `fn(*args_fn())` so the trace + backend compile (or, on a
    fresh replica pointed at the persistent compile cache, the
    executable LOAD) happens now — at index-open / replica-start time —
    instead of inside the first serving query. A real dummy-argument
    call is used rather than `.lower().compile()` because only a
    dispatched call populates jax's executable cache: the warmed shape's
    first serving query must show `compile.traces == 0`, not a cheap
    re-trace. Returns True iff the warmup ran (False: memo hit, or the
    attempt failed — warm-start is an optimization, never a failure).
    Counted as `compile.aot.{warmups,memo_hits,errors}`."""
    with _aot_lock:
        if key in _aot_keys:
            _registry.get_registry().counter("compile.aot.memo_hits").inc()
            return False
        _aot_keys.add(key)
    try:
        fn(*args_fn())
        _registry.get_registry().counter("compile.aot.warmups").inc()
        return True
    except Exception:
        import logging
        logging.getLogger(__name__).warning(
            "AOT warmup failed for %r (serving proceeds; the first "
            "query of this shape pays the trace)", key, exc_info=True)
        _registry.get_registry().counter("compile.aot.errors").inc()
        return False


def _frames() -> list:
    frames = getattr(_tls, "frames", None)
    if frames is None:
        frames = []
        _tls.frames = frames
    return frames


def _abstract(leaf) -> str:
    """One signature atom: dtype[shape] for arrays, repr for statics
    (truncated — stage-program keys can be long)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(s) for s in shape)}]"
    r = repr(leaf)
    return r if len(r) <= 80 else r[:77] + "..."


def _signature(args, kwargs):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return str(treedef), tuple(_abstract(l) for l in leaves)


def _retrace_cause(prev, sig) -> str:
    """Human-readable delta between the previous trace's signature and
    this one's — the 'why did this retrace' answer."""
    if prev is None:
        return "first trace"
    prev_tree, prev_leaves = prev
    tree, leaves = sig
    if prev_tree != tree:
        return "argument structure changed"
    if len(prev_leaves) != len(leaves):
        return (f"argument count changed "
                f"({len(prev_leaves)} -> {len(leaves)})")
    deltas = [f"{a} -> {b}" for a, b in zip(prev_leaves, leaves)
              if a != b]
    if not deltas:
        # Same abstract signature yet jax re-traced: the executable
        # cache was dropped (clear_cache / eviction), not a shape delta.
        return "signature unchanged (executable cache dropped)"
    shown = "; ".join(deltas[:3])
    more = f" (+{len(deltas) - 3} more)" if len(deltas) > 3 else ""
    return f"shape/dtype: {shown}{more}"


class _Frame:
    __slots__ = ("traced",)

    def __init__(self):
        self.traced = False


def _capture_cost(name: str, jfn, args, kwargs) -> Optional[tuple]:
    """XLA cost analysis of the program just traced: re-lower with the
    same arguments (the trace path already paid once; observability
    rides the slow path, never the warm one) and read the modeled
    flops / bytes accessed. Best-effort — any backend or shape that
    cannot be lowered out-of-line returns None and the dispatch
    proceeds uncounted. Re-entrancy guard: the re-lower re-runs the
    wrapped body, and a NESTED instrumented jit called from it must
    not count phantom traces or recurse into its own capture."""
    if getattr(_tls, "in_cost_capture", False):
        return None
    _tls.in_cost_capture = True
    try:
        lowered = jfn.lower(*args, **kwargs)
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not isinstance(ca, dict):
            return None
        flops = float(ca.get("flops") or 0.0)
        nbytes = float(ca.get("bytes accessed") or 0.0)
        return (flops, nbytes)
    except Exception:
        return None
    finally:
        _tls.in_cost_capture = False


def device_scoped(scope: str):
    """Decorator: the ops of `fn` carry the device scope `scope`
    (`telemetry.DEVICE_SCOPES`) in a capture. `instrumented_jit` applies
    it to every program it builds; used directly, it names a piece
    traced INSIDE a program (called eagerly it would compile at every
    call).

    A bare `jax.named_scope` is not enough here. This package asks jax
    for one frame per MLIR location (`_jax_config.py`, for the compile
    cache's sake), and in that form XLA's op metadata keeps the name
    stack only for ops inside a NESTED call that the scope encloses: a
    primitive traced directly in the program's body, or in a nested
    call's body under a scope opened there, comes out without it; one
    traced inside a nested jit as `jit(f)/hs.compact/jit(f)/gather`. So
    the scope wraps a nested jit of the function; XLA inlines the call,
    the program computes what it did."""
    def decorate(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            import jax

            def piece():
                return fn(*args, **kwargs)

            piece.__name__ = fn.__name__
            with jax.named_scope(scope):
                return jax.jit(piece)()

        return scoped

    return decorate


def instrumented_jit(name: str, fn=None, *, scope: str, **jit_kwargs):
    """`jax.jit` with compile observability, its ops under the device
    scope `scope` (a key of `telemetry.DEVICE_SCOPES`, through
    `device_scoped`). Use exactly like jit:

        run = instrumented_jit("fusion.run_stage", scope="hs.stage",
                               static_argnames=("prog",))(body)

    The program keeps the function's name (`jit_<function>` in a
    capture). The returned callable forwards `clear_cache` and exposes
    `cache_size()` (the live executable count, where jax provides it).
    Usable as `instrumented_jit(name, fn, scope=...)` or as a decorator
    factory.
    """
    if scope not in _trace.DEVICE_SCOPES:
        raise ValueError(f"instrumented jit {name!r}: device scope "
                         f"{scope!r} is not in telemetry.DEVICE_SCOPES")
    if fn is None:
        return lambda f: instrumented_jit(name, f, scope=scope,
                                          **jit_kwargs)

    import jax

    fn = device_scoped(scope)(fn)

    @functools.wraps(fn)
    def body(*args, **kwargs):
        frames = _frames()
        if frames:
            frames[-1].traced = True
        return fn(*args, **kwargs)

    jfn = jax.jit(body, **jit_kwargs)

    def cache_size() -> Optional[int]:
        probe = getattr(jfn, "_cache_size", None)
        try:
            return int(probe()) if callable(probe) else None
        except Exception:
            return None

    @functools.wraps(fn)
    def call(*args, **kwargs):
        from hyperspace_tpu import telemetry

        if getattr(_tls, "in_cost_capture", False):
            # Nested dispatch under a cost-analysis re-lower: execute
            # without instrumentation (the outer capture would
            # otherwise pollute trace counters and recurse).
            return jfn(*args, **kwargs)
        frames = _frames()
        frame = _Frame()
        frames.append(frame)
        t0 = time.perf_counter()
        try:
            out = jfn(*args, **kwargs)
        finally:
            if frames and frames[-1] is frame:
                frames.pop()
        elapsed = time.perf_counter() - t0
        reg = _registry.get_registry()
        if frame.traced:
            sig = _signature(args, kwargs)
            with _sig_lock:
                cause = _retrace_cause(_last_sigs.get(name), sig)
                _last_sigs[name] = sig
            _trace.completed(f"hs.compile.{name}", "compile", elapsed,
                             target=name, cause=cause)
            reg.counter("compile.traces").inc()
            reg.counter("compile.seconds").inc(elapsed)
            reg.counter(f"compile.{name}.traces").inc()
            # Device cost attribution: capture XLA's modeled flops /
            # bytes for THIS program while the trace is already the
            # slow path; every later dispatch charges the memoized
            # cost (per-query and process-wide).
            cost = _capture_cost(name, jfn, args, kwargs)
            if cost is not None:
                with _sig_lock:
                    _costs[name] = cost
                reg.counter(f"compile.{name}.flops").inc(cost[0])
                reg.counter(
                    f"compile.{name}.bytes_accessed").inc(cost[1])
            telemetry.memory.cache_miss("jit")
            entries = cache_size()
            if entries is not None:
                reg.gauge(f"cache.jit.{name}.entries").set(entries)
            telemetry.add_count("compile.traces")
            telemetry.add_seconds("compile.seconds", elapsed)
            telemetry.event("compile",
                            "trace" if cause == "first trace"
                            else "retrace",
                            target=name, cause=cause,
                            seconds=round(elapsed, 4))
        else:
            reg.counter("compile.cache_hits").inc()
            telemetry.memory.cache_hit("jit")
            telemetry.add_count("compile.cache_hits")
            # Warm dispatch wall = measured device-side seconds (the
            # traced path's elapsed is compile time and stays in the
            # compile bucket). Dispatch-side on async backends.
            reg.counter("device.dispatch.seconds").inc(elapsed)
            telemetry.charge_tenant("device.dispatch.seconds", elapsed)
            telemetry.add_seconds("device.dispatch_s", elapsed)
        cost = _costs.get(name)
        if cost is not None:
            # The device executed this program either way: charge the
            # modeled cost per dispatch — per-query, process-wide, AND
            # to the active tenant's `tenant.<id>.device.*` bill at the
            # same site, so per-tenant sums equal the globals exactly
            # (the chargeback contract `Hyperspace.tenant_report()`
            # asserts).
            reg.counter("device.flops").inc(cost[0])
            reg.counter("device.bytes_accessed").inc(cost[1])
            telemetry.charge_tenant("device.flops", cost[0])
            telemetry.charge_tenant("device.bytes_accessed", cost[1])
            telemetry.add_seconds("device.flops", cost[0])
            telemetry.add_seconds("device.bytes_accessed", cost[1])
        return out

    call.__compile_span_instrumented__ = True
    call.__wrapped_jit__ = jfn
    call.cache_size = cache_size
    # Drop-in jit surface: forward the introspection/maintenance API so
    # callers (HLO probes via `.lower()`, cache resets, existing
    # `_cache_size` call sites) need not know about the wrapper.
    for attr in ("clear_cache", "lower", "eval_shape", "trace",
                 "_cache_size"):
        impl = getattr(jfn, attr, None)
        if impl is not None:
            setattr(call, attr, impl)
    REGISTRY[name] = call
    return call
