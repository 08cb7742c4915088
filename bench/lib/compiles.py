"""Seconds jax spends compiling or loading programs from its persistent
cache, process-wide, from jax's own monitoring events (the program's
registry sees only its `instrumented_jit` programs, not the plain
`jax.jit` sort programs)."""

from __future__ import annotations

EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
    "/jax/core/compile/jaxpr_trace_duration": "trace",
}


class CompileListener:
    def __init__(self):
        self.seconds = {v: 0.0 for v in EVENTS.values()}
        self.counts = {v: 0 for v in EVENTS.values()}

    def _on_event(self, event: str, seconds: float, **_kw) -> None:
        kind = EVENTS.get(event)
        if kind:
            self.seconds[kind] += seconds
            self.counts[kind] += 1

    def listen(self) -> "CompileListener":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def snapshot(self) -> dict:
        return {"seconds": dict(self.seconds), "counts": dict(self.counts)}


def delta(after: dict, before: dict) -> dict:
    return {k: {n: after[k][n] - before[k][n] for n in after[k]}
            for k in after}
