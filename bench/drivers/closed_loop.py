"""The traffic kind `closed_loop`: `clients` (1) callers, each sending
its next operation when the last one's answer is in hand.

A kind of traffic is a module of `drivers/` with a class
`Driver(spec, deployment, seed, spans)` that has `setup`, `warm`,
`next_op`, `run_window(seconds, tracer) -> {"start", "end"}` and
`check() -> {name: [number, limit]}`, and keeps `records` (one dict per
operation finished in the window), `warm_records`, `n_started` and
`failed`. A traffic mix (`traffic/<mix>.json`) names its kind under
`"driver"` and the kind of operation it sends under `"op"`
(`ops/<op>.py`); the rest of the file is theirs to read.
"""

from __future__ import annotations

import time
import traceback

from lib import plugins
from lib.lake import note

MAX_FAILED = 3


class Driver:
    def __init__(self, spec: dict, deployment, seed: int, spans):
        if spec.get("clients", 1) != 1:
            raise ValueError("closed_loop drives one client; more callers "
                             "or an open loop are drivers of their own")
        self.spec = spec
        self.dep = deployment
        self.seed = seed
        self.spans = spans
        self.op = None
        self.records = []       # one dict per finished operation
        self.warm_records = []
        self.n_started = 0
        self.failed = 0
        self._warming = True

    # -- phases -----------------------------------------------------------

    def setup(self) -> None:
        """The indexes the mix lists, then the operation (which may look
        at them)."""
        for index in self.spec.get("indexes", ()):
            wall = self.dep.create_index(index)
            note(f"create_index {index}: lane {self.dep.build_lane(index)}, "
                 f"wall {wall:.2f}s")
        self.op = plugins.load(self.dep.bench_dir, "ops", self.spec["op"]).Op(
            self.spec, self.dep, self.seed, self.spans)

    def next_op(self, traced: bool = False) -> dict:
        i = self.n_started
        self.n_started += 1
        with self.spans.span("op", i):
            rec = self.op.run(i, traced, self._warming)
        rec["op"] = i
        self.records.append(rec)
        return rec

    def warm(self) -> None:
        """Every shape the window uses, through the window's own call."""
        for _ in range(self.op.warm_ops):
            rec = self.next_op()
            note(f"warm op {rec['op']}: {rec['end'] - rec['start']:.3f}s, "
                 f"{rec['rows']} rows, lanes {rec['lanes']}")
        self.warm_records, self.records = self.records, []
        self._warming = False

    def run_window(self, seconds: float, tracer=None) -> dict:
        """Operations back to back until `seconds` have passed; the one
        in flight then is finished. An operation that raises is counted
        as failed and the loop goes on, up to MAX_FAILED of them."""
        start = end = time.perf_counter()
        if tracer is not None:
            tracer.start()
        while time.perf_counter() - start < seconds \
                and self.failed < MAX_FAILED:
            try:
                end = self.next_op(traced=tracer is not None)["end"]
            except Exception:  # counted, printed, and the run is not correct
                self.failed += 1
                note("operation failed:\n" + traceback.format_exc())
            if tracer is not None:
                tracer.maybe_stop(len(self.records))
        if tracer is not None:
            tracer.maybe_stop(len(self.records), force=True)
        return {"start": start, "end": end}

    def check(self) -> dict:
        """Every answer of the window, and the warm-up's too: they came
        through the same call."""
        return self.op.check(self.warm_records + self.records)
