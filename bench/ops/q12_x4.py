"""The operation `q12_x4`: `q12`'s query, data and plain reference
(`reference/q12.py`, no copy), held to the mesh: a four-chip host under
default conf serves it from indexes born sharded over the chips, with
the filter, the join and the partial aggregate as SPMD programs.

Besides `q12`'s comparison, `check` counts, each with the limit 0:

- `spmd_fallbacks`: queries whose metrics carry an `spmd`/`fallback`
  event, plus what the program's `spmd.fallbacks` counter gained since
  this op was made (the warm-up and the window; the index builds before
  it run no join);
- `unsharded_indexes`: indexes of the mix whose `_shard_layout.json`
  does not say `numShards` = the config's `chips`;
- `idle_shards`: queries whose `mesh`/`join` event does not show rows
  on every one of the `chips` shards.

All of that holds whenever jax reports at least the config's `chips`
devices; on the chip `run.py`'s `require_chips` makes that always so.
Where jax reports fewer (the one-device CPU rehearsal of
`tests/test_run.py`, which turns distribution off), there is no mesh
to hold a query to: the op then holds it to `closed_loop_q12`'s
one-chip lanes and leaves the three counts out. `tests/test_run_x4.py`
rehearses the mesh on four virtual CPU devices.
"""

from __future__ import annotations

import json
import os

from lib import plugins
from lib.lake import counters, note

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ONE_CHIP_MIX = "closed_loop_q12"


class Op(plugins.load(_BENCH, "ops", "q12").Op):
    def __init__(self, spec: dict, deployment, seed: int, spans):
        import jax

        self.chips = int(deployment.config["chips"])
        self.on_mesh = len(jax.devices()) >= self.chips
        if not self.on_mesh:
            with open(os.path.join(deployment.bench_dir, "traffic",
                                   ONE_CHIP_MIX + ".json")) as f:
                spec = dict(spec, lanes=json.load(f)["lanes"])
            note(f"jax reports {len(jax.devices())} device(s), the config "
                 f"asks for {self.chips}: held to {ONE_CHIP_MIX}'s lanes")
        super().__init__(spec, deployment, seed, spans)
        self.fallbacks_before = counters().get("spmd.fallbacks", 0)

    def of_metrics(self, metrics) -> dict:
        """What the query's metrics say of the mesh: the join's rows per
        shard and every fallback's reason."""
        return {"shard_rows": [list(e["shard_rows"])
                               for e in metrics.events_of("mesh", "join")],
                "fallbacks": [e.get("reason")
                              for e in metrics.events_of("spmd", "fallback")]}

    def run(self, i: int, traced: bool = False, warming: bool = False) -> dict:
        rec = super().run(i, traced, warming)
        if warming:
            note(f"op {i}: join rows per shard {rec['shard_rows']}, "
                 f"fallbacks {rec['fallbacks']}")
        return rec

    def idle(self, rec: dict) -> bool:
        rows = rec["shard_rows"]
        return not rows or any(len(r) != self.chips or min(r) <= 0
                               for r in rows)

    def unsharded_indexes(self) -> int:
        from hyperspace_tpu.io.builder import read_shard_layout

        bad = 0
        for index in self.spec.get("indexes", ()):
            layout = read_shard_layout(self.dep.index_dir(index))
            shards = layout["numShards"] if layout else None
            note(f"index {index}: born sharded over {shards}")
            bad += shards != self.chips
        return bad

    def check(self, records: list) -> dict:
        compared = super().check(records)
        if not self.on_mesh:
            return compared
        gained = counters().get("spmd.fallbacks", 0) - self.fallbacks_before
        compared.update({
            "spmd_fallbacks": [sum(1 for r in records if r["fallbacks"])
                               + int(gained), 0],
            "unsharded_indexes": [self.unsharded_indexes(), 0],
            "idle_shards": [sum(self.idle(r) for r in records), 0]})
        return compared
