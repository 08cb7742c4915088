"""The Hyperspace user facade.

Parity: reference `Hyperspace.scala:24-133` — lifecycle verbs delegated to
the index collection manager, `indexes` catalog view, `explain`, plus the
session-keyed context holding a CachingIndexCollectionManager
(`Hyperspace.scala:107-133`).
"""

from __future__ import annotations

import threading
import weakref
from typing import Optional

from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.engine.session import HyperspaceSession
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.index.manager import CachingIndexCollectionManager


class HyperspaceContext:
    """Per-session context (reference `Hyperspace.scala:131-133`).

    Holds no strong reference back to the session (it is the weak key in
    `Hyperspace._contexts`); only the conf-derived manager lives here.
    """

    def __init__(self, session: HyperspaceSession):
        self.index_collection_manager = CachingIndexCollectionManager(session.conf)


def index_usage_report(manager, last_n: Optional[int] = None):
    """Per-index rule-usage rows for `manager`'s catalog (the body of
    `Hyperspace.index_usage`, module-level so the `/healthz`
    `index_usage` section can render the same report from a bare
    conf-built manager — an HTTP handler thread has no facade)."""
    from hyperspace_tpu import telemetry

    counters = telemetry.get_registry().counters_dict()
    ring = telemetry.get_recorder().queries(last_n)
    ring_counts: dict = {}
    for qm in ring:
        try:
            for use in qm.index_usage():
                name = use.get("name")
                if name:
                    ring_counts[name] = ring_counts.get(name, 0) + 1
        except Exception:
            continue  # a foreign recorder shape never breaks the report
    out = []
    for entry in manager.indexes():
        name = entry.name
        served_ring = ring_counts.get(name, 0)
        out.append({
            "index": name,
            "state": entry.state,
            "served_total": int(
                counters.get(f"rules.served.{name}", 0)),
            "served_in_ring": served_ring,
            "ring_entries": len(ring),
            "unused": served_ring == 0,
        })
    return out


class Hyperspace:
    # Weak keys: a dropped session must not be pinned by its context.
    _contexts: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
    _lock = threading.Lock()

    def __init__(self, session: Optional[HyperspaceSession] = None):
        self.session = session or HyperspaceSession()
        self._context = Hyperspace.get_context(self.session)

    @staticmethod
    def get_context(session: HyperspaceSession) -> HyperspaceContext:
        """Session-keyed context cache (reference `Hyperspace.scala:107-129`
        uses a thread-local keyed on the active session)."""
        with Hyperspace._lock:
            ctx = Hyperspace._contexts.get(session)
            if ctx is None:
                ctx = HyperspaceContext(session)
                Hyperspace._contexts[session] = ctx
            return ctx

    @property
    def _manager(self) -> CachingIndexCollectionManager:
        return self._context.index_collection_manager

    # -- lifecycle verbs (reference `Hyperspace.scala:33-92`) -------------

    def create_index(self, df, index_config) -> None:
        """Build an index over `df`'s relation. The config type selects
        the KIND: `IndexConfig` builds a covering index (bucketed,
        sorted derived dataset); `DataSkippingIndexConfig` builds a
        data-skipping index (per-file zone-map + bloom sketch blob,
        optional Z-order clustering — docs/data-skipping.md). Both flow
        through the same transactional log FSM."""
        self._manager.create(df, index_config)

    def delete_index(self, index_name: str) -> None:
        self._manager.delete(index_name)

    def restore_index(self, index_name: str) -> None:
        self._manager.restore(index_name)

    def vacuum_index(self, index_name: str) -> None:
        self._manager.vacuum(index_name)

    def refresh_index(self, index_name: str, mode: str = "full") -> None:
        """mode='full' rebuilds (reference behavior); mode='incremental'
        indexes only appended source files (reference roadmap, exceeded)."""
        self._manager.refresh(index_name, mode)

    def optimize_index(self, index_name: str) -> None:
        """Merge-compact incremental deltas (extension; reference roadmap)."""
        self._manager.optimize(index_name)

    def cancel(self, index_name: str) -> None:
        self._manager.cancel(index_name)

    def recover_index(self, index_name: str) -> bool:
        """Force crash recovery: if a writer died mid-operation (the log's
        latest entry is transient), run the Cancel FSM transition back to
        the last stable state immediately — no waiting for the
        `spark.hyperspace.maintenance.lease.seconds` lease that gates
        AUTOMATIC recovery by the next create/refresh/optimize. Returns
        True iff a recovery ran (False: index already stable)."""
        return self._manager.recover(index_name)

    def indexes(self):
        """Catalog as a pandas DataFrame (reference `Hyperspace.scala:33-36`)."""
        return self._manager.indexes_df()

    # -- self-driving indexes ---------------------------------------------

    def advisor(self):
        """The session's self-driving index advisor
        (`hyperspace_tpu/advisor/`): mines the flight ring for
        recurring un-indexed filter/join shapes, what-if scores
        hypothetical indexes by replaying recorded plans through the
        real rewrite rules, and auto-builds winners through the normal
        lease-gated Create path. `advisor().run_once()` is one
        mine→score→build cycle; `advisor().start(interval_s)` runs it
        in the background. One advisor per facade instance (the miner
        holds an incremental cursor over the process flight ring)."""
        if not hasattr(self, "_advisor"):
            from hyperspace_tpu.advisor import IndexAdvisor
            self._advisor = IndexAdvisor(self.session)
        return self._advisor

    def ingest(self, producer=None, indexes=()):
        """A continuous-ingest coordinator (`engine/ingest.py`) bound
        to this session: each `run_once()` tick lands `producer`'s
        micro-batch appends, defers under serve pressure, and drives
        mode='incremental' refresh of `indexes` through the lease-gated
        manager path with typed conflict concession. Caller-threaded —
        drive it on `spark.hyperspace.ingest.interval.seconds`; the
        coordinator never owns a thread. Fresh instance per call (the
        staleness ledger belongs to one append stream)."""
        from hyperspace_tpu.engine.ingest import IngestCoordinator
        return IngestCoordinator(self.session, producer=producer,
                                 indexes=indexes)

    # -- observability ----------------------------------------------------

    def index_usage(self, last_n: Optional[int] = None):
        """Per-index rule-usage report — the drop advisor's raw
        material (ROADMAP: "storage is a budget too"). For every index
        in this session's catalog: how many queries a rewrite rule
        served from it over the PROCESS lifetime
        (`rules.served.<index>` counters) and within the last `last_n`
        flight-ring entries (None = the whole ring), plus an `unused`
        flag for indexes no ring entry selected. Report only — nothing
        is vacuumed; an index idle here may still serve a workload that
        rotated out of the bounded ring, so treat `unused` as a
        candidate list, not a verdict."""
        return index_usage_report(self._manager, last_n)

    def incidents(self, active_only: bool = False):
        """The incident plane's structured incidents (rule-driven
        alerting, `telemetry/alerts.py`): each carries its rule, fire
        and resolve times, breaching value, and the evidence bundle
        captured at fire time. `active_only` keeps the still-firing
        ones. The same documents the `/alerts` ops endpoint serves."""
        from hyperspace_tpu.telemetry import alerts

        return alerts.get_manager().incidents(active_only=active_only)

    def metrics_registry(self):
        """The process-wide metrics registry (delegates to the
        session; see `HyperspaceSession.metrics_registry`)."""
        return self.session.metrics_registry()

    def tenant_report(self) -> dict:
        """Per-tenant usage/cost chargeback report: for every tenant
        seen since process start, the device cost it was billed
        (modeled flops + bytes accessed and measured dispatch-seconds
        from `instrumented_jit`'s per-dispatch charges), the link bytes
        it moved, the segment-cache fills it paid for, and its serving
        state (admitted bytes, in-flight/queued counts, SLO window,
        configured quota knobs). EXACT by construction: every charge
        site mirrors its global counter inc onto the active tenant's
        `tenant.<id>.*` series at the same line, so `totals` (the
        per-tenant sums) equals `global` (the process counters) to the
        bit (`tests/test_tenancy.py::test_tenant_report_exactness`
        holds it). Unscoped work bills the "default" tenant; nothing is
        ever dropped."""
        from hyperspace_tpu import telemetry

        usage = telemetry.tenant_digest()
        counters = telemetry.get_registry().series_snapshot()["counters"]
        totals = {name: sum(u.get(name, 0) for u in usage.values())
                  for name in telemetry.TENANT_CHARGE_COUNTERS}
        global_ = {name: counters.get(name, 0)
                   for name in telemetry.TENANT_CHARGE_COUNTERS}
        sched = self.session.scheduler()
        serving = sched.tenant_snapshot(self.session.conf)
        tenants = {}
        for t in sorted(set(usage) | set(serving)):
            tenants[t] = {"usage": usage.get(t, {})}
            if t in serving:
                tenants[t]["serving"] = serving[t]
        return {
            "tenants": tenants,
            "totals": totals,
            "global": global_,
            # Byte/flop/fill counters are integer-valued and sum
            # exactly; dispatch-seconds is the one genuinely fractional
            # series, where float summation order costs at most a few
            # ulps — hence the relative epsilon instead of ==.
            "exact": all(abs(totals[n] - global_[n])
                         <= 1e-9 * max(1.0, abs(global_[n]))
                         for n in totals),
        }

    def export_trace(self, path: str) -> dict:
        """Export collected spans as Chrome trace-event JSON (requires
        a prior `telemetry.enable_tracing()`); loads in
        chrome://tracing and ui.perfetto.dev."""
        from hyperspace_tpu import telemetry
        return telemetry.export_trace(path)

    def device_memory(self) -> dict:
        """Snapshot of the device-memory accountant: per-device
        live/peak HBM bytes and which backend measured them
        (`memory_stats` on real accelerators, the live-arrays
        accounting fallback on CPU/virtual meshes). Takes a fresh
        sample first so the answer is current, not last-span-stale."""
        from hyperspace_tpu import telemetry
        telemetry.memory.sample()
        return telemetry.memory.snapshot()

    def explain(self, df, verbose: bool = False, redirect=None,
                metrics=None) -> None:
        """Plan diff with rules on vs off (reference
        `Hyperspace.scala:101-104`). Pass `metrics` (a
        `telemetry.QueryMetrics`, e.g. `session.last_query_metrics()`)
        to append the runtime numbers of an actual execution under the
        diff — plan change and cost in one view."""
        from hyperspace_tpu.plananalysis.analyzer import PlanAnalyzer
        out = PlanAnalyzer.explain_string(df, self.session,
                                          self._manager.indexes(), verbose,
                                          metrics=metrics)
        (redirect or print)(out)
