"""Transactional action template — the lifecycle state machine core.

Parity: reference `actions/Action.scala:33-96`:
`run() = validate(); begin(); op(); end()`. `begin()` writes log id
`base_id+1` with a *transient* state; `end()` writes id `base_id+2` with the
*final* state and deletes + recreates `latestStable`. `base_id` = latest log
id or -1. A failure between begin and end strands the index in a transient
state; the Cancel FSM transition recovers it (reference
`actions/CancelAction.scala`) — run explicitly via
`Hyperspace.recover_index`/`cancel`, or automatically by the next
create/refresh/optimize once the stranded entry outlives
`spark.hyperspace.maintenance.lease.seconds` (lease-based recovery,
`CreateActionBase._recover_stale_writer`).
Optimistic concurrency: `write_log` refuses existing ids, so exactly one of
two racing actions wins the `base_id+1` slot.

Observability: every `run()` emits a structured ACTION REPORT — action
name, index, per-phase wall seconds (validate/begin/op/end), and
op-specific detail (rows, files, bytes; annotated via
`annotate_report`). Reports land in the process metrics registry
(counters `actions.*` + the report ring) and, on success, persist as
`<id>.report.json` next to the final log entry, so index maintenance
cost is auditable per log id long after the process exits.
`Action.__init_subclass__` wraps any subclass-defined `run` with the
same machinery and stamps it, mirroring `PhysicalNode`'s operator
instrumentation — `scripts/check_metrics_coverage.py` fails if any
Action subclass can run without emitting a report.
"""

from __future__ import annotations

import functools
import logging
import time
from abc import ABC, abstractmethod

from hyperspace_tpu import telemetry
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.index.log_entry import LogEntry
from hyperspace_tpu.index.log_manager import IndexLogManager
from hyperspace_tpu.utils import faults

logger = logging.getLogger(__name__)


def _instrument_run(fn):
    """Wrap a `run` implementation with the action-report machinery.
    Re-entrant: a subclass override calling `super().run()` shares the
    outer invocation's report instead of emitting two."""

    @functools.wraps(fn)
    def wrapper(self):
        if self._report is not None:
            return fn(self)
        report = self._report = {
            "action": type(self).__name__,
            "started_at": time.time(),
            "phases": {},
            "detail": {},
            "ok": False,
        }
        t0 = time.perf_counter()
        try:
            with telemetry.span(f"hs.action.{type(self).__name__}",
                                "action"):
                out = fn(self)
            report["ok"] = True
            return out
        except BaseException as exc:
            report["error"] = repr(exc)
            raise
        finally:
            report["wall_s"] = round(time.perf_counter() - t0, 6)
            try:
                self._publish_report(report)
            finally:
                self._report = None

    wrapper.__action_report_instrumented__ = True
    return wrapper


class Action(ABC):
    def __init__(self, log_manager: IndexLogManager):
        self.log_manager = log_manager
        self._base_id: int | None = None
        self._latest_entry = None
        self._report: dict | None = None

    def __init_subclass__(cls, **kwargs):
        # EVERY subclass's run() emits an action report; opting out is
        # not supported by design (the metrics-coverage lint flags an
        # unstamped run).
        super().__init_subclass__(**kwargs)
        fn = cls.__dict__.get("run")
        if fn is not None and callable(fn) \
                and not getattr(fn, "__action_report_instrumented__",
                                False):
            cls.run = _instrument_run(fn)

    def latest_entry(self, verb: str):
        """Latest IndexLogEntry, cached; raises if the log is empty or not an
        index entry (shared by the metadata-only actions)."""
        if self._latest_entry is None:
            from hyperspace_tpu.index.log_entry import IndexLogEntry
            entry = self.log_manager.get_latest_log()
            if not isinstance(entry, IndexLogEntry):
                raise HyperspaceException(f"No index found to {verb}.")
            self._latest_entry = entry
        return self._latest_entry

    @property
    def base_id(self) -> int:
        if self._base_id is None:
            latest = self.log_manager.get_latest_id()
            self._base_id = latest if latest is not None else -1
        return self._base_id

    @property
    @abstractmethod
    def transient_state(self) -> str: ...

    @property
    @abstractmethod
    def final_state(self) -> str: ...

    @abstractmethod
    def log_entry(self) -> LogEntry:
        """The record to persist (with state filled in by begin/end)."""

    def validate(self) -> None:
        """Override to gate on the current lifecycle state."""

    @abstractmethod
    def op(self) -> None:
        """The data-moving operation (may dispatch device work)."""

    def begin(self) -> None:
        entry = self.log_entry()
        entry.state = self.transient_state
        if not self.log_manager.write_log(self.base_id + 1, entry):
            raise HyperspaceException(
                "Another operation is in progress for this index "
                f"(log id {self.base_id + 1} already exists).")
        logger.info("Begin %s (log id %d, state %s)",
                    type(self).__name__, self.base_id + 1, self.transient_state)

    def end(self) -> None:
        entry = self.log_entry()
        entry.state = self.final_state
        if not self.log_manager.write_log(self.base_id + 2, entry):
            raise HyperspaceException(
                "Another operation is in progress for this index "
                f"(log id {self.base_id + 2} already exists).")
        self.log_manager.delete_latest_stable_log()
        self.log_manager.create_latest_stable_log(self.base_id + 2)
        logger.info("End %s (log id %d, state %s)",
                    type(self).__name__, self.base_id + 2, self.final_state)

    # -- action report plumbing -------------------------------------------

    def annotate_report(self, **detail) -> None:
        """Attach op-specific detail (rows, files, bytes, ...) to the
        in-flight action report; no-op outside `run()`."""
        if self._report is not None:
            self._report["detail"].update(detail)

    def _timed_phase(self, name: str, fn) -> None:
        # Fault-injection point at every phase BOUNDARY: a "crash" rule
        # matching `action.<Class>.<phase>` aborts just before that phase
        # runs — i.e. between the preceding phase and this one, the
        # stranded-writer scenario recovery must unwind.
        faults.fire(f"action.{type(self).__name__}.{name}")
        if self._report is None:  # phase called directly, not via run()
            fn()
            return
        t0 = time.perf_counter()
        with telemetry.span(f"hs.action.{type(self).__name__}.{name}",
                            "action"):
            fn()
        self._report["phases"][name] = round(time.perf_counter() - t0, 6)

    def _index_identity(self) -> str | None:
        """Best-effort index name for the report — whichever of the
        config / cached entries the action got far enough to hold."""
        try:
            cfg = getattr(self, "index_config", None)
            if cfg is not None and getattr(cfg, "index_name", None):
                return cfg.index_name
        except Exception:
            pass
        for attr in ("_entry", "_previous", "_latest_entry"):
            entry = getattr(self, attr, None)
            if entry is not None and getattr(entry, "name", None):
                return entry.name
        return None

    def _publish_report(self, report: dict) -> None:
        """Finalize + publish one action report: registry counters and
        the report ring always; a per-query telemetry event when a
        recorder is active; persisted next to the final log entry on
        success. Publishing must never mask the action's own outcome."""
        try:
            report["index"] = self._index_identity()
            if report["ok"] and self._base_id is not None:
                report["log_id"] = self._base_id + 2
            name = report["action"]
            reg = telemetry.get_registry()
            reg.counter(f"actions.{name}.runs").inc()
            reg.counter("actions.reports").inc()
            if not report["ok"]:
                reg.counter(f"actions.{name}.failures").inc()
            reg.histogram(f"actions.{name}.wall_s").observe(
                report["wall_s"])
            detail = report["detail"]
            if detail.get("rows"):
                reg.counter("actions.rows_indexed").inc(detail["rows"])
            if detail.get("bytes"):
                reg.counter("actions.bytes_written").inc(detail["bytes"])
            reg.record_action_report(report)
            telemetry.event("action", name, index=report["index"],
                            ok=report["ok"], wall_s=report["wall_s"])
            if report.get("log_id") is not None:
                self.log_manager.write_action_report(report["log_id"],
                                                     report)
        except Exception:
            logger.warning("Failed to publish action report for %s",
                           report.get("action"), exc_info=True)

    def run(self) -> None:
        self._timed_phase("validate", self.validate)
        self._timed_phase("begin", self.begin)
        self._timed_phase("op", self.op)
        self._timed_phase("end", self.end)

    run = _instrument_run(run)
