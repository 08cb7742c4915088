"""The operation `q12_hybrid`: `q12`'s query and plain reference
(`reference/q12.py`, no copy) over a lake that took appends after its
indexes were built, served through upstream's Hybrid Scan: each join
side is its covering index's version directory UNION the files appended
since the build, and nothing else.

`select.off_lane` calls any query with a scan outside a `v__=`
directory off its lane, which every hybrid query is; so this op holds a
query to its lanes by what the program's own record of it says:

- `off_lane_queries`: the mix's `lanes`, each key optional: `index_scan`
  (the lanes of the scans of index version directories, in the
  operators' order), `appended_scan` (of the scans of appended source
  files), `joins` (the join operators that ran as operators of their
  own, by name), `join` (the lane of each `SortMergeJoin`), `fusion`
  (the lane of each fused stage, sorted: a broadcast join inside a
  stage's one program shows here, and one that declined as `eager`), `broadcast` ([path, lane] of each join planned
  as a broadcast join, where the program's record says: `fused`,
  `direct-address`, or `counting` where it declined to the counting
  join) and `shuffles` (the Exchange and Sort operators, exactly);
- `unindexed_queries`: queries in which a side was NOT served from its
  index version plus its appended files: no `JoinIndexRule` `applied`
  event naming the mix's two indexes, an index of the event that no scan
  read, or a scan of source files that read another number of files
  than the event's `appended_files` for that side (the silent fall to
  the whole source reads them all);
- `appended_files_unread`: per query, the files the driver landed less
  the appended files its scans read (each table's once, however many
  branches scan them), summed.

All three have the limit 0. The numbers come from the query's
QueryMetrics (the rule's event, every `Scan` operator's `roots`, `lane`
and `files_scanned`), which every commit that has hybrid scan writes.

The CPU rehearsals lower `execution.min.device.rows` to 0, which puts
every scan and join on the device lane and every broadcast join into a
fused stage: where the session's conf holds that 0, only `shuffles` of
the lanes is held (the three counts above are).
"""

from __future__ import annotations

import json
import os

from lib import plugins
from lib.lake import note

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JOINS = ("SortMergeJoin", "BroadcastHashJoin")
SERVED = ("path", "lane", "probe_rows", "build_rows")
MIN_DEVICE_ROWS = "spark.hyperspace.execution.min.device.rows"


def _is_index(roots) -> bool:
    return bool(roots) and all("v__=" in r for r in roots)


class Op(plugins.load(_BENCH, "ops", "q12").Op):
    def __init__(self, spec: dict, deployment, seed: int, spans):
        super().__init__(spec, deployment, seed, spans)
        self.rehearsal = str(deployment.sess.conf.get(
            MIN_DEVICE_ROWS, "")) == "0"
        if self.rehearsal:
            note(f"{MIN_DEVICE_ROWS} is 0 (a CPU rehearsal): the mix's lane "
                 f"lists are not held")
        refresh = deployment.config["refresh"]
        self.landed = {t: int(refresh["sets"])
                       * int(refresh.get("files_per_table_per_set", 1))
                       for t in spec["tables"]}

    def of_metrics(self, metrics) -> dict:
        """The rule's word on each side, and every scan and join of the
        query as its operator record shows it."""
        applied = [e for e in metrics.events_of("rule", "JoinIndexRule")
                   if e.get("action") == "applied"]
        ops = metrics.operators
        self.last_metrics = metrics
        return {
            "rule": [{k: ix.get(k) for k in ("name", "side", "root",
                                             "appended_files",
                                             "deleted_files")}
                     for e in applied for ix in e.get("indexes", ())],
            "scans": [{"roots": list(op.detail.get("roots") or ()),
                       "lane": op.detail.get("lane"),
                       "files": op.detail.get("files_scanned")}
                      for op in ops if op.name == "Scan"],
            "joins": [op.name for op in ops if op.name in JOINS],
            # where the program says so itself: each join that was
            # planned as a broadcast join, and the path that served it
            # (a fused one by its event, an eager one on its record)
            "broadcast": [{k: said.get(k) for k in SERVED} for said in (
                metrics.events_of("join", "broadcast")
                + [op.detail for op in ops if op.name == "BroadcastHashJoin"
                   and "path" in op.detail])]}

    def run(self, i: int, traced: bool = False, warming: bool = False) -> dict:
        rec = super().run(i, traced, warming)
        if warming:
            note(f"op {i}: rule {rec['rule']}; scans "
                 f"{[(os.path.basename(s['roots'][0]) if s['roots'] else None, s['lane'], s['files']) for s in rec['scans']]}; "
                 f"broadcast {rec['broadcast']}; lanes "
                 f"{json.dumps(self.hybrid_lanes(rec))}")
            if i == self.warm_ops - 1:
                # the last warm-up query's tree (operators only, lines
                # cut short), for whoever reads the run's notes
                tree = self.last_metrics.format_tree().split("Events:")[0]
                note("tree of op %d:\n%s" % (i, "\n".join(
                    line[:180] for line in tree.splitlines())))
        self.last_metrics = None
        return rec

    # -- the comparison ---------------------------------------------------

    def hybrid_lanes(self, rec: dict) -> dict:
        scans = rec["scans"]
        return {"index_scan": [s["lane"] for s in scans
                               if _is_index(s["roots"])],
                "appended_scan": [s["lane"] for s in scans
                                  if not _is_index(s["roots"])],
                "joins": rec["joins"],
                "join": rec["lanes"]["join"],
                "fusion": sorted(str(x) for x in rec["lanes"]["fusion"]),
                "broadcast": [[b["path"], b["lane"]]
                              for b in rec["broadcast"]],
                "shuffles": rec["lanes"]["shuffles"]}

    def off_lane(self, rec: dict) -> bool:
        """Whether the query ran elsewhere than the mix's `lanes` say.
        `shuffles` is held always; the lane lists where the session is
        not a rehearsal's; `broadcast` only where the program's record
        says anything of its broadcast joins (a commit before the
        annotation says nothing, and is held by `joins` and `fusion`)."""
        want, got = self.spec.get("lanes", {}), self.hybrid_lanes(rec)
        if got["shuffles"] != list(want.get("shuffles", [])):
            return True
        if self.rehearsal:
            return False
        held = [k for k in ("index_scan", "appended_scan", "joins", "join",
                            "fusion") if k in want]
        if "broadcast" in want and got["broadcast"]:
            held.append("broadcast")
        return any(got[k] != want[k] for k in held)

    def _table_of(self, roots) -> str:
        """The table a scan of source files reads: the one whose
        directory its root is."""
        for t in self.spec["tables"]:
            if any(os.path.basename(r.rstrip("/")) == t for r in roots):
                return t
        return ""

    def appended_read(self, rec: dict) -> dict:
        """{table: appended files read}: the most any one scan of that
        table's source files read."""
        read = dict.fromkeys(self.landed, 0)
        for s in rec["scans"]:
            t = self._table_of(s["roots"])
            if not _is_index(s["roots"]) and t in read:
                read[t] = max(read[t], int(s["files"] or 0))
        return read

    def unindexed(self, rec: dict) -> bool:
        sides = {ix["name"]: ix for ix in rec["rule"]}
        if set(sides) != set(self.spec["indexes"]):
            return True
        index_roots = {r for s in rec["scans"] if _is_index(s["roots"])
                       for r in s["roots"]}
        if any(ix["root"] not in index_roots or ix["deleted_files"]
               for ix in sides.values()):
            return True
        appended = {self.dep.config["indexes"][n]["table"]:
                    int(ix["appended_files"] or 0)
                    for n, ix in sides.items()}
        source = [s for s in rec["scans"] if not _is_index(s["roots"])]
        return (not source
                or any(int(s["files"] or 0)
                       != appended.get(self._table_of(s["roots"]))
                       for s in source))

    def check(self, records: list) -> dict:
        compared = super().check(records)
        unread = 0
        for rec in records:
            read = self.appended_read(rec)
            unread += sum(max(0, n - read[t])
                          for t, n in self.landed.items())
        compared.update({
            "unindexed_queries": [sum(self.unindexed(r) for r in records),
                                  0],
            "appended_files_unread": [unread, 0]})
        return compared
