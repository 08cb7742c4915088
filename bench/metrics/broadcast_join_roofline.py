"""The broadcast joins' share of the HBM roofline: the least time the
chip could take for the bytes that the equi-joins which ran as
direct-address probes on the device must move, over the device time of
the ops under the scope `hs.join.broadcast`.

Which joins those are is the program's own word, per query: the
`join`/`broadcast` events of a fused stage and the `BroadcastHashJoin`
operator records whose `path` is `direct-address` on the device lane
(`ops/q12_hybrid.of_metrics` keeps both under `broadcast`). A join that
declined to the counting join or ran on the host lane is in neither the
bytes nor the time. The bytes are counted here, from shapes alone, and
are the same whatever implements the join: each side's 8-byte key read
once as the join was handed it (`probe_rows`, `build_rows`: a filter
fused into the same stage has not shrunk the probe side yet), and an
index vector pair written per pair found. The pairs found are not in the
events; for TPC-H's refresh function they are known from the keys: an
appended side's keys lie above every indexed key, so a join of an index
side with an appended side finds none, and the term is 0. Bytes of one
traced query (the first record's) over the median query's device time.
None where there is no device plane, no such event or no op under the
scope; never 0."""

from lib import program_spans, roofline

KEY_BYTES = 8


def least_bytes(broadcast: list) -> int:
    return sum(KEY_BYTES * (int(b["probe_rows"]) + int(b["build_rows"]))
               for b in broadcast
               if b.get("lane") == "device"
               and b.get("path") in ("fused", "direct-address"))


def compute(run):
    ms = program_spans.scope_device_ms(run, "hs.join.broadcast")
    n_bytes = least_bytes(run["records"][0].get("broadcast") or ())
    if not ms or not n_bytes:
        return None
    return roofline.share_pct(n_bytes, ms * 1e-3, run["device_kind"])
