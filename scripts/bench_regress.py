#!/usr/bin/env python
"""Pre-merge perf gate: diff the newest bench artifact against the
previous one and exit nonzero on a regression — arriving WITH its own
diagnosis: any gate failure auto-runs the regression differ
(`telemetry/diff.py`) on the same pair and prints the ranked
attribution tree, so the reviewer sees *why*, not just *that*.

Two artifact families, one gate:

  python scripts/bench_regress.py                 # newest two BENCH_r*.json
  python scripts/bench_regress.py --tpcds         # newest two BENCH_TPCDS_r*.json
  python scripts/bench_regress.py OLD.json NEW.json
  python scripts/bench_regress.py --threshold 0.10 --glob 'BENCH_r*.json'

  python scripts/bench_regress.py --serve         # newest two BENCH_SERVE_r*.json

Rung artifacts (bench.py) gate per-rung `vs_baseline`, peak HBM growth,
the rung-1 link share, AND the warm-rung segment-cache bar: the
steady-state repeat run of each query rung must show ZERO
`link.h2d.chunks` (absolute gate — the healthy value is 0), and the
segment-cache hit rate must not drop >threshold. Query artifacts (bench_tpcds.py /
bench_tpch.py) gate the aggregate `vs_baseline` AND every per-query
`vs_baseline` — the r03->r04 TPC-DS regression (aggregate 3.14x ->
0.81x, q64 at 0.45x) is exactly the failure this mode exists to stop
at the door. The mode is detected from artifact content (`queries` vs
`rungs` vs `serve`), so explicit paths need no flag.

Serving artifacts (bench_serve.py, `--serve`) gate the closed-loop
scaling ratio (`vs_baseline` = K-client QPS / 1-client QPS), p99 and
p50 latency GROWTH, and the reject/timeout RATES — rates gate on
absolute movement (> 2 points), because a 0 -> 0.3 reject-rate jump is
exactly the regression a ratio gate on zero cannot see.

Artifacts must be in the canonical schema (`telemetry/artifact.py`,
`schema_version` + `process_metrics`); a legacy-schema artifact is
REFUSED with exit 2 — gating shapes that cannot be compared
mechanically is how the r04 regression went unnoticed for two rounds.
Migrate committed legacy rounds with
`python -m hyperspace_tpu.telemetry.artifact migrate FILE`.

Entries present in only one artifact are reported but never gate (a
new rung/query has no baseline; a removed one is a review question,
not a perf fact). The 15% default threshold leaves headroom for
run-to-run wobble of a shared host on sub-ratios near 1 (its size on
the current chip is unmeasured; see `link_probe` in bench_common.py)
while still catching real cliffs; artifacts carry the probe so a borderline failure can be
attributed to link vs code before overriding the gate.
"""

import argparse
import glob
import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
# A lint/diff tool over committed artifacts and source: it never needs the
# chip, and pinning the CPU keeps it (and the tests that shell out to it)
# from taking the one process slot a chip allows.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def load_artifact(path: str) -> dict:
    """Canonical-schema load; legacy artifacts are refused LOUDLY
    (exit 2) — the gate must never silently pass what it cannot
    mechanically compare."""
    from hyperspace_tpu.telemetry import artifact

    try:
        return artifact.load(path)
    except artifact.LegacyArtifactError as exc:
        print(f"bench_regress: REFUSING to gate a legacy-schema "
              f"artifact:\n  {exc}", file=sys.stderr)
        raise SystemExit(2)
    except (OSError, ValueError) as exc:
        raise SystemExit(f"{path}: not a bench artifact object ({exc})")


def _round_key(path: str):
    """Numeric round ordering: `_r9` sorts before `_r10` (a plain
    lexicographic sort would interleave them); non-round files sort
    last, then by name, so the newest ROUND is always picked."""
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    return (m is None, int(m.group(1)) if m else 0, path)


def pick_latest_two(pattern: str):
    paths = sorted(glob.glob(os.path.join(REPO_ROOT, pattern)),
                   key=_round_key)
    if len(paths) < 2:
        raise SystemExit(
            f"need at least two artifacts matching {pattern!r}; "
            f"found {len(paths)}")
    return paths[-2], paths[-1]


def _rung1_link_share(doc: dict):
    """(key_stage_link_s + perm_d2h_link_s) / build_s of rung 1 — the
    fraction of the build the device path spends on the link. The
    pipelined transfer engine exists to drive this DOWN; a >threshold
    rebound means the link seam regressed even if wall times still
    pass. None when the artifact predates the device-path phases."""
    r1 = (doc.get("rungs") or {}).get("1_build") or {}
    phases = r1.get("device_path") or {}
    stage = phases.get("key_stage_link_s")
    d2h = phases.get("perm_d2h_link_s")
    build = r1.get("build_s")
    if not all(isinstance(v, (int, float)) for v in (stage, d2h, build)) \
            or not build:
        return None
    return (stage + d2h) / build


# Reject/timeout RATES gate on absolute movement, not ratio: the
# healthy value is 0, and nothing ratio-gates against zero.
RATE_SLACK = 0.02

# Latency-anatomy absolutes (PR 17). The critical-path decomposition
# is sum-exact BY CONSTRUCTION (the host_python residual absorbs the
# unattributed remainder), so the only honest tolerance is rounding:
# segments round to 1 µs, nine of them. The profiler bound is the
# tentpole promise: "continuous" means cheap enough to leave on.
CRITPATH_EPSILON_S = 1e-4
PROFILER_OVERHEAD_MAX = 0.02


def _segment_rows(old: dict, new: dict, threshold: float):
    """Warm-rung gate rows from the `segments` block bench.py embeds:

    - `warm_h2d.<rung>` — the steady-state repeat run of each query
      rung must cross the link ZERO times (`link.h2d.chunks` delta).
      This gates on the NEW artifact alone and absolutely: the healthy
      value is 0, and nothing ratio-gates against zero (same logic as
      the serve rates).
    - `segment_hit_rate` — hits/(hits+misses) of the HBM segment cache
      over the whole ladder; a >threshold drop means repeat queries
      started re-paying decode+H2D even if walls still pass.
    """
    rows = []
    oseg = old.get("segments") or {}
    nseg = new.get("segments") or {}
    for rung, w in sorted((nseg.get("warm") or {}).items()):
        chunks = w.get("h2d_chunks")
        if not isinstance(chunks, (int, float)):
            continue
        ow = ((oseg.get("warm") or {}).get(rung) or {}).get("h2d_chunks")
        rows.append((f"warm_h2d.{rung}",
                     float(ow) if isinstance(ow, (int, float)) else 0.0,
                     float(chunks), float(chunks), chunks > 0))

    def rate(seg):
        hits, misses = seg.get("hits"), seg.get("misses")
        if not (isinstance(hits, (int, float))
                and isinstance(misses, (int, float))) \
                or hits + misses <= 0:
            return None
        return hits / (hits + misses)

    old_rate, new_rate = rate(oseg), rate(nseg)
    if old_rate and new_rate is not None:
        change = new_rate / old_rate - 1.0
        rows.append(("segment_hit_rate", old_rate, new_rate, change,
                     change < -threshold))
    return rows


def _skipping_rows(old: dict, new: dict):
    """Data-skipping gate row: the `5_data_skipping` rung's
    `files_pruned` must be > 0 in the NEW artifact (absolute gate, like
    the warm-H2D rows — the healthy value is never zero: a selective
    predicate over the clustered bench source must read strictly fewer
    files than the unindexed plan). Artifacts predating the rung are
    not gated."""
    r = (new.get("rungs") or {}).get("5_data_skipping") or {}
    fp = r.get("files_pruned")
    if not isinstance(fp, (int, float)):
        return []
    old_fp = ((old.get("rungs") or {}).get("5_data_skipping")
              or {}).get("files_pruned")
    return [("skipping_files_pruned",
             float(old_fp) if isinstance(old_fp, (int, float)) else 0.0,
             float(fp), float(fp), fp <= 0)]


def _spmd_rows(old: dict, new: dict):
    """One-architecture gate row: a TPC-DS artifact carrying the
    `spmd.fallbacks` counter must report ZERO (absolute — the healthy
    value is 0 and nothing ratio-gates against zero). A fallback means
    a bucketed SMJ with an active mesh dropped off the single-program
    SPMD lane, i.e. a second execution architecture crept back."""
    fb = (new.get("spmd") or {}).get("fallbacks")
    if not isinstance(fb, (int, float)):
        return []
    old_fb = (old.get("spmd") or {}).get("fallbacks")
    return [("spmd_fallbacks",
             float(old_fb) if isinstance(old_fb, (int, float)) else 0.0,
             float(fb), float(fb), fb > 0)]


def compare_multichip(old: dict, new: dict, threshold: float):
    """Multi-chip artifact gate rows (same row shape as `compare`):

    - `smj_speedup_8dev` — the 8-vs-1-device SMJ speedup must not drop
      >threshold between rounds (the scaling claim itself);
    - `warm_h2d.<n>dev` — the warm per-device read of each rung must
      cross the link ZERO times (absolute gate on the NEW artifact —
      the healthy value is 0 and nothing ratio-gates against zero);
    - `inter_stage_d2h.<q>@<n>dev` — a warm multi-stage query must
      record zero D2H link crossings between stages (absolute);
    - `bit_identical` — sharded results must equal the 1-device run
      (absolute: False fails regardless of history).

    Legacy MULTICHIP rounds (the migrated `{n_devices, rc, ok, tail}`
    smoke blobs) carry no `multichip` section: their rows report as
    not-gated, the new artifact's absolute rows still gate."""
    o = old.get("multichip") or {}
    n = new.get("multichip") or {}
    rows = []

    def ratio(name, old_v, new_v):
        if not (isinstance(old_v, (int, float))
                and isinstance(new_v, (int, float)) and old_v > 0):
            rows.append((name, old_v, new_v, None, False))
            return
        change = new_v / old_v - 1.0
        rows.append((name, old_v, new_v, change, change < -threshold))

    ratio("smj_speedup_8dev", o.get("smj_speedup"), n.get("smj_speedup"))
    if isinstance(n.get("smj_speedup"), (int, float)):
        # Absolute floor: the whole point of the rung — the widest mesh
        # must beat one device, this round, regardless of history.
        rows.append(("smj_speedup_floor", 1.0, n["smj_speedup"],
                     n["smj_speedup"] - 1.0, n["smj_speedup"] <= 1.0))
    # String-keyed SMJ (strings born-sharded, PR 13): gated exactly like
    # the numeric co-bucketed headline — ratio vs the previous round
    # when it carried the rung, plus the absolute >1x floor.
    ratio("string_smj_speedup", o.get("string_smj_speedup"),
          n.get("string_smj_speedup"))
    if isinstance(n.get("string_smj_speedup"), (int, float)):
        v = n["string_smj_speedup"]
        rows.append(("string_smj_speedup_floor", 1.0, v, v - 1.0,
                     v <= 1.0))
    for ndev, chunks in sorted((n.get("warm_h2d_chunks") or {}).items()):
        if isinstance(chunks, (int, float)):
            old_c = (o.get("warm_h2d_chunks") or {}).get(ndev)
            rows.append((f"warm_h2d.{ndev}dev",
                         float(old_c) if isinstance(old_c, (int, float))
                         else 0.0, float(chunks), float(chunks),
                         chunks > 0))
    for ndev, rung in sorted((n.get("devices") or {}).items()):
        for q, res in sorted((rung.get("queries") or {}).items()):
            d2h = res.get("inter_stage_d2h_chunks")
            if isinstance(d2h, (int, float)):
                rows.append((f"inter_stage_d2h.{q}@{ndev}dev", 0.0,
                             float(d2h), float(d2h), d2h > 0))
    bi = n.get("bit_identical")
    if bi is not None:
        rows.append(("bit_identical", 1.0, 1.0 if bi else 0.0,
                     0.0 if bi else -1.0, not bi))
    rows.extend(_multislice_rows(o, n, threshold))
    return rows


# Replica routing balance bar: at steady state no replica may take
# more than this share of routed queries (least-loaded routing that
# degenerates to one slice is replication paying HBM for nothing).
REPLICA_MAX_SHARE = 0.70
# Cross-slice byte-share bar: under the two-hop hierarchy each routed
# row crosses DCN at most once and ICI at most once, so the DCN share
# of a full re-bucket sits near 1/2 by construction (slab rounding adds
# a little). A share past this bar means the heavy fan-out inverted
# onto the slow axis — stage order or capacity sizing regressed.
DCN_BYTE_SHARE_MAX = 0.60


def _multislice_rows(o: dict, n: dict, threshold: float):
    """Multi-slice + replication gate rows (the scale-OUT section of
    the MULTICHIP artifact):

    - `multislice_qps_ratio` — concurrent-client aggregate QPS of the
      replicated multi-slice topology over the flat whole-mesh
      topology; absolute floor 1.0 (replication that loses to the flat
      mesh is the regression) plus the usual ratio-vs-previous-round;
    - `replica_max_share` — no replica may take > REPLICA_MAX_SHARE of
      routed queries at steady state (absolute);
    - `dcn_byte_share` — cross-slice DCN bytes /
      (ICI + DCN) of the in-program repartitions must stay under
      DCN_BYTE_SHARE_MAX (absolute — the hierarchy's point is that the
      heavy hop rides ICI);
    - `multislice_warm_h2d` / `multislice_spmd_fallbacks` /
      `multislice_bit_identical` — the flat-lane absolutes, re-asserted
      on the replicated grid. Rounds predating the section gate
      nothing."""
    om = o.get("multislice") or {}
    nm = n.get("multislice") or {}
    rows = []
    if not nm:
        return rows
    ratio = nm.get("qps_ratio")
    if isinstance(ratio, (int, float)):
        rows.append(("multislice_qps_floor", 1.0, ratio, ratio - 1.0,
                     ratio < 1.0))
        old_r = om.get("qps_ratio")
        if isinstance(old_r, (int, float)) and old_r > 0:
            change = ratio / old_r - 1.0
            rows.append(("multislice_qps_ratio", old_r, ratio, change,
                         change < -threshold))
    share = nm.get("replica_max_share")
    if isinstance(share, (int, float)):
        rows.append(("replica_max_share", REPLICA_MAX_SHARE, share,
                     share - REPLICA_MAX_SHARE,
                     share > REPLICA_MAX_SHARE))
    dcn = nm.get("dcn_byte_share")
    if isinstance(dcn, (int, float)):
        rows.append(("dcn_byte_share", DCN_BYTE_SHARE_MAX, dcn,
                     dcn - DCN_BYTE_SHARE_MAX, dcn > DCN_BYTE_SHARE_MAX))
    wh = nm.get("warm_h2d_chunks")
    if isinstance(wh, (int, float)):
        rows.append(("multislice_warm_h2d", 0.0, float(wh), float(wh),
                     wh > 0))
    fb = nm.get("spmd_fallbacks")
    if isinstance(fb, (int, float)):
        rows.append(("multislice_spmd_fallbacks", 0.0, float(fb),
                     float(fb), fb > 0))
    bi = nm.get("bit_identical")
    if bi is not None:
        rows.append(("multislice_bit_identical", 1.0,
                     1.0 if bi else 0.0, 0.0 if bi else -1.0, not bi))
    return rows


def compare_advisor(old: dict, new: dict, threshold: float):
    """Advisor-rung gate rows (same row shape as `compare`):

    - `advisor_built` — the cycle must have auto-built at least one
      index (absolute: a run that recommends but never builds has not
      closed the loop);
    - `advisor_bytes_reduction` — the recommended index must REDUCE
      scanned bytes on the repeat workload (absolute > 0), and must not
      drop >threshold vs the previous round;
    - `advisor_bit_identical` — index-served results must equal the
      unindexed run (absolute: False fails regardless of history);
    - `advisor_rule_applied` — the rebuilt workload must actually be
      SERVED by an index (rule-usage telemetry > 0, absolute)."""
    o = old.get("advisor") or {}
    n = new.get("advisor") or {}
    rows = []
    built = n.get("built")
    if isinstance(built, (int, float)):
        ob = o.get("built")
        rows.append(("advisor_built",
                     float(ob) if isinstance(ob, (int, float)) else 0.0,
                     float(built), float(built), built < 1))
    red = n.get("bytes_reduction")
    if isinstance(red, (int, float)):
        rows.append(("advisor_bytes_reduction_floor", 0.0, float(red),
                     float(red), red <= 0))
        ored = o.get("bytes_reduction")
        if isinstance(ored, (int, float)) and ored > 0:
            change = red / ored - 1.0
            rows.append(("advisor_bytes_reduction", float(ored),
                         float(red), change, change < -threshold))
    applied = n.get("rule_applied_after")
    if isinstance(applied, (int, float)):
        rows.append(("advisor_rule_applied", 0.0, float(applied),
                     float(applied), applied < 1))
    bi = n.get("bit_identical")
    if bi is not None:
        rows.append(("advisor_bit_identical", 1.0, 1.0 if bi else 0.0,
                     0.0 if bi else -1.0, not bi))
    return rows


# --ingest gate bounds. Staleness at the committed append rate must
# stay under the alert rule's firing threshold (an artifact that ships
# already-alerting staleness is a regression by definition), and the
# ingest lap's p99 may cost at most this multiple of the quiet lap.
# The degradation cap is a coarse backstop, not a target: in a
# single-process GIL-bound engine the refresh's sketch/bucket work
# inevitably stalls concurrent clients (measured ~25-35x at the
# committed rate), so the cap only catches runaway regressions —
# the old-vs-new p99_degradation_x ratio row is the tight gate.
INGEST_STALENESS_MAX_S = 30.0
INGEST_P99_DEGRADATION_MAX = 60.0
INGEST_WARM_HIT_RATE_FLOOR = 0.5


def compare_ingest(old: dict, new: dict, threshold: float):
    """Continuous-ingest gate rows (PR 19): the staleness-vs-p99
    frontier must not regress, and the chaos/warm-set ABSOLUTE wins the
    plane exists for stay won:

    - `p99_degradation_x` — ingest-lap p99 over quiet-lap p99, ratio
      vs the previous artifact plus an absolute ceiling;
    - `staleness_max_s` — worst staleness at the committed append
      rate, ratio when history is nonzero plus the absolute alert
      bound (nothing ratio-gates against zero);
    - `chaos_{mismatches,stuck,stranded}` — crash + transient
      injection mid-refresh under load: zero wrong answers, zero stuck
      clients, zero non-ACTIVE op-log leftovers after recovery;
    - `warm_hit_rate` / `segments_rekeyed` — sustained append must not
      collapse the segment cache: hit rate holds the floor and version
      rekeying actually ran (rekeyed == 0 means every flip dumped the
      warm set)."""
    o = old.get("ingest") or {}
    n = new.get("ingest") or {}
    rows = []

    def add(name, old_v, new_v, lower_is_better=False):
        if not (isinstance(old_v, (int, float))
                and isinstance(new_v, (int, float)) and old_v > 0):
            return
        change = new_v / old_v - 1.0
        gated = (change > threshold if lower_is_better
                 else change < -threshold)
        rows.append((name, old_v, new_v, change, gated))

    add("p99_degradation_x", o.get("p99_degradation_x"),
        n.get("p99_degradation_x"), lower_is_better=True)
    add("quiet_p99_s", (o.get("quiet") or {}).get("p99_s"),
        (n.get("quiet") or {}).get("p99_s"), lower_is_better=True)
    add("staleness_max_s",
        (o.get("committed_rate") or {}).get("staleness_max_s"),
        (n.get("committed_rate") or {}).get("staleness_max_s"),
        lower_is_better=True)

    chaos = n.get("chaos") or {}
    for key, label in (("mismatches", "chaos_mismatches"),
                       ("stuck_threads", "chaos_stuck"),
                       ("stranded_entries", "chaos_stranded")):
        v = chaos.get(key)
        if isinstance(v, (int, float)):
            rows.append((label, 0.0, float(v), float(v), v > 0))

    seg = n.get("segcache") or {}
    hit_rate = seg.get("warm_hit_rate")
    if isinstance(hit_rate, (int, float)):
        rows.append(("warm_hit_rate", INGEST_WARM_HIT_RATE_FLOOR,
                     float(hit_rate),
                     float(hit_rate) - INGEST_WARM_HIT_RATE_FLOOR,
                     hit_rate < INGEST_WARM_HIT_RATE_FLOOR))
    rekeyed = seg.get("rekeyed")
    if isinstance(rekeyed, (int, float)):
        rows.append(("segments_rekeyed", 1.0, float(rekeyed),
                     float(rekeyed), rekeyed <= 0))

    staleness = (n.get("committed_rate") or {}).get("staleness_max_s")
    if isinstance(staleness, (int, float)):
        rows.append(("staleness_abs_s", INGEST_STALENESS_MAX_S,
                     float(staleness), float(staleness),
                     staleness > INGEST_STALENESS_MAX_S))
    degradation = n.get("p99_degradation_x")
    if isinstance(degradation, (int, float)):
        rows.append(("p99_degradation_abs", INGEST_P99_DEGRADATION_MAX,
                     float(degradation), float(degradation),
                     degradation > INGEST_P99_DEGRADATION_MAX))
    return rows


def compare_serve(old: dict, new: dict, threshold: float):
    """Serving-artifact gate rows (same row shape as `compare`):
    scaling ratio + QPS drop >threshold, p50/p99 growth >threshold,
    reject/timeout rate growth > RATE_SLACK absolute — plus, for
    artifacts carrying the batched-execution sections (PR 12), the
    ABSOLUTE wins the lane exists for:

    - `scaling_floor` — the 8-client closed loop must BEAT serial
      (`vs_baseline >= 1.0`; concurrency that loses is the regression,
      whatever history said);
    - `batch_occupancy` — `serve.batch.members / serve.batch.
      invocations` on the concurrent rung must exceed 1 (an occupancy
      of exactly 1 means the lane ran but never coalesced anything);
    - `aot_warm_traces` — the AOT-warmed replica phase must record
      ZERO new `compile.traces` (absolute, like the warm-H2D rows:
      the healthy value is 0 and nothing ratio-gates against zero);
    - `window_p99_agreement` / `slo_burn` — operations-plane rounds
      (PR 15): the sampler's sliding-window p99 must agree with the
      closed-loop percentile within the log2-bucket + population
      slack, and the steady-state SLO burn rate must not exceed 1.0;
    - `tenant_victim_p99_x` / `tenant_mismatches` / `tenant_deadlock`
      / `tenant_chargeback_exact` — multi-tenant rounds (PR 16): the
      victim tenant's co-located p99 stays <= 2x solo, chaos costs no
      correctness or liveness, and per-tenant chargeback sums equal
      the global counters exactly;
    - `critpath_sum_exact` / `profiler_overhead` — latency-anatomy
      rounds (PR 17): every sweep rate's stamped p99 decomposition
      sums to its measured wall within CRITPATH_EPSILON_S, and the
      sampling profiler costs <= PROFILER_OVERHEAD_MAX of closed-loop
      QPS;
    - `clean_run_incidents` — incident-plane rounds (PR 18): zero
      alert incidents fired during the timed closed loop (the
      false-positive gate on the default rule set).

    Absolute rows gate on the NEW artifact alone; rounds predating the
    sections are not gated on them."""
    o = old.get("serve") or {}
    n = new.get("serve") or {}
    rows = []

    def add(name, old_v, new_v, lower_is_better=False):
        if not (isinstance(old_v, (int, float))
                and isinstance(new_v, (int, float)) and old_v > 0):
            return
        change = new_v / old_v - 1.0
        gated = (change > threshold if lower_is_better
                 else change < -threshold)
        rows.append((name, old_v, new_v, change, gated))

    add("scaling_ratio", old.get("vs_baseline"), new.get("vs_baseline"))
    add("qps", o.get("qps"), n.get("qps"))
    add("p50_s", o.get("p50_s"), n.get("p50_s"), lower_is_better=True)
    add("p99_s", o.get("p99_s"), n.get("p99_s"), lower_is_better=True)
    for rate in ("reject_rate", "timeout_rate"):
        ov, nv = o.get(rate), n.get(rate)
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)):
            delta = nv - ov
            rows.append((rate, ov, nv, delta, delta > RATE_SLACK))

    vb = new.get("vs_baseline")
    if isinstance(vb, (int, float)) and ("batch" in n or "aot" in n):
        rows.append(("scaling_floor", 1.0, vb, vb - 1.0, vb < 1.0))
    b = n.get("batch") or {}
    inv, mem = b.get("invocations"), b.get("members")
    if isinstance(inv, (int, float)) and isinstance(mem, (int, float)):
        occ = (mem / inv) if inv > 0 else 0.0
        rows.append(("batch_occupancy", 1.0, occ, occ - 1.0, occ <= 1.0))
    a = n.get("aot") or {}
    wt = a.get("warm_traces")
    if isinstance(wt, (int, float)):
        rows.append(("aot_warm_traces", 0.0, float(wt), float(wt),
                     wt > 0))
    # Operations-plane gates (rounds predating the sections skip):
    # - `window_p99_agreement` — the timeseries sampler's sliding-
    #   window p99 over the timed closed loop must agree with the
    #   client-measured percentile. The window value is a log2-bucket
    #   UPPER bound (within 2x above the truth by construction), and
    #   the two populations differ slightly (server walls vs client
    #   walls), so the gate allows 4x each way: outside that, the
    #   window math or the sampling itself broke.
    # - `slo_burn` — the closed loop ran with the SLO window reset at
    #   the timed-loop start, so a burn rate above 1.0 means the
    #   steady-state serving round violated its own p99 objective
    #   (absolute — the healthy value is ~0 and nothing ratio-gates
    #   against zero).
    wp, cp = n.get("window_p99_s"), n.get("p99_s")
    if isinstance(wp, (int, float)) and isinstance(cp, (int, float)) \
            and wp > 0 and cp > 0:
        ratio = wp / cp
        rows.append(("window_p99_agreement", cp, wp, ratio - 1.0,
                     not (0.25 <= ratio <= 4.0)))
    burn = (n.get("slo") or {}).get("burn_rate")
    if isinstance(burn, (int, float)):
        rows.append(("slo_burn", 1.0, float(burn), float(burn) - 1.0,
                     burn > 1.0))
    # Multi-tenant gates (PR 16; rounds predating `--tenants` skip the
    # section rows, but chargeback exactness gates on ANY new artifact
    # that carries the `tenant_cost` digest):
    # - `tenant_victim_p99_x` — the victim tenant's p99 co-located
    #   with the greedy + doomed tenants must stay <= 2x its solo p99
    #   (absolute: the isolation promise the weighted-fair queue and
    #   per-tenant quotas exist for);
    # - `tenant_mismatches` / `tenant_deadlock` — chaos must not cost
    #   correctness or liveness (healthy values 0/false);
    # - `tenant_chargeback_exact` — per-tenant chargeback sums must
    #   equal the global device/link/cache counters exactly.
    tn = n.get("tenants") or {}
    solo = tn.get("victim_solo_p99_s")
    coloc = tn.get("victim_coloc_p99_s")
    if isinstance(solo, (int, float)) and solo > 0 \
            and isinstance(coloc, (int, float)):
        x = coloc / solo
        rows.append(("tenant_victim_p99_x", 2.0, round(x, 3),
                     x - 2.0, x > 2.0))
    mm = tn.get("mismatches")
    if isinstance(mm, (int, float)):
        rows.append(("tenant_mismatches", 0.0, float(mm), float(mm),
                     mm > 0))
    dl = tn.get("deadlock")
    if isinstance(dl, bool):
        rows.append(("tenant_deadlock", 0.0, float(dl), float(dl), dl))
    cb = tn.get("chargeback") or new.get("tenant_cost") or {}
    exact = cb.get("exact")
    if isinstance(exact, bool):
        rows.append(("tenant_chargeback_exact", 1.0, float(exact),
                     float(exact) - 1.0, not exact))
    ol = n.get("open_loop") or {}
    slo_qps = ol.get("qps_at_p99_slo")
    oslo = (old.get("serve") or {}).get("open_loop") or {}
    if isinstance(slo_qps, (int, float)):
        add("qps_at_p99_slo", oslo.get("qps_at_p99_slo"), slo_qps)
        if not isinstance(oslo.get("qps_at_p99_slo"), (int, float)):
            rows.append(("qps_at_p99_slo_floor", 0.0, float(slo_qps),
                         float(slo_qps), slo_qps <= 0))
    # Latency-anatomy gates (PR 17; rounds predating the sections skip):
    # - `critpath_sum_exact` — every sweep rate's stamped p99 query
    #   must satisfy the sum-exactness contract (segments sum to the
    #   measured wall within CRITPATH_EPSILON_S — absolute: the
    #   decomposition's one invariant, and a nonzero error means a
    #   segment was double-counted or dropped);
    # - `profiler_overhead` — the closed-loop QPS with the sampling
    #   profiler ON must stay within PROFILER_OVERHEAD_MAX of
    #   profiler-off (absolute: the price of always-on visibility is
    #   part of the contract, not a footnote).
    errs = [e["critical_path"]["p99_sum_error_s"]
            for e in (ol.get("sweep") or [])
            if isinstance((e.get("critical_path") or {})
                          .get("p99_sum_error_s"), (int, float))]
    if errs:
        worst = max(errs)
        rows.append(("critpath_sum_exact", CRITPATH_EPSILON_S, worst,
                     worst - CRITPATH_EPSILON_S,
                     worst > CRITPATH_EPSILON_S))
    ovh = (n.get("profiler") or {}).get("overhead_fraction")
    if isinstance(ovh, (int, float)):
        rows.append(("profiler_overhead", PROFILER_OVERHEAD_MAX,
                     float(ovh), ovh - PROFILER_OVERHEAD_MAX,
                     ovh > PROFILER_OVERHEAD_MAX))
    # Incident-plane gate (PR 18; rounds predating the `alerts` digest
    # skip): `clean_run_incidents` — the timed closed loop is a clean,
    # correctly-sized lap, so ANY incident fired during it is a false
    # positive of the alert rules (absolute: the healthy value is 0 and
    # nothing ratio-gates against zero). The open-loop sweep past the
    # knee may legitimately fire; those land in the digest but do not
    # gate.
    cf = (n.get("alerts") or {}).get("clean_run_fired")
    if isinstance(cf, (int, float)):
        rows.append(("clean_run_incidents", 0.0, float(cf), float(cf),
                     cf > 0))
    return rows


def compare(old: dict, new: dict, threshold: float):
    """[(name, old_ratio, new_ratio, change, gated)] for every
    comparable vs_baseline (higher is better), headline first — rungs
    for rung artifacts, per-query rows for query artifacts — plus the
    peak-HBM row and the rung-1 link share (both lower is better —
    they gate on GROWTH)."""
    rows = []

    def add(name, old_v, new_v, lower_is_better=False):
        if not (isinstance(old_v, (int, float))
                and isinstance(new_v, (int, float)) and old_v > 0):
            return
        change = new_v / old_v - 1.0
        gated = (change > threshold if lower_is_better
                 else change < -threshold)
        rows.append((name, old_v, new_v, change, gated))

    add("headline", old.get("vs_baseline"), new.get("vs_baseline"))
    for section, prefix in (("rungs", ""), ("queries", "")):
        old_entries = old.get(section) or {}
        new_entries = new.get(section) or {}
        for entry in sorted(set(old_entries) | set(new_entries)):
            o, n = old_entries.get(entry), new_entries.get(entry)
            if o is None or n is None:
                rows.append((prefix + entry,
                             (o or {}).get("vs_baseline"),
                             (n or {}).get("vs_baseline"), None, False))
                continue
            add(prefix + entry, o.get("vs_baseline"),
                n.get("vs_baseline"))
    add("peak_hbm_bytes",
        (old.get("memory") or {}).get("peak_hbm_bytes"),
        (new.get("memory") or {}).get("peak_hbm_bytes"),
        lower_is_better=True)
    add("rung1_link_share", _rung1_link_share(old),
        _rung1_link_share(new), lower_is_better=True)
    rows.extend(_segment_rows(old, new, threshold))
    rows.extend(_skipping_rows(old, new))
    rows.extend(_spmd_rows(old, new))
    return rows


def print_attribution(old: dict, new: dict, old_path: str,
                      new_path: str) -> None:
    """The failed gate's own diagnosis: run the differ on the gated
    pair and print the ranked attribution tree."""
    from hyperspace_tpu.telemetry import diff

    d = diff.diff_artifacts(old, new,
                            old_name=os.path.basename(old_path),
                            new_name=os.path.basename(new_path))
    print()
    print(d.format_tree())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("artifacts", nargs="*",
                    help="explicit OLD NEW artifact paths")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="max tolerated vs_baseline drop (default 0.15)")
    ap.add_argument("--glob", default=None,
                    help="artifact family when paths are not given "
                         "(default BENCH_r*.json)")
    ap.add_argument("--tpcds", action="store_true",
                    help="gate the TPC-DS macro-bench family "
                         "(BENCH_TPCDS_r*.json) instead of the "
                         "micro-rung ladder")
    ap.add_argument("--serve", action="store_true",
                    help="gate the serving-bench family "
                         "(BENCH_SERVE_r*.json): scaling ratio, QPS, "
                         "p50/p99 latency growth, reject/timeout "
                         "rates")
    ap.add_argument("--advisor", action="store_true",
                    help="gate the index-advisor family "
                         "(BENCH_ADVISOR_r*.json): at least one "
                         "auto-built index, scanned-bytes reduction, "
                         "index-served repeats, bit-identity")
    ap.add_argument("--ingest", action="store_true",
                    help="gate the continuous-ingest family "
                         "(BENCH_INGEST_r*.json): staleness-vs-p99 "
                         "frontier, chaos zeros, warm hit-rate floor, "
                         "p99 degradation vs the quiet lap")
    ap.add_argument("--multichip", action="store_true",
                    help="gate the multi-chip scaling family "
                         "(MULTICHIP_r*.json): 8-device SMJ speedup, "
                         "per-device warm link-freedom, inter-stage "
                         "D2H, bit-identity vs 1 device")
    ap.add_argument("--no-diff", action="store_true",
                    help="skip the attribution tree on gate failure")
    args = ap.parse_args()

    if len(args.artifacts) == 2:
        old_path, new_path = args.artifacts
    elif not args.artifacts:
        pattern = args.glob or ("MULTICHIP_r*.json" if args.multichip
                                else "BENCH_ADVISOR_r*.json"
                                if args.advisor
                                else "BENCH_INGEST_r*.json"
                                if args.ingest
                                else "BENCH_SERVE_r*.json" if args.serve
                                else "BENCH_TPCDS_r*.json" if args.tpcds
                                else "BENCH_r*.json")
        old_path, new_path = pick_latest_two(pattern)
    else:
        ap.error("pass exactly two artifact paths, or none for auto")

    old = load_artifact(old_path)
    new = load_artifact(new_path)
    # Serving / multichip artifacts are content-detected like the other
    # families, so explicit paths gate correctly without the flag.
    serve_mode = args.serve or ("serve" in old and "serve" in new)
    multichip_mode = args.multichip or "multichip" in new
    advisor_mode = args.advisor or "advisor" in new
    ingest_mode = args.ingest or "ingest" in new
    rows = (compare_multichip(old, new, args.threshold) if multichip_mode
            else compare_advisor(old, new, args.threshold)
            if advisor_mode
            else compare_ingest(old, new, args.threshold)
            if ingest_mode
            else compare_serve(old, new, args.threshold) if serve_mode
            else compare(old, new, args.threshold))

    print(f"bench_regress: {os.path.basename(old_path)} -> "
          f"{os.path.basename(new_path)} "
          f"(gate: vs_baseline drop > {args.threshold:.0%})")
    regressions = []
    for name, old_v, new_v, change, gated in rows:
        if change is None:
            print(f"  {name:18s} {old_v!s:>9} -> {new_v!s:>9}   "
                  "(not in both artifacts; not gated)")
            continue
        flag = "REGRESSION" if gated else "ok"
        print(f"  {name:18s} {old_v:9.3f} -> {new_v:9.3f}   "
              f"{change:+7.1%}  {flag}")
        if gated:
            regressions.append(name)
    if regressions:
        if not args.no_diff:
            print_attribution(old, new, old_path, new_path)
        print(f"bench_regress: FAILED — {len(regressions)} gate(s) "
              f"regressed >{args.threshold:.0%}: "
              + ", ".join(regressions), file=sys.stderr)
        return 1
    print("bench_regress: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
