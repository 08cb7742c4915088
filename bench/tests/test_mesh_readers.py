"""The five readers of the four-chip cell over a four-plane trace made
here: an `.xplane.pb` written field by field (tsl's xplane.proto, the
fields `lib/program_spans.py` lists), reduced by `trace_reduce.reduce`
like a chip's. Mean over the planes, chip-seconds summed in the
roofline, the busiest chip's collectives, and None (never 0) where
nothing ran."""

import os

import pyarrow as pa
import pytest

from conftest import plug
from lib import mesh_planes, roofline, trace_reduce

CELL = "x4_cell"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, lines: dict) -> bytes:
    """{line name: [(event name, start_s, dur_s), ...]} as one XPlane."""
    ids, body = {}, _field(2, name)
    for line, events in lines.items():
        msg = _field(2, line) + _field(3, 0)
        for ev, start, dur in events:
            mid = ids.setdefault(ev, len(ids) + 1)
            msg += _field(4, _field(1, mid)
                          + _field(2, round(start * 1e12))
                          + _field(3, round(dur * 1e12)))
        body += _field(3, msg)
    for ev, mid in ids.items():
        body += _field(4, _field(1, mid)
                       + _field(2, _field(1, mid) + _field(2, ev)))
    return body


# window 0..10 s; two whole queries (1..4, 5..8) and one cut off (9..11).
# Every chip runs the join for 1.0 s a query; chip 2 runs 0.4 s more of
# other work and 0.05 + 0.03 s of collectives a query.
QUERIES = (1.0, 5.0, 9.0)


def _device(chip: int) -> dict:
    modules, ops = [], []
    for q in QUERIES:
        modules += [("jit_spmd_join(123)", q + 0.1, 0.9),
                    ("jit__take_flat_i32(7)", q + 1.0, 0.1),
                    ("jit_aggregate_step(9)", q + 1.2, 0.2)]
        ops += [("%sort.1 = sort(...)", q + 0.1, 0.9),
                ("%gather.2 = gather(...)", q + 1.0, 0.1),
                ("%fusion.3 = fusion(%all-reduce.9)", q + 1.2, 0.2)]
        if chip == 2:
            modules.append(("jit_other(1)", q + 1.5, 0.48))
            ops += [("%fusion.4 = fusion(...)", q + 1.5, 0.4),
                    ("%all-reduce.5 = all-reduce(...)", q + 1.9, 0.05),
                    ("%all-gather-start.6 = all-gather-start(...)",
                     q + 1.95, 0.03)]
    return {"XLA Modules": modules, "XLA Ops": ops}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("x4")
    d = root / ".bench_work" / CELL / "seed1" / "trace" / "plugins" / \
        "profile" / "t"
    os.makedirs(d)
    host = {"thread": [("bench.window", 0.0, 10.0)] + [
        ("bench.collect", q, 3.0 if q < 9 else 2.0) for q in QUERIES]}
    space = _field(1, _plane("/host:CPU", host))
    for chip in range(4):
        space += _field(1, _plane(f"/device:TPU:{chip}", _device(chip)))
    (d / "x.xplane.pb").write_bytes(space)
    return str(root)


def make_run(root, programs=True, answer=True):
    trace = trace_reduce.reduce(trace_reduce.find_xplane(os.path.join(
        root, ".bench_work", CELL, "seed1", "trace")))
    table = pa.table({"l_shipmode": ["MAIL", "SHIP"],
                      "high_line_count": [300, 200],
                      "low_line_count": [400, 100]})
    return {"trace": trace, "cell": {"name": CELL},
            "traffic": {"programs": {"join": "jit_spmd_join|jit__take_flat"}}
            if programs else {},
            "records": [{"answer": table} if answer else {}] * 2,
            "rows": {"orders": 5000}, "device_kind": "TPU v5 lite",
            "counters": {"mesh.join.sync_s": 0.5}}


def test_the_trace_made_here_reduces_like_a_chips(root):
    trace = make_run(root)["trace"]
    assert trace["window"] == pytest.approx((0.0, 10.0))
    assert trace["chips"] == 4 and len(trace["busy"]) == 4
    ops = mesh_planes.read_plane_ops(trace_reduce.find_xplane(os.path.join(
        root, ".bench_work", CELL, "seed1", "trace")))
    assert sorted(ops) == [f"/device:TPU:{c}" for c in range(4)]
    assert len(ops["/device:TPU:2"]) == 18 and len(ops["/device:TPU:0"]) == 9


def test_join_device_time_is_the_mean_over_the_planes(root):
    reader = plug("metrics", "join_x4_device_ms")
    # 1.0 s a query on each of 4 planes: 4.0 chip-seconds, mean 1.0 s
    assert reader.compute(make_run(root)) == pytest.approx(1000.0)
    assert reader.compute(make_run(root, programs=False)) is None
    run = make_run(root)
    run["traffic"]["programs"]["join"] = "no_such_program"
    assert reader.compute(run) is None


def test_the_roofline_divides_by_summed_chip_seconds(root):
    reader = plug("metrics", "join_x4_roofline")
    n_bytes = roofline.join_min_bytes(1000, 5000, 1000)
    assert n_bytes == 6000 * 8 + 1000 * 8
    want = 100.0 * roofline.least_seconds(n_bytes, "TPU v5 lite") / 4.0
    got = reader.compute(make_run(root))
    assert got == pytest.approx(want) and 0 < got < 100
    # nothing to read: None, never 0
    assert reader.compute(make_run(root, programs=False)) is None
    assert reader.compute(make_run(root, answer=False)) is None
    run = make_run(root)
    run["trace"] = None
    assert reader.compute(run) is None


def test_the_roofline_reads_a_repeats_answer_from_its_first(root):
    """The window's records as `ops/select.settle` leaves them: the
    answer repeated a warm-up's bytes, so the record holds `same_as`,
    the warm-up's record, and no table of its own."""
    reader = plug("metrics", "join_x4_roofline")
    want = reader.compute(make_run(root))
    run = make_run(root)
    warm = run["records"][0]
    run["records"] = [{"same_as": warm}, {"same_as": warm}]
    assert reader.compute(run) == pytest.approx(want)
    run["records"] = [{"same_as": {}}]
    assert reader.compute(run) is None


def test_skew_is_the_busiest_chip_over_the_mean(root):
    reader = plug("metrics", "shard_busy_skew_pct")
    # inside a whole query chips 0, 1, 3 are busy 1.2 s, chip 2 1.68 s
    busy = mesh_planes.busy_per_plane(make_run(root))
    assert busy["/device:TPU:0"] == pytest.approx(2.4)
    assert busy["/device:TPU:2"] == pytest.approx(3.36)
    mean = (3 * 1.2 + 1.68) / 4
    assert reader.compute(make_run(root)) == pytest.approx(
        100 * (1.68 - mean) / mean)
    run = make_run(root)
    run["trace"] = dict(run["trace"], chips=0, busy={})
    assert reader.compute(run) is None


def test_collectives_are_read_on_the_busiest_chip_by_op_name(
        root, monkeypatch):
    from lib import program_spans

    reader = plug("metrics", "collective_ms")
    monkeypatch.setattr(program_spans, "ROOT", root)
    # chip 2, two whole queries of 0.05 + 0.03 s; a fusion that only
    # mentions a collective among its operands is none
    assert mesh_planes.busiest_plane(make_run(root)) == "/device:TPU:2"
    assert reader.compute(make_run(root)) == pytest.approx(80.0)
    monkeypatch.setattr(program_spans, "ROOT", os.path.join(root, "none"))
    assert reader.compute(make_run(root)) is None
    run = make_run(root)
    run["trace"] = None
    assert reader.compute(run) is None


def test_the_sync_wait_is_the_counter_by_the_queries(root):
    reader = plug("metrics", "mesh_sync_ms")
    assert reader.compute(make_run(root)) == pytest.approx(250.0)
    run = make_run(root)
    run["counters"] = {}
    assert reader.compute(run) is None
    run["records"] = []
    assert reader.compute(run) is None
