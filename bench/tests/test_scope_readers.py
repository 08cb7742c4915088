"""The three scope readers on the chip-recorded fixtures of
`test_program_spans.py`: `unscoped_device_ms` (ops with no `hs.*` scope),
`gather_device_ms` (`hs.gather`) and `stage_program_device_ms`
(`hs.stage`). A renamed program still counts under its scope; the
parent's program (no scopes) gives a number for `unscoped_device_ms` and
None for the other two; an untraced run gives None for all three."""

import statistics

import pytest

from conftest import plug
from lib import program_spans as ps
from lib import trace_reduce as tr
from test_program_spans import CELL, FIXTURE, PARENT_FIXTURE, lay_out

READERS = ["unscoped_device_ms", "gather_device_ms", "stage_program_device_ms"]


@pytest.fixture
def run(tmp_path, monkeypatch):
    lay_out(tmp_path)
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    return {"cell": {"name": CELL}, "trace": tr.reduce(FIXTURE),
            "traffic": {}}


def _relabel(found, tf_op):
    """The fixture's scoped ops under another scope path; the others as
    they are."""
    return {"spans": found["spans"], "ops": [
        (s, d, ps.scopes_of(tf_op) if scopes else (), name)
        for s, d, scopes, name in found["ops"]]}


def _per_query(run, found, keep):
    return 1e3 * statistics.median(
        sum(d for s, d, scopes, _ in found["ops"]
            if lo <= s < hi and keep(scopes))
        for lo, hi in ps._whole(run, ps.QUERY))


def test_unscoped_reads_the_ops_without_a_scope(run):
    found = ps.load(run)
    got = plug("metrics", "unscoped_device_ms").compute(run)
    assert got == pytest.approx(_per_query(run, found, lambda sc: not sc))
    assert got > 0
    # and it is what the scoped reader leaves: together they are every op
    every = _per_query(run, found, lambda sc: True)
    scoped = plug("metrics", "compact_device_ms").compute(run)
    assert 0 < got < every
    assert scoped > 0


@pytest.mark.parametrize("name, scope", [
    ("gather_device_ms", "hs.gather"),
    ("stage_program_device_ms", "hs.stage")])
@pytest.mark.parametrize("program", ["_take_all", "renamed"])
def test_a_renamed_program_still_counts_under_its_scope(
        run, monkeypatch, name, scope, program):
    """The fixture's compaction ops relabeled as the scope's: the reader
    reads what `compact_device_ms` read of them, whatever the program is
    called, and `unscoped_device_ms` does not move."""
    found = ps.load(run)
    compact = plug("metrics", "compact_device_ms").compute(run)
    unscoped = plug("metrics", "unscoped_device_ms").compute(run)
    assert plug("metrics", name).compute(run) is None  # not in the fixture
    relabeled = _relabel(found, f"jit({program})/{scope}/jit({program})/"
                                f"gather:")
    monkeypatch.setattr(ps, "load", lambda run, root=None: relabeled)
    assert plug("metrics", name).compute(run) == pytest.approx(compact)
    assert plug("metrics", "unscoped_device_ms").compute(run) == \
        pytest.approx(unscoped)


def test_a_nested_scope_counts_for_the_outer_one(run, monkeypatch):
    """A predicate inside the stage's program is the stage's time too."""
    found = ps.load(run)
    compact = plug("metrics", "compact_device_ms").compute(run)
    nested = _relabel(found, "jit(_run)/hs.stage/jit(_run)/hs.predicate/"
                             "jit(compile_predicate)/ge:")
    monkeypatch.setattr(ps, "load", lambda run, root=None: nested)
    assert plug("metrics", "stage_program_device_ms").compute(run) == \
        pytest.approx(compact)


def test_unscoped_reads_zero_only_where_every_op_carries_a_scope(
        run, monkeypatch):
    found = ps.load(run)
    every = _relabel(found, "jit(x)/hs.gather/jit(x)/gather:")
    every["ops"] = [(s, d, ("hs.gather",), n) for s, d, _, n in every["ops"]]
    monkeypatch.setattr(ps, "load", lambda run, root=None: every)
    assert plug("metrics", "unscoped_device_ms").compute(run) == 0.0
    # no op inside a traced query: nothing was read, not 0
    lo, _ = run["trace"]["window"]
    before = {"spans": found["spans"], "ops": [
        (lo - 10.0, d, (), n) for _, d, _, n in found["ops"]]}
    monkeypatch.setattr(ps, "load", lambda run, root=None: before)
    assert plug("metrics", "unscoped_device_ms").compute(run) is None


@pytest.mark.parametrize("case", ["untraced", "parent_program"])
def test_the_parent_reads_unscoped_and_untraced_reads_nothing(
        case, tmp_path, monkeypatch):
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    run = {"cell": {"name": CELL}, "traffic": {},
           "trace": tr.reduce(PARENT_FIXTURE)}
    if case == "untraced":
        run["trace"] = None
        for name in READERS:
            assert plug("metrics", name).compute(run) is None, name
        return
    # a commit without the scopes: every op it ran is unscoped
    lay_out(tmp_path, PARENT_FIXTURE)
    found = ps.load(run)
    assert found["ops"] and not any(sc for _, _, sc, _ in found["ops"])
    got = plug("metrics", "unscoped_device_ms").compute(run)
    assert got == pytest.approx(_per_query(run, found, lambda sc: True))
    assert got > 0
    for name in ("gather_device_ms", "stage_program_device_ms"):
        assert plug("metrics", name).compute(run) is None, name
