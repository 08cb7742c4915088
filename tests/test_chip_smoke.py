"""`chip_smoke.py` off the chip: it must refuse the CPU, and its phases —
the same functions the chip run calls — must hold at a tiny size on the
CPU backend. Sizes, the device threshold and the mesh are steered HERE;
the script has no option for them."""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402



@pytest.fixture
def tiny(monkeypatch):
    """Tiny tables, the device lane at any size, and a transfer chunk
    small enough that the permutation comes back in several D2H chunks
    (multi-run bucket files)."""
    monkeypatch.setattr(chip_smoke, "FACT_ROWS", 1 << 15)
    monkeypatch.setattr(chip_smoke, "DIM_ROWS", 1 << 14)
    monkeypatch.setattr(chip_smoke, "EDGE_EVERY", 16)
    monkeypatch.setitem(chip_smoke.CONF,
                        "spark.hyperspace.execution.min.device.rows", "0")
    monkeypatch.setitem(chip_smoke.CONF,
                        "spark.hyperspace.io.transfer.chunk.bytes", "16384")


@pytest.fixture
def lossy_float64_decode(monkeypatch):
    """On the CPU a float64 decode is an exact bitcast, so a path that
    decodes payload it should only move would go unseen. Make the decode
    as lossy as the chip's (f32), and exact equality then tells: the
    smoke computes on `measure` in one predicate only, where no value is
    that close to the bound."""
    import jax.numpy as jnp

    from hyperspace_tpu.io import columnar

    exact = columnar.f64_from_bits
    monkeypatch.setattr(
        columnar, "f64_from_bits",
        lambda bits: exact(bits).astype(jnp.float32).astype(jnp.float64))


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode not in (0, None)
    assert done.stdout.strip() == ""  # no phase ran, no result line
    assert "no TPU" in done.stderr


def test_float64_probe_holds_on_the_cpu():
    chip_smoke.probe_float64()


def test_one_chip_phases_hold_on_the_cpu(tiny, lossy_float64_decode, tmp_path,
                                         monkeypatch):
    monkeypatch.setitem(chip_smoke.CONF,
                        "spark.hyperspace.distribution.enabled", "false")
    monkeypatch.setattr(chip_smoke, "probe_float64", lambda: None)
    lake = chip_smoke.Lake(str(tmp_path), 22)
    chip_smoke.run_one_chip(lake)
    lake.sess.close()


def test_process_notes_read_the_registry(tiny, tmp_path, capsys):
    """The closing notes of a chip run, after a build here: the link's
    totals are the registry's, which belong to the process (so the
    build's share is read as a difference), and the allocator's peak is
    asked of a chip only: the CPU's accountant is refused, after the
    totals."""
    import json

    from hyperspace_tpu import telemetry

    before = telemetry.get_registry().counters_dict()
    lake = chip_smoke.Lake(str(tmp_path), 22)
    chip_smoke.phase_build(lake)
    lake.sess.close()
    capsys.readouterr()
    with pytest.raises(AssertionError, match="HBM accountant"):
        chip_smoke.print_process_notes()
    said = capsys.readouterr().out.splitlines()
    line = next(ln for ln in said if ln.startswith("[smoke] link totals: "))
    totals = json.loads(line.split("link totals: ", 1)[1])
    assert len(totals) == 9
    now = telemetry.get_registry().counters_dict()
    assert totals == {name: now.get(name, 0) for name in totals}
    moved = {name: totals[name] - before.get(name, 0) for name in totals}
    assert moved["link.h2d.bytes"] > 0 and moved["link.d2h.bytes"] > 0
    assert moved["link.h2d.chunks"] >= moved["link.h2d.transfers"] > 0
    assert any(ln.startswith("[smoke] registry totals: compile.seconds")
               for ln in said)


def test_mesh_phases_hold_on_the_virtual_mesh(tiny, lossy_float64_decode,
                                              tmp_path):
    import jax

    lake = chip_smoke.Lake(str(tmp_path), 22)
    chip_smoke.run_mesh(lake, n_devices=len(jax.devices()))
    lake.sess.close()
