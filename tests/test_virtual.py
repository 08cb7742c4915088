"""`parallel.virtual.ensure_devices`: virtual CPU devices for tests, never
in place of a live accelerator."""

import jax
import jax.extend.backend
import pytest

from hyperspace_tpu.parallel.virtual import ensure_devices


def test_ensure_devices_keeps_the_conftest_mesh():
    ensure_devices(8)
    devices = jax.devices()
    assert len(devices) == 8
    assert {d.platform for d in devices} == {"cpu"}


def test_ensure_devices_refuses_to_replace_an_accelerator(monkeypatch):
    """Asked for more devices than a non-CPU backend has, it must raise —
    not drop the backend and pin the CPU for the rest of the process."""
    cleared = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.extend.backend, "clear_backends",
                        lambda: cleared.append(True))
    with pytest.raises(RuntimeError, match="refusing to replace"):
        ensure_devices(len(jax.devices()) + 1)
    assert not cleared
    assert len(jax.devices()) == 8
