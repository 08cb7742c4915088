"""Virtual multi-device bootstrap for tests and dry runs.

Multi-chip behavior is validated the way the reference validates
distribution — a real local multi-way runtime in one process (`local[4]`
SparkSession, reference `SparkInvolvedSuite.scala:29-35`): here, an
n-device virtual CPU mesh. Used by `tests/conftest.py` and
`__graft_entry__.dryrun_multichip`.
"""

from __future__ import annotations


def ensure_devices(n_devices: int) -> None:
    """Make `jax.devices()` report at least ``n_devices`` devices.

    Enough live devices are used as-is. Otherwise, ON THE CPU BACKEND
    ONLY, the live backends are dropped and CPU is re-initialized with a
    forced device count. ``clear_backends`` MUST precede the config
    update — jax refuses ``jax_num_cpu_devices`` changes while backends
    are live.

    On an accelerator with too few chips this RAISES: swapping a live
    TPU for virtual CPU devices would let a chip run measure the CPU
    under the chip's name. A caller that wants the virtual mesh says so
    before jax initializes (``JAX_PLATFORMS=cpu``).

    PROCESS-DESTRUCTIVE in the re-initializing path: it invalidates
    every live jax array and compiled computation. Call it before any
    device work (tests do it at conftest import; the dryrun gate does it
    first thing).
    """
    import jax

    have = len(jax.devices())
    if have >= n_devices:
        return
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"ensure_devices({n_devices}): the live backend is {backend!r} "
            f"with {have} device(s); refusing to replace it with virtual "
            f"CPU devices. Set JAX_PLATFORMS=cpu before starting to run on "
            f"a virtual mesh.")

    import jax.extend.backend

    jax.extend.backend.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"virtual mesh bootstrap failed: have {len(jax.devices())} "
            f"devices, requested {n_devices}")
