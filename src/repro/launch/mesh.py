"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run sets
``xla_force_host_platform_device_count`` before first jax init.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "make_chip_mesh",
           "make_sim_mesh", "ensure_sim_devices"]

_SIM_FLAG = "--xla_force_host_platform_device_count"


def _jax_backend_initialized() -> bool:
    """True once any XLA backend has been created (after which
    ``xla_force_host_platform_device_count`` can no longer take effect).
    Reads a private JAX field: if it moves, this fails loudly instead of
    guessing."""
    from jax._src import xla_bridge
    return bool(xla_bridge._backends)


def ensure_sim_devices(n: int) -> bool:
    """Best-effort: set ``XLA_FLAGS={_SIM_FLAG}=n`` if jax has not
    initialized yet and the flag is absent. Returns True if, after this
    call, ``n`` host devices will be (or already are) visible.

    Call this before any other jax work (e.g. first thing in a test
    module or a launcher ``main``). Once a backend exists the flag is
    inert, so this only *reports* availability in that case."""
    if _jax_backend_initialized():
        return len(jax.devices()) >= n
    flags = os.environ.get("XLA_FLAGS", "")
    if _SIM_FLAG not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " " if flags else "") + f"{_SIM_FLAG}={n}"
        return True
    # flag present — honour whatever count the user pinned
    try:
        pinned = int(flags.split(f"{_SIM_FLAG}=", 1)[1].split()[0])
    except (IndexError, ValueError):
        return True
    return pinned >= n


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``: the partitioner
    propagates shardings from the placed arguments, as the sharding rules
    in ``repro.sharding.partition`` assume (``make_mesh`` now defaults to
    ``Explicit`` axes, which type-check shardings instead)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """1×1 mesh over the real local device(s) — for smoke tests."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def make_chip_mesh(n: int):
    """(1, n) ("data", "model") mesh over the first ``n`` accelerator
    chips — the expert-parallel serving layout. Raises when fewer than
    ``n`` devices are visible."""
    avail = len(jax.devices())
    if avail < n:
        raise RuntimeError(f"make_chip_mesh({n}) needs {n} devices but "
                           f"only {avail} are visible")
    return _auto_mesh((1, n), ("data", "model"), jax.devices()[:n])


def make_sim_mesh(n: int):
    """(1, n) ("data", "model") mesh over ``n`` simulated host devices —
    the CPU-CI stand-in for an n-chip edge cluster, so the sharded
    serving paths (expert-parallel params, sharded KV slots) execute for
    real under GSPMD partitioning.

    Requires ``XLA_FLAGS={_SIM_FLAG}=n`` (or more) to have been set
    BEFORE the first jax init — e.g. via :func:`ensure_sim_devices` at
    process start, or in the CI job env. Raises a clear ``RuntimeError``
    when fewer than ``n`` devices are visible instead of silently
    handing back a 1-device mesh whose shardings all degrade to no-op
    replication (which would green-light tests that never exercised
    partitioning at all)."""
    avail = len(jax.devices())
    if avail < n:
        raise RuntimeError(
            f"make_sim_mesh({n}) needs {n} devices but only {avail} "
            f"{'is' if avail == 1 else 'are'} visible. On CPU, export "
            f"XLA_FLAGS='{_SIM_FLAG}={n}' (appending to any existing "
            f"XLA_FLAGS) *before* the first jax import/init — or call "
            f"repro.launch.mesh.ensure_sim_devices({n}) at process "
            f"start. Refusing to degrade to a {avail}-device mesh: its "
            f"shardings would all guard down to replication and the "
            f"sharded code paths would silently not be exercised.")
    return _auto_mesh((1, n), ("data", "model"), jax.devices()[:n])
