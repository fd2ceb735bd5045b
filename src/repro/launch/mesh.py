"""Production mesh construction (assignment-mandated shapes) + serving meshes."""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis pinned to ``AxisType.Auto``.

    The sharding rules rely on GSPMD propagation, and jax's default axis
    type is not Auto. ``devices`` restricts the mesh to a subset (e.g. a
    dp*tp serving slice of a larger host); default is all of
    ``jax.devices()``.
    """
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Small mesh for CI-scale dry-run tests (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serve_mesh(dp: int = 1, tp: int = 1):
    """Serving mesh: (dp, tp) over ("data", "model"), on the first dp*tp
    devices — real accelerators, or ``--xla_force_host_platform_device_count``
    CPU devices for CI. A 1x1 mesh is valid (single-device SPMD), so one
    engine construction path serves every scale.
    """
    if dp < 1 or tp < 1:
        raise ValueError(f"mesh degrees must be >= 1, got dp={dp} tp={tp}")
    n = dp * tp
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"serve mesh dp={dp} x tp={tp} needs {n} devices, have "
            f"{len(devs)} (CPU runs: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before importing jax)")
    return make_mesh((dp, tp), ("data", "model"), devices=devs[:n])
