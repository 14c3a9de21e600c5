"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

A function, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS *before* any jax import).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules in
    ``repro.dist`` place arrays by ``NamedSharding`` and let the compiler
    propagate the rest (``Explicit``, the JAX 0.9 default, would demand an
    ``out_sharding`` on every gather inside the simulator's step)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a (data, model) mesh (tests)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def make_mesh(shape, axes=("data", "model")):
    """Mesh of the local devices with an explicit logical shape — the
    sub-production construction dry-runs and CI use with host-platform
    device virtualization (``--xla_force_host_platform_device_count=N``)."""
    import math
    n = len(jax.devices())
    if math.prod(shape) > n:
        raise ValueError(f"mesh shape {shape} needs {math.prod(shape)} "
                         f"devices, only {n} present")
    return _auto_mesh(shape, axes)


def make_sweep_mesh(n_devices=None, axis="data"):
    """1-D data-parallel mesh for ``simlock.sweep(..., mesh=)``: the sweep's
    cell dimension shards over ``axis``.  Defaults to every local device."""
    n = len(jax.devices()) if n_devices is None else n_devices
    return make_mesh((n,), (axis,))
