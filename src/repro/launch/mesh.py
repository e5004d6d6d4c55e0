"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module does not touch JAX device state.  The dry-run entry point
(dryrun.py) sets XLA_FLAGS host-device-count *before* any jax import.

Every mesh is built with *Auto* axes: the model code annotates
activations with ``with_sharding_constraint`` and lets GSPMD propagate
(the vocab-sharded embedding gather in particular has no explicit out
sharding), which JAX's default *Explicit* axes refuse.
"""
from __future__ import annotations

import jax


def make_mesh(shape, names, devices=None):
    """``jax.make_mesh`` with Auto axis types (see module docstring)."""
    auto = (jax.sharding.AxisType.Auto,) * len(names)
    return jax.make_mesh(tuple(shape), tuple(names), axis_types=auto,
                         devices=devices)


def model_mesh(tp: int):
    """A ``tp``-way ``model`` mesh over this process's first ``tp``
    devices.  Too few devices is an error: a process cannot hand a chip
    to a child, and on the CPU the count is fixed before jax starts."""
    if jax.device_count() < tp:
        raise ValueError(
            f"a {tp}-way model mesh needs {tp} devices but only "
            f"{jax.device_count()} are visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={tp} before jax "
            f"starts")
    return make_mesh((tp,), ("model",), devices=jax.devices()[:tp])


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh():
    """1x1 mesh over the single real CPU device (tests/benches)."""
    return make_mesh((1, 1), ("data", "model"))


def mesh_chip_count(mesh) -> int:
    import numpy as np
    return int(np.prod(list(mesh.shape.values())))
