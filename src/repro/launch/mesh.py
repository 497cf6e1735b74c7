"""Production mesh builders.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the models place activations with
    ``with_sharding_constraint``, which only accepts Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_degraded_mesh(n_alive: int, model_parallel: int = 16):
    """Elastic mesh over survivors: keep TP fixed, shed DP replicas."""
    dp = n_alive // model_parallel
    assert dp >= 1, "not enough devices for one model-parallel group"
    devs = jax.devices()[: dp * model_parallel]
    import numpy as np
    arr = np.array(devs).reshape(dp, model_parallel)
    from jax.sharding import Mesh
    return Mesh(arr, ("data", "model"))


def make_local_mesh(dp: int = 1, mp: int = 1):
    """Small mesh over whatever devices exist (tests)."""
    return _auto_mesh((dp, mp), ("data", "model"))


def mesh_axes(mesh):
    names = mesh.axis_names
    dp_axes = tuple(n for n in names if n != "model")
    return dp_axes, "model"
