"""The one mesh constructor of the repo.

Every mesh -- launchers, benchmarks, examples, tests -- is built here with
``AxisType.Auto`` axes: GSPMD propagates shardings through the model and
``shard_map`` makes the sync axes Manual inside the step.  JAX's own
``jax.make_mesh`` defaults to Explicit axes, under which plain gathers such
as the embedding lookup refuse to trace without an ``out_sharding``.

Functions, not module-level constants: importing this module never touches
jax device state, so callers can set XLA_FLAGS for placeholder devices
*before* the first device query.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None):
    """A mesh of ``shape`` over the named ``axes``, all of them Auto.
    ``devices`` (a flat list) fixes which devices, in row-major order;
    by default JAX lays the visible devices out for the physical
    topology."""
    shape, axes = tuple(shape), tuple(axes)
    types = (AxisType.Auto,) * len(axes)
    if devices is not None:
        return Mesh(np.asarray(devices).reshape(shape), axes,
                    axis_types=types)
    return jax.make_mesh(shape, axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) over 256 chips (a v5e pod's 16x16
    torus).  Multi-pod: (pod=2, data=16, model=16) over 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
