"""Mesh construction — the one place a ``jax.sharding.Mesh`` is made.

Every mesh gets ``AxisType.Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which ``with_sharding_constraint`` (how a
:class:`~repro.core.plan.ShardingPlan` applies its buffer specs) acts as
an assert instead of a constraint, and a plan-sharded step fails to
trace.  Meshes are built by FUNCTIONS (never module-level constants) so
importing this module does not touch jax device state.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
from jax.sharding import AxisType

from ..core.estimator import MULTI_POD, SINGLE_POD, MeshSpec


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices: Sequence | None = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes, over ``devices`` when given
    (e.g. a described topology's devices for a compile-only check)."""
    kw = {} if devices is None else {"devices": list(devices)}
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_spec(multi_pod: bool = False) -> MeshSpec:
    return MULTI_POD if multi_pod else SINGLE_POD


def make_host_mesh(shape: tuple[int, int]) -> jax.sharding.Mesh:
    """``(data, model)`` mesh over the first ``prod(shape)`` devices."""
    return make_mesh(shape, ("data", "model"),
                     jax.devices()[:math.prod(shape)])


def host_mesh_and_spec() -> tuple[jax.sharding.Mesh, MeshSpec]:
    """The ``(data=n, model=1)`` mesh over all devices that the drivers
    run on, and the :class:`MeshSpec` the plan is derived for — one
    description of the mesh that runs, so plan and mesh cannot
    disagree."""
    n = len(jax.devices())
    return make_host_mesh((n, 1)), MeshSpec((("data", n), ("model", 1)))
