"""Device meshes (functions, not module constants — importing this module
must never touch jax device state)."""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis Auto: the sharding-rule code places
    arrays with ``NamedSharding``s and lets the compiler propagate them, which
    the Explicit axes that ``jax.make_mesh`` now defaults to refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh for CPU tests (requires forced host device count)."""
    return make_mesh(shape, axes)
