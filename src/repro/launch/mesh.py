"""Production mesh construction (function, not module constant — importing
this module never touches jax device state).

Every mesh is built with ``Auto`` axis types: the engine places arrays with
explicit `NamedSharding`s and lets the compiler propagate the rest, which
is what `jax.make_mesh`'s ``Explicit`` default would refuse (its sharding-in-
types rules reject the per-workload ``segment_sum`` over sharded lanes)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic scaling uses this with recomputed shapes)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests, examples, the
    four-chip smoke)."""
    n = len(jax.devices())
    data = n // model_axis
    return make_mesh((data, model_axis), ("data", "model"))
