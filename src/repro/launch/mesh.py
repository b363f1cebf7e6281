"""Production mesh construction (function, not constant: importing this module
never touches jax device state).

Every mesh is built with ``Auto`` axis types: the sharding rules here and
in the serving path place arrays with ``NamedSharding`` and
``with_sharding_constraint`` and let the partitioner propagate the rest,
which ``jax.make_mesh``'s ``Explicit`` default refuses (an embedding
gather, for one, raises a ``ShardingTypeError``)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic re-meshing, serving), ``Auto`` axes."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh ('pod' folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, names) -> int:
    s = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for n in (names if isinstance(names, (tuple, list)) else (names,)):
        s *= sizes.get(n, 1)
    return s
