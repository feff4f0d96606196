"""Production mesh builders.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count`` before first jax init.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the model and search code
    shards through ``with_sharding_constraint``/``shard_map`` specs, which
    ``jax.make_mesh``'s default ``Explicit`` axes refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over available devices (tests / CPU smoke runs)."""
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return jax.sharding.Mesh(devs, ("data", "model"))


def make_serving_mesh(data: int = 1, query: int = 1):
    """2D query×data serving mesh (DESIGN.md §13): the corpus is
    row-partitioned over ``data`` and the query batch over ``query``, so
    each of the ``query`` lanes walks Q/query queries against every data
    shard. ``query=1`` degrades to the PR 3 queries-replicated layout."""
    devs = np.asarray(jax.devices()[: data * query]).reshape(data, query)
    return jax.sharding.Mesh(devs, ("data", "query"))


def data_axis_names(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def index_axis_size(mesh, axis: str = "data") -> int:
    """Corpus shard count a sharded index gets on this mesh: the size of
    the row-partition axis (DESIGN.md §7), 1 when the mesh lacks it."""
    return int(mesh.shape[axis]) if axis in mesh.axis_names else 1


def query_axis_name(mesh, candidates=("query", "model")) -> str | None:
    """The mesh axis that carries query lanes (DESIGN.md §13): the first
    candidate axis present with size > 1, else None (queries replicated).
    A dedicated ``query`` axis wins over reusing ``model``."""
    if mesh is None:
        return None
    for a in candidates:
        if a in mesh.axis_names and int(mesh.shape[a]) > 1:
            return a
    return None


def query_axis_size(mesh, candidates=("query", "model")) -> int:
    """Number of query lanes the mesh provides (1 = replicated)."""
    name = query_axis_name(mesh, candidates)
    return int(mesh.shape[name]) if name is not None else 1
