"""Compile the search path's Pallas kernel, and the fused search program
around it, for a described TPU v5e at the paper's catalogue widths —
n=105,100 rows, d=2048, 27 metadata fields (24 catalogue fields + two OR
fields + a timestamp), Q=256 queries, 1024-value bitmaps — with no chip
attached. Mosaic refuses here what it would refuse on the chip (block
shapes off the (8, 128) tiling, scalar reads from vector memory, SMEM
overflow), so these tests guard the chip path at no chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library at a time, and every test
worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.config import FnsConfig
from repro.kernels.filter_eval import filter_eval_batch

N, D, F, Q = 105_100, 2048, 27, 256
WV = 1024 // 32            # value-bitmap words at the auto v_cap ceiling
C = FnsConfig().kernel.max_clauses
TN = FnsConfig().kernel.filter_tile


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct maker placed on one described v5e chip, with the
    persistent compilation cache off: entries compiled for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _tables(spec, form, d_n=2, c_n=C):
    """(fields, allowed, n_disj, bounds) shapes of one clause-table form,
    as ``pack_query_batch`` emits them."""
    if form == "rank2":
        return (spec((Q, c_n), jnp.int32), spec((Q, c_n, WV), jnp.uint32),
                None, None)
    fields = spec((Q, d_n, c_n), jnp.int32)
    allowed = spec((Q, d_n, c_n, WV), jnp.uint32)
    n_disj = spec((Q,), jnp.int32)
    if form == "dnf":
        return fields, allowed, n_disj, None
    return fields, allowed, n_disj, spec((Q, d_n, c_n, 2), jnp.int32)


@pytest.mark.parametrize("form,d_n,c_n", [("rank2", 1, C), ("dnf", 2, C),
                                          ("dnf_bounds", 1, C),
                                          ("dnf_bounds", 8, 8)])
def test_filter_eval_lowers_for_v5e(spec, form, d_n, c_n):
    """Each table form (and the widest tables the packers emit: 8
    disjuncts x 8 clauses) lowers to a Mosaic custom call."""
    fn = jax.jit(functools.partial(filter_eval_batch, tn=TN,
                                   interpret=False))
    compiled = fn.lower(spec((N, F), jnp.int32),
                        *_tables(spec, form, d_n, c_n)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_search_lowers_for_v5e(spec, monkeypatch):
    """The whole one-dispatch search program (predicate sweep, anchor
    rounds, lockstep walks) compiles for the chip at the catalogue shape
    and carries the filter_eval kernel. The platform choice is steered to
    the TPU branch here: this process's backend is the CPU."""
    import repro.core.batched.engine as engine
    from repro.core.device_atlas import DeviceAtlas

    monkeypatch.setattr(engine, "on_tpu", lambda: True)
    cfg = FnsConfig()
    n, k, r = N + 1024, 325, 128          # capacity slab, sqrt(n) clusters
    datlas = DeviceAtlas(
        spec((k, D), jnp.float32), spec((n,), jnp.int32),
        spec((n,), jnp.int32), spec((k + 1,), jnp.int32),
        spec((n,), jnp.int32), spec((F, k, WV), jnp.uint32),
        spec((F, k), jnp.int32), spec((F, k), jnp.int32), v_cap=WV * 32)
    fields, allowed, _, _ = _tables(spec, "rank2")
    fn = jax.jit(functools.partial(engine.search_batch, p=cfg.walk,
                                   kcfg=cfg.kernel))
    compiled = fn.lower(datlas, spec((n, D), jnp.float32),
                        spec((n, r), jnp.int32), spec((n, F), jnp.int32),
                        spec((Q, D), jnp.float32), fields, allowed,
                        valid_bm=spec(((n + 31) // 32,), jnp.uint32)
                        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("form", ["dnf", "dnf_bounds"])
def test_tag_filter_eval_lowers_for_v5e(spec, form):
    """The tag test (a tag-set field's label words in columns 1..5, read
    at a dynamic column per clause word) lowers to a Mosaic custom call
    in both table forms that carry it."""
    fn = jax.jit(functools.partial(filter_eval_batch, tn=TN,
                                   interpret=False, tag_cols=(1,)))
    compiled = fn.lower(spec((N, F), jnp.int32),
                        *_tables(spec, form, 1, C)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_tag_search_lowers_for_v5e(spec, monkeypatch):
    """The one-dispatch search program at the arXiv-titles store's shape:
    49,152 rows of 384, a 155-label tag-set field in columns 0..4 and a
    day column, label-set AND window tables (rank 3 with bounds)."""
    import repro.core.batched.engine as engine
    from repro.core.device_atlas import DeviceAtlas

    monkeypatch.setattr(engine, "on_tpu", lambda: True)
    cfg = FnsConfig()
    n, d, f, k, r = 49_152, 384, 6, 222, 128   # sqrt(n) clusters
    datlas = DeviceAtlas(
        spec((k, d), jnp.float32), spec((n,), jnp.int32),
        spec((n,), jnp.int32), spec((k + 1,), jnp.int32),
        spec((n,), jnp.int32), spec((f, k, WV), jnp.uint32),
        spec((f, k), jnp.int32), spec((f, k), jnp.int32), v_cap=WV * 32)
    fields, allowed, _, bounds = _tables(spec, "dnf_bounds", 1, C)
    fn = jax.jit(functools.partial(engine.search_batch, p=cfg.walk,
                                   kcfg=cfg.kernel, tag_cols=(0,)))
    compiled = fn.lower(datlas, spec((n, d), jnp.float32),
                        spec((n, r), jnp.int32), spec((n, f), jnp.int32),
                        spec((Q, d), jnp.float32), fields, allowed,
                        bounds=bounds).compile()
    assert "tpu_custom_call" in compiled.as_text()
