"""2D query×data mesh scale-out (ISSUE 10 acceptance): partitioning the
query batch over a second mesh axis must stay bit-identical to
``search_reference`` at selectivities {0.5, 0.1, 0.02}, lane padding must
be invisible, and the serving path must route + bucket for the lane count.

Same two layers as test_sharded_engine: a subprocess test that always
runs on 8 virtual CPU devices, and in-process tests gated on the session
having >= 8 devices (the 2D CI job sets
``--xla_force_host_platform_device_count=8``).
"""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

MESH2D = len(jax.devices()) >= 8

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import numpy as np, jax
    from repro.core.batched.engine import BatchedParams
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.data.synth import (make_selectivity_dataset,
                                  make_selectivity_queries)
    from repro.launch.mesh import make_serving_mesh

    ds = make_selectivity_dataset((0.5, 0.1, 0.02), n=1200, d=32,
                                  n_components=12)
    queries = []
    for v in range(3):
        queries.extend(make_selectivity_queries(ds, v, 4))
    sidx = build_sharded_index(ds.vectors, ds.metadata, 2, graph_k=8,
                               r_max=24)
    mesh = make_serving_mesh(data=2, query=4)
    eng = ShardedEngine(sidx, mesh, BatchedParams(k=10, beam_width=4))
    assert eng.q_axis == "query" and eng.q_lanes == 4, (eng.q_axis,
                                                       eng.q_lanes)
    ids_m, st_m = eng.search(queries)          # 12 queries = 3 per lane
    assert eng.dispatches == 1, eng.dispatches
    ids_r, st_r = eng.search_reference(queries)
    for i, (a, b) in enumerate(zip(ids_m, ids_r)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), i
    assert np.array_equal(st_m["walks"], st_r["walks"])
    assert np.array_equal(st_m["hops"], st_r["hops"])
    assert sum(np.asarray(i).size > 0 for i in ids_m) == len(queries)
    # non-divisible batch: 7 queries on 4 lanes pad to 8 internally, and
    # the pad must be invisible in both results and per-query stats
    ids_m7, st_m7 = eng.search(queries[:7])
    ids_r7, _ = eng.search_reference(queries[:7])
    for i, (a, b) in enumerate(zip(ids_m7, ids_r7)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), i
    assert st_m7["walks"].shape == (7,), st_m7["walks"].shape
    # lanes stop on their own, so the slowest lane's counters stand
    assert isinstance(st_m["rounds"], int) and st_m["rounds"] >= 1
    assert 2 * st_m["iters"] >= st_m["hops"].max(), st_m
    print("mesh2d-parity ok")
""")


@pytest.mark.slow
def test_mesh2d_bit_identity_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=420, cwd=".")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "mesh2d-parity ok" in r.stdout


@pytest.fixture(scope="module")
def mesh2d_setup(sel_sweep):
    if not MESH2D:
        pytest.skip("needs >= 8 devices (2D-mesh CI job)")
    from repro.core.batched.engine import BatchedParams
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.launch.mesh import make_serving_mesh

    ds, index, queries = sel_sweep
    sidx = build_sharded_index(ds.vectors, ds.metadata, 2, graph_k=16,
                               r_max=48)
    mesh = make_serving_mesh(data=2, query=4)
    eng = ShardedEngine(sidx, mesh, BatchedParams(k=10, beam_width=4))
    return ds, index, queries, eng


def test_mesh2d_matches_reference_exactly(mesh2d_setup):
    """2D shard_map dispatch == shard-at-a-time reference: same ids in
    the same order, same per-query walks/hops, across the selectivity
    sweep (36 queries = 9 per lane)."""
    _, _, queries, eng = mesh2d_setup
    assert eng.q_lanes == 4
    ids_m, st_m = eng.search(queries)
    ids_r, st_r = eng.search_reference(queries)
    for i, (a, b) in enumerate(zip(ids_m, ids_r)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            (i, queries[i].selectivity)
    np.testing.assert_array_equal(st_m["walks"], st_r["walks"])
    np.testing.assert_array_equal(st_m["hops"], st_r["hops"])


def test_mesh2d_single_dispatch_and_lane_pad(mesh2d_setup):
    """A non-divisible batch (Q=7 on 4 lanes) is still ONE compiled
    invocation — the engine pads with inert unit-basis/never() queries —
    and the pad rows never leak into results or per-query stats."""
    _, _, queries, eng = mesh2d_setup
    calls = {"n": 0}
    orig = eng._search

    def counted(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    eng._search = counted
    try:
        d0 = eng.dispatches
        ids, st = eng.search(queries[:7])
        assert calls["n"] == 1
        assert eng.dispatches - d0 == 1
        assert len(ids) == 7 and st["walks"].shape == (7,)
        ids_r, _ = eng.search_reference(queries[:7])
        for a, b in zip(ids, ids_r):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    finally:
        eng._search = orig


def test_query_only_mesh_matches_reference():
    """A data=1 mesh with 4 query lanes (pure query parallelism) must be
    bit-identical to its own shard-at-a-time reference too."""
    if not MESH2D:
        pytest.skip("needs >= 8 devices (2D-mesh CI job)")
    from repro.core.batched.engine import BatchedParams
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.core.types import FilterPredicate, Query, normalize
    from repro.launch.mesh import make_serving_mesh

    rng = np.random.default_rng(3)
    n, d = 600, 16
    vecs = normalize(rng.standard_normal((n, d)))
    meta = rng.integers(0, 5, (n, 3)).astype(np.int32)
    sidx = build_sharded_index(vecs, meta, 1, graph_k=8, r_max=24)
    eng = ShardedEngine(sidx, make_serving_mesh(data=1, query=4),
                        BatchedParams(k=5, beam_width=4))
    assert eng.n_shards == 1 and eng.q_lanes == 4
    queries = [Query(vector=normalize(rng.standard_normal(d)),
                     predicate=FilterPredicate.make({0: [int(i) % 5]}))
               for i in range(8)]
    ids_m, st_m = eng.search(queries)
    ids_r, st_r = eng.search_reference(queries)
    for a, b in zip(ids_m, ids_r):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(st_m["walks"], st_r["walks"])


def test_query_parallel_off_keeps_1d_layout():
    """mesh.query_parallel=False forces the queries-replicated layout on
    the same 2D mesh — the off-switch for the new axis."""
    if not MESH2D:
        pytest.skip("needs >= 8 devices (2D-mesh CI job)")
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.core.config import FnsConfig
    from repro.core.types import FilterPredicate, Query, normalize
    from repro.launch.mesh import make_serving_mesh

    rng = np.random.default_rng(4)
    vecs = normalize(rng.standard_normal((300, 8)))
    meta = rng.integers(0, 3, (300, 2)).astype(np.int32)
    cfg = FnsConfig().with_knobs({"walk.k": 5, "graph.graph_k": 8,
                                  "mesh.query_parallel": False})
    sidx = build_sharded_index(vecs, meta, 2, config=cfg)
    eng = ShardedEngine(sidx, make_serving_mesh(data=2, query=4),
                        config=cfg)
    assert eng.q_axis is None and eng.q_lanes == 1
    q = Query(vector=normalize(rng.standard_normal(8)),
              predicate=FilterPredicate.make({}))
    ids, _ = eng.search([q])  # Q=1 needs no lane divisibility now
    assert np.asarray(ids[0]).size == 5


def test_query_batch_routes_and_buckets_for_lanes():
    """Serving on a 2D mesh: query_batch routes to the sharded engine and
    the bucket former rounds the pad target up to a multiple of the lane
    count, so the engine-level lane pad is a no-op."""
    if not MESH2D:
        pytest.skip("needs >= 8 devices (2D-mesh CI job)")
    from repro.core.search import SearchParams
    from repro.core.types import Dataset, FilterPredicate, normalize
    from repro.launch.mesh import make_serving_mesh
    from repro.serve.retrieval import RetrievalService

    rng = np.random.default_rng(5)
    n, d = 800, 16
    vecs = normalize(rng.standard_normal((n, d)))
    meta = rng.integers(0, 5, (n, 3)).astype(np.int32)
    ds = Dataset(vecs, meta, [f"f{i}" for i in range(3)], [5] * 3)
    svc = RetrievalService.build(ds, graph_k=8, r_max=24,
                                 params=SearchParams(k=5, max_hops=40),
                                 mesh=make_serving_mesh(data=2, query=4))
    eng = svc._live_engine()
    assert svc._sharded is eng and eng.q_lanes == 4
    seen = []
    orig = eng.search
    eng.search = lambda qs, **k: seen.append(len(qs)) or orig(qs, **k)
    try:
        # 5 real queries: pow2 bucket is 8, already a lane multiple
        ids, stats = svc.query_batch(
            rng.standard_normal((5, d)),
            [FilterPredicate.make({0: [i % 5]}) for i in range(5)])
    finally:
        eng.search = orig
    assert seen == [8]
    assert len(ids) == 5 and stats["walks"].shape == (5,)
    assert eng.dispatches == 1
    for i, row in enumerate(ids):
        row = np.asarray(row)
        assert row.size > 0
        assert (meta[row, 0] == i % 5).all()
