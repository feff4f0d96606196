"""Sharded fused search (ISSUE 3 acceptance): the corpus partitioned over
the mesh ``data`` axis must return bit-identical ids to the single-device
fused per-shard programs + exact merge, at selectivities {0.5, 0.1, 0.02},
with ONE compiled dispatch per batch.

Two layers: a subprocess test that always runs on 8 virtual CPU devices
(like test_distributed), and in-process tests that exercise the same
assertions whenever the session already has >= 4 devices (the
multi-device CI job sets ``--xla_force_host_platform_device_count=8``).
"""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

MULTI = len(jax.devices()) >= 4

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import numpy as np, jax
    from repro.core.batched.engine import BatchedParams
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.data.synth import (make_selectivity_dataset,
                                  make_selectivity_queries)
    from repro.launch.mesh import make_local_mesh

    ds = make_selectivity_dataset((0.5, 0.1, 0.02), n=1200, d=32,
                                  n_components=12)
    queries = []
    for v in range(3):
        queries.extend(make_selectivity_queries(ds, v, 4))
    sidx = build_sharded_index(ds.vectors, ds.metadata, 4, graph_k=8,
                               r_max=24)
    mesh = make_local_mesh(data=4, model=1)
    eng = ShardedEngine(sidx, mesh, BatchedParams(k=10, beam_width=4))
    ids_m, st_m = eng.search(queries)
    assert eng.dispatches == 1, eng.dispatches
    ids_r, st_r = eng.search_reference(queries)
    for i, (a, b) in enumerate(zip(ids_m, ids_r)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), i
    assert np.array_equal(st_m["walks"], st_r["walks"])
    assert np.array_equal(st_m["hops"], st_r["hops"])
    assert sum(np.asarray(i).size > 0 for i in ids_m) == len(queries)
    # the reference runs on the default device alone, never partitioned
    ref_out = eng._run_reference(*eng._pack_queries(queries))
    assert {d for v in ref_out.values() for d in v.devices()} == \
        {jax.devices()[0]}
    print("sharded-parity ok")
""")


@pytest.mark.slow
def test_sharded_bit_identity_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=420, cwd=".")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sharded-parity ok" in r.stdout


def test_shard_bounds_balanced():
    """No shard may come out empty or inverted: sizes differ by at most 1
    and the max is ceil(n/S) (regression: a fixed ceil(n/S) stride left
    trailing shards empty whenever (S-1)*ceil(n/S) >= n, e.g. n=10 S=7)."""
    from repro.core.graph import shard_bounds

    for n, s in [(10, 7), (10, 4), (1202, 4), (8, 8), (9, 2), (3000, 8)]:
        b = shard_bounds(n, s)
        sizes = [hi - lo for lo, hi in b]
        assert b[0][0] == 0 and b[-1][1] == n
        assert all(lo < hi for lo, hi in b), (n, s, b)
        assert all(b[i][1] == b[i + 1][0] for i in range(s - 1))
        assert max(sizes) == -(-n // s) and min(sizes) >= n // s
    with pytest.raises(ValueError):
        shard_bounds(4, 5)


def test_tiny_corpus_many_shards_exact():
    """A corpus barely larger than the shard count must still build
    (single-point shards get degenerate graphs) and, because every shard
    is exhaustively seeded, the merged result IS the exact top-k."""
    if not MULTI:
        pytest.skip("needs >= 4 devices (multi-device CI job)")
    from repro.core.batched.engine import BatchedParams
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.core.types import FilterPredicate, Query, normalize
    from repro.launch.mesh import make_local_mesh

    rng = np.random.default_rng(0)
    vecs = normalize(rng.standard_normal((10, 8)))
    meta = rng.integers(0, 3, (10, 2)).astype(np.int32)
    sidx = build_sharded_index(vecs, meta, 4, graph_k=4, r_max=8)
    eng = ShardedEngine(sidx, make_local_mesh(data=4, model=1),
                        BatchedParams(k=3, beam_width=2))
    q = Query(vector=normalize(rng.standard_normal(8)).astype(np.float32),
              predicate=FilterPredicate.make({}))
    ids, _ = eng.search([q])
    exact = np.argsort(-(vecs @ q.vector))[:3]
    assert set(np.asarray(ids[0]).tolist()) == set(exact.tolist())


@pytest.fixture(scope="module")
def sharded_setup(sel_sweep):
    if not MULTI:
        pytest.skip("needs >= 4 devices (multi-device CI job)")
    from repro.core.batched.engine import BatchedParams
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.launch.mesh import make_local_mesh

    ds, index, queries = sel_sweep
    sidx = build_sharded_index(ds.vectors, ds.metadata, 4, graph_k=16,
                               r_max=48)
    mesh = make_local_mesh(data=4, model=1)
    eng = ShardedEngine(sidx, mesh, BatchedParams(k=10, beam_width=4))
    return ds, index, queries, eng


def test_sharded_matches_reference_exactly(sharded_setup):
    """Mesh shard_map dispatch == single-device per-shard programs + same
    merge: same ids in the same order, same summed walks/hops, across the
    engineered selectivity sweep."""
    _, _, queries, eng = sharded_setup
    ids_m, st_m = eng.search(queries)
    ids_r, st_r = eng.search_reference(queries)
    for i, (a, b) in enumerate(zip(ids_m, ids_r)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            (i, queries[i].selectivity)
    np.testing.assert_array_equal(st_m["walks"], st_r["walks"])
    np.testing.assert_array_equal(st_m["hops"], st_r["hops"])


def test_sharded_single_dispatch(sharded_setup):
    """One batch = one compiled-callable invocation of the shard_map
    program (the fused per-shard search + merge is one device program)."""
    _, _, queries, eng = sharded_setup
    calls = {"n": 0}
    orig = eng._search

    def counted(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    eng._search = counted
    try:
        d0 = eng.dispatches
        ids, _ = eng.search(queries)
        assert calls["n"] == 1
        assert eng.dispatches - d0 == 1
        assert any(np.asarray(i).size for i in ids)
    finally:
        eng._search = orig


def test_sharded_recall_parity_and_filters(sharded_setup):
    """Correctness bar vs the single-device fused engine over the full
    corpus: per-shard restarts may find different (not worse) neighbours,
    so compare recall, and check the hard invariants exactly — results
    pass their filters, ids unique, ids globally valid."""
    from repro.core.batched.engine import BatchedEngine, BatchedParams
    from repro.data.ground_truth import recall_at_k

    ds, index, queries, eng = sharded_setup
    ids_s, _ = eng.search(queries)
    geng = BatchedEngine(index, BatchedParams(k=10, beam_width=4))
    ids_g, _ = geng.search(queries)
    rec_s = np.mean([recall_at_k(np.asarray(i), q.gt_ids)
                     for i, q in zip(ids_s, queries)])
    rec_g = np.mean([recall_at_k(np.asarray(i), q.gt_ids)
                     for i, q in zip(ids_g, queries)])
    assert rec_s > rec_g - 0.08, (rec_s, rec_g)
    n = ds.vectors.shape[0]
    for q, row in zip(queries, ids_s):
        row = np.asarray(row)
        assert row.size == np.unique(row).size
        assert ((row >= 0) & (row < n)).all()
        if row.size:
            assert q.predicate.mask(ds.metadata)[row].all()


def test_query_batch_routes_to_sharded_engine():
    """Serving path: a RetrievalService built with a mesh whose data axis
    spans >1 device must answer query_batch through the sharded engine
    (the single-device engine is never built), with filter-valid
    results."""
    if not MULTI:
        pytest.skip("needs >= 4 devices (multi-device CI job)")
    from repro.core.search import SearchParams
    from repro.core.types import Dataset, FilterPredicate, normalize
    from repro.launch.mesh import make_local_mesh
    from repro.serve.retrieval import RetrievalService

    rng = np.random.default_rng(2)
    n, d = 800, 16
    vecs = normalize(rng.standard_normal((n, d)))
    meta = rng.integers(0, 5, (n, 3)).astype(np.int32)
    ds = Dataset(vecs, meta, [f"f{i}" for i in range(3)], [5] * 3)
    svc = RetrievalService.build(ds, graph_k=8, r_max=24,
                                 params=SearchParams(k=5, max_hops=40),
                                 mesh=make_local_mesh(data=4, model=1))
    preds = [FilterPredicate.make({0: [1]}),
             FilterPredicate.make({1: [2, 3]}),
             FilterPredicate.make({})]
    ids, stats = svc.query_batch(rng.standard_normal((3, d)), preds)
    assert svc._sharded is not None and svc._engine is None
    assert svc.index is None  # the global graph/atlas were never built
    assert svc._sharded.dispatches == 1
    assert stats["walks"].shape == (3,)
    for pred, row in zip(preds, ids):
        row = np.asarray(row)
        assert row.size > 0
        assert pred.mask(meta)[row].all()
    assert np.asarray(ids[2]).size == 5  # unconstrained fills k


def test_sharded_global_ids_cover_all_shards(sharded_setup):
    """Results must come from more than one shard for a broad filter —
    the merge really is cross-shard, not shard-0-wins."""
    ds, _, queries, eng = sharded_setup
    broad = [q for q in queries if q.selectivity > 0.3]
    ids, _ = eng.search(broad)
    gids = np.asarray(eng.global_ids)  # (S, m), -1 pads
    got = np.unique(np.concatenate([np.asarray(r) for r in ids]))
    shards = {s for s in range(gids.shape[0])
              if np.isin(got, gids[s]).any()}
    assert len(shards) > 1, shards
