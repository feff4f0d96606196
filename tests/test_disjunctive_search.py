"""Disjunctive filtered search end-to-end (ISSUE 4 acceptance): Or-of-two-
fields expressions must flow through DNF clause tables and the in-kernel
disjunct union with pass bitmaps bit-identical to the numpy expression-tree
oracle, on the fused single-dispatch engine AND the 4-shard ShardedEngine,
preserving one dispatch + one host sync per batch; serving must reject
mismatched batches and keep its bucket pads inert under disjunctions."""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import AnchorAtlas, FiberIndex, build_alpha_knn
from repro.core.batched.bitmap import pack_bits
from repro.core.batched.engine import BatchedEngine, BatchedParams
from repro.core.predicate import FilterExpr, In, Not, Or
from repro.core.types import FilterPredicate, Query
from repro.data.ground_truth import attach_ground_truth, recall_at_k
from repro.data.synth import (add_or_pair_fields, make_or_queries,
                              make_selectivity_dataset, or_pair_predicate)

MULTI = len(jax.devices()) >= 4

OR_SELS = (0.5, 0.1, 0.02)


@pytest.fixture(scope="module")
def or_sweep():
    """Corpus with engineered two-field OR selectivities ~{0.5, 0.1, 0.02}
    (each or-pair field carries half the union mass) + 12 queries per
    level, ground truth attached."""
    ds = add_or_pair_fields(
        make_selectivity_dataset(OR_SELS, n=2400, d=48, n_components=16),
        sels=OR_SELS)
    graph = build_alpha_knn(ds.vectors, k=16, r_max=48, alpha=1.2)
    atlas = AnchorAtlas.build(ds, seed=0)
    index = FiberIndex(ds.vectors, ds.metadata, graph, atlas)
    queries = []
    for ci, _sel in enumerate(OR_SELS):
        queries.extend(make_or_queries(ds, ci + 1, 12))
    attach_ground_truth(ds, queries, k=10)
    return ds, index, queries


@pytest.fixture(scope="module")
def or_engine(or_sweep):
    ds, index, _ = or_sweep
    return BatchedEngine(index, BatchedParams(k=10, beam_width=4),
                         vocab_sizes=ds.vocab_sizes)


def test_engineered_or_selectivities(or_sweep):
    ds, _, queries = or_sweep
    sels = sorted({q.selectivity for q in queries}, reverse=True)
    for got, want in zip(sels, OR_SELS):
        assert abs(got - want) < 0.4 * want, (got, want)
    for q in queries:
        assert isinstance(q.predicate, Or)
        assert len({e.field for e in q.predicate.children}) == 2


def test_pass_bitmaps_match_tree_oracle_bitexact(or_sweep, or_engine):
    """The engine's device-evaluated DNF pass bitmaps == packed expression-
    tree masks, bit for bit, across the whole disjunctive sweep."""
    ds, _, queries = or_sweep
    _, fields, allowed, bounds = or_engine._pack_queries(queries)
    assert fields.ndim == 3 and fields.shape[1] == 2  # D buckets to 2
    got = np.asarray(or_engine._passes(or_engine.metadata, fields, allowed,
                                       bounds))
    want = np.asarray(pack_bits(jnp.asarray(np.stack(
        [q.predicate.mask(ds.metadata, ds.vocab_sizes) for q in queries]))))
    np.testing.assert_array_equal(got, want)


def test_fused_matches_hostloop_on_disjunctions(or_sweep, or_engine):
    """One fused dispatch == the per-round host loop, exactly, for OR
    queries (same ids, same walks/hops) — and exactly one compiled call."""
    _, _, queries = or_sweep
    d0 = or_engine.dispatches
    ids_f, st_f = or_engine.search(queries)
    assert or_engine.dispatches - d0 == 1
    ids_h, st_h = or_engine.search_hostloop(queries)
    for i, (a, b) in enumerate(zip(ids_f, ids_h)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), i
    np.testing.assert_array_equal(st_f["walks"], st_h["walks"])
    np.testing.assert_array_equal(st_f["hops"], st_h["hops"])


def test_disjunctive_results_valid_and_recall(or_sweep, or_engine):
    """Results satisfy the expression-tree oracle, are unique, and the
    fused engine's recall (vs the oracle's exact union ground truth) stays
    within epsilon of the sequential reference at every engineered
    selectivity — the disjunctive mirror of the conjunctive parity test."""
    from repro.core.search import SearchParams, run_queries

    ds, index, queries = or_sweep
    ids, _ = or_engine.search(queries)
    for q, row in zip(queries, ids):
        row = np.asarray(row)
        assert row.size > 0
        assert q.predicate.mask(ds.metadata, ds.vocab_sizes)[row].all()
        assert row.size == np.unique(row).size
    ids_seq, _ = run_queries(index, queries,
                             SearchParams(k=10, walk="guided", beam_width=2))
    for ci, sel in enumerate(OR_SELS):
        idx = [i for i, q in enumerate(queries)
               if q.predicate.children[0].values == (ci + 1,)]
        rec_seq = float(np.mean([recall_at_k(ids_seq[i], queries[i].gt_ids)
                                 for i in idx]))
        rec_b = float(np.mean([recall_at_k(np.asarray(ids[i]),
                                           queries[i].gt_ids)
                               for i in idx]))
        assert rec_b > rec_seq - 0.1, (sel, rec_b, rec_seq)
        assert rec_b > 0.5, (sel, rec_b)


def test_conjunctive_lane_unchanged_in_mixed_batch(or_sweep, or_engine):
    """A conjunctive query's results are identical whether it ships in a
    legacy (Q, C) batch or rides a widened (Q, D, C) mixed batch — the
    disjunct axis is pure padding for it."""
    ds, _, queries = or_sweep
    conj = Query(vector=queries[0].vector,
                 predicate=FilterPredicate.make({0: [1]}))
    solo_ids, _ = or_engine.search([conj])
    mixed_ids, _ = or_engine.search([conj] + queries[:3])
    np.testing.assert_array_equal(np.asarray(solo_ids[0]),
                                  np.asarray(mixed_ids[0]))
    _, f_solo, _, b_solo = or_engine._pack_queries([conj])
    assert f_solo.ndim == 2 and b_solo is None  # legacy tables kept


def test_hier_atlas_sequential_search_with_expressions(or_sweep):
    """The hierarchical atlas honors the flat atlas's interchangeability
    contract for expression predicates too: sequential search over a
    HierAtlas-backed index answers an Or query with oracle-valid seeds."""
    from repro.core.hier_atlas import HierAtlas
    from repro.core.search import FiberIndex, SearchParams, search

    ds, index, queries = or_sweep
    hidx = FiberIndex(ds.vectors, ds.metadata, index.graph,
                      HierAtlas.build(ds, index.atlas))
    q = queries[0]
    ids, _, stats = search(hidx, q.vector, q.predicate,
                           SearchParams(k=10, walk="guided", beam_width=2))
    mask = q.predicate.mask(ds.metadata, ds.vocab_sizes)
    assert len(ids) > 0 and mask[np.asarray(ids)].all()
    assert stats.n_walks >= 1


def test_not_queries_through_engine(or_sweep, or_engine):
    """Not lowers to the complement value-set and the engine result obeys
    the tree oracle."""
    ds, _, queries = or_sweep
    q = Query(vector=queries[0].vector, predicate=Not(In(0, [0])))
    ids, _ = or_engine.search([q])
    row = np.asarray(ids[0])
    mask = q.predicate.mask(ds.metadata, ds.vocab_sizes)
    assert row.size == 10 and mask[row].all()


SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import numpy as np, jax
    from repro.core.batched.engine import BatchedParams
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.data.synth import (add_or_pair_fields, make_or_queries,
                                  make_selectivity_dataset)
    from repro.launch.mesh import make_local_mesh

    ds = add_or_pair_fields(
        make_selectivity_dataset((0.5, 0.1, 0.02), n=1200, d=32,
                                 n_components=12), sels=(0.5, 0.1, 0.02))
    queries = []
    for ci in range(3):
        queries.extend(make_or_queries(ds, ci + 1, 4))
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
    for q, row in zip(queries, ids_m):
        row = np.asarray(row)
        assert row.size > 0
        assert q.predicate.mask(ds.metadata, ds.vocab_sizes)[row].all()
    print("sharded-or-parity ok")
""")


@pytest.mark.slow
def test_sharded_disjunctive_bit_identity_subprocess():
    """4-shard mesh dispatch == single-device per-shard programs + merge,
    bit-identical, for Or-of-two-fields queries (always runs: 8 virtual
    CPU devices in a subprocess)."""
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                       capture_output=True, text=True, timeout=420, cwd=".")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sharded-or-parity ok" in r.stdout


@pytest.fixture(scope="module")
def sharded_or_setup(or_sweep):
    if not MULTI:
        pytest.skip("needs >= 4 devices (multi-device CI job)")
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.launch.mesh import make_local_mesh

    ds, index, queries = or_sweep
    sidx = build_sharded_index(ds.vectors, ds.metadata, 4, graph_k=16,
                               r_max=48)
    mesh = make_local_mesh(data=4, model=1)
    eng = ShardedEngine(sidx, mesh, BatchedParams(k=10, beam_width=4))
    return ds, index, queries, eng


def test_sharded_disjunctive_matches_reference(sharded_or_setup):
    _, _, queries, eng = sharded_or_setup
    d0 = eng.dispatches
    ids_m, st_m = eng.search(queries)
    assert eng.dispatches - d0 == 1
    ids_r, st_r = eng.search_reference(queries)
    for i, (a, b) in enumerate(zip(ids_m, ids_r)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), i
    np.testing.assert_array_equal(st_m["walks"], st_r["walks"])


def test_sharded_disjunctive_recall_parity(sharded_or_setup, or_engine):
    """4-shard recall within epsilon of the global fused engine for the
    OR sweep; hard invariants exact (oracle-valid, unique, in-range)."""
    ds, _, queries, eng = sharded_or_setup
    ids_s, _ = eng.search(queries)
    ids_g, _ = or_engine.search(queries)
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
            assert q.predicate.mask(ds.metadata, ds.vocab_sizes)[row].all()


# -- serving-path satellites -------------------------------------------------

def _tiny_service(seed=11, n=700, d=16):
    from repro.core.search import SearchParams
    from repro.core.types import Dataset, normalize
    from repro.serve.retrieval import RetrievalService

    rng = np.random.default_rng(seed)
    vecs = normalize(rng.standard_normal((n, d)))
    meta = rng.integers(0, 5, (n, 3)).astype(np.int32)
    ds = Dataset(vecs, meta, [f"f{i}" for i in range(3)], [5] * 3)
    svc = RetrievalService.build(ds, graph_k=8, r_max=24,
                                 params=SearchParams(k=5, max_hops=40))
    return rng, ds, svc


def test_query_batch_length_mismatch_raises():
    """Silent truncation regression (ISSUE 4 satellite): mismatched
    vectors/predicates lengths must raise, not drop trailing queries."""
    rng, _, svc = _tiny_service()
    preds = [FilterPredicate.make({0: [1]})] * 3
    with pytest.raises(ValueError, match="2 vectors but 3 predicates"):
        svc.query_batch(rng.standard_normal((2, 16)), preds)
    with pytest.raises(ValueError, match="4 vectors but 3 predicates"):
        svc.query_batch(rng.standard_normal((4, 16)), preds)
    assert svc._engine is None  # rejected before touching the engine


def test_bucket_pads_are_never_and_inert_under_disjunctions():
    """Bucket pads use the canonical FilterExpr.never(): they reach the
    engine as zero-disjunct lanes that never seed, walk, or emit results,
    also when the real queries are disjunctive."""
    rng, ds, svc = _tiny_service()
    eng = svc.engine()
    captured = {}
    orig = eng.search

    def spy(queries, finish=None, **kw):
        # keep the engine's own output, before the service slices it
        def keep(ids, stats):
            captured["out"] = (ids, stats)
            return finish(ids, stats)

        captured["queries"] = queries
        return orig(queries, finish=keep, **kw)

    eng.search = spy
    try:
        preds = [Or(In(0, [1]), In(1, [2])),
                 Or(In(1, [0]), In(2, [3])),
                 Not(In(0, [0]))]
        ids, stats = svc.query_batch(rng.standard_normal((3, 16)), preds)
    finally:
        eng.search = orig
    assert len(ids) == 3 and stats["walks"].shape == (3,)
    for pred, row in zip(preds, ids):
        row = np.asarray(row)
        assert row.size > 0
        assert pred.mask(ds.metadata, ds.vocab_sizes)[row].all()
    # the pad lane: a never() query that produced nothing and walked 0
    padded = captured["queries"]
    assert len(padded) == 4
    assert isinstance(padded[3].predicate, FilterExpr)
    from repro.core.predicate import as_dnf
    assert as_dnf(padded[3].predicate).n_disjuncts == 0
    full_ids, full_stats = captured["out"]
    assert np.asarray(full_ids[3]).size == 0
    assert full_stats["walks"][3] == 0 and full_stats["hops"][3] == 0


def test_query_batch_accepts_expressions_and_matches_oracle():
    """End-to-end serving with FilterExpr predicates: one dispatch, results
    obey the expression-tree oracle with the dataset's vocab domains."""
    rng, ds, svc = _tiny_service(seed=13)
    preds = [Or(In(0, [1]), In(1, [2])),
             FilterPredicate.make({2: [3]}),
             Not(In(0, [0, 1]))]
    eng = svc.engine()
    d0 = eng.dispatches
    ids, stats = svc.query_batch(rng.standard_normal((3, 16)), preds)
    assert eng.dispatches - d0 == 1
    for pred, row in zip(preds, ids):
        row = np.asarray(row)
        assert row.size > 0
        assert pred.mask(ds.metadata, ds.vocab_sizes)[row].all()


# -- per-disjunct anchor quota (ROADMAP PR 4 follow-up) ----------------------

def _starved_or_setup():
    """Engineered dominant/rare OR pair (selectivities 0.5 / 0.001): 1500
    points around e0 all match the dominant disjunct (field 0 == 1) and
    form cluster 0, whose matched count alone exhausts the seed budget for
    any query near e0; the rare disjunct's 3 points (field 1 == 1) sit 2
    degrees off e0 in their own hand-assigned cluster 1, so they belong in
    the true top-10 of an e0 query but their cluster ranks strictly below
    cluster 0. The atlas is built from the explicit assignment (kmeans
    could fold the 3-point cluster into its big neighbour and mask the
    starvation)."""
    from repro.core.types import normalize

    rng = np.random.default_rng(17)
    d = 8
    e = np.eye(d, dtype=np.float32)
    n_dom, n_rare, n_far = 1500, 3, 1497
    dom = normalize(e[0] + 0.25 * rng.standard_normal((n_dom, d)))
    off = normalize(e[0] + np.tan(np.deg2rad(2.0)) * e[1])
    rare = normalize(off + 0.003 * rng.standard_normal((n_rare, d)))
    far = normalize(e[2] + 0.25 * rng.standard_normal((n_far, d)))
    vecs = np.concatenate([dom, rare, far]).astype(np.float32)
    n = vecs.shape[0]
    meta = np.zeros((n, 2), np.int32)
    meta[:n_dom, 0] = 1
    meta[n_dom:n_dom + n_rare, 1] = 1
    assign = np.concatenate([np.zeros(n_dom), np.ones(n_rare),
                             np.full(n_far, 2)]).astype(np.int32)
    centroids = np.stack([normalize(vecs[assign == c].mean(axis=0))
                          for c in range(3)])
    atlas = AnchorAtlas.from_assignment(centroids, assign, meta)
    rare_ids = np.arange(n_dom, n_dom + n_rare)
    return vecs, meta, atlas, rare_ids


def test_disjunct_quota_rescues_starved_disjunct():
    """Selection-level regression: without a quota, the dominant
    disjunct's nearest cluster swallows the whole seed budget and the rare
    disjunct's cluster is never visited; with the default quota the rare
    cluster is force-visited and its nearest passing members are seeded."""
    from repro.core.device_atlas import DeviceAtlas, pack_dnf
    from repro.core.predicate import as_dnf
    from repro.core.types import normalize

    vecs, meta, atlas, rare_ids = _starved_or_setup()
    pred = Or(In(0, [1]), In(1, [1]))
    assert abs(float(np.mean(meta[:, 0] == 1)) - 0.5) < 0.01
    assert float(np.mean(meta[:, 1] == 1)) == pytest.approx(0.001)
    datlas = DeviceAtlas.from_atlas(atlas)
    dnf = as_dnf(pred, [2, 2])
    f_np, a_np, _, _ = pack_dnf([dnf], v_cap=datlas.v_cap)
    q = np.eye(vecs.shape[1], dtype=np.float32)[0]
    passes = jnp.asarray(pred.mask(meta, [2, 2])[None])
    proc = jnp.zeros((1, 3), bool)
    args = (jnp.asarray(q[None]), (jnp.asarray(f_np), jnp.asarray(a_np)),
            proc, jnp.asarray(vecs), passes)
    seeds0, used0 = datlas.select_anchors_batch(*args, n_seeds=10, c_max=5,
                                                disjunct_quota=0)
    s0 = np.asarray(seeds0[0])
    assert not np.isin(s0, rare_ids).any(), "setup no longer starves"
    assert not bool(np.asarray(used0)[0, 1])
    seeds2, used2 = datlas.select_anchors_batch(*args, n_seeds=10, c_max=5,
                                                disjunct_quota=2)
    s2 = np.asarray(seeds2[0])
    assert np.isin(s2, rare_ids).sum() == 2, s2
    assert bool(np.asarray(used2)[0, 1])  # rare cluster consumed
    # main seeds still fill the budget; quota displaced, not duplicated
    assert (s2 >= 0).sum() == 10 and np.unique(s2).size == 10


def test_disjunct_quota_end_to_end_recall():
    """Through the fused engine with default params, the rare disjunct's
    members (which sit inside the true top-10) are returned — the failure
    this quota fixes is the loop ending with k dominant-only results."""
    vecs, meta, atlas, rare_ids = _starved_or_setup()
    graph = build_alpha_knn(vecs, k=8, r_max=24)
    index = FiberIndex(vecs, meta, graph, atlas)
    eng = BatchedEngine(index, BatchedParams(k=10, beam_width=4),
                        vocab_sizes=(2, 2))
    pred = Or(In(0, [1]), In(1, [1]))
    q = np.eye(vecs.shape[1], dtype=np.float32)[0]
    # precondition: all rare members belong in the exact filtered top-10
    passing = np.nonzero(pred.mask(meta, [2, 2]))[0]
    gt = passing[np.argsort(-(vecs[passing] @ q))[:10]]
    assert np.isin(rare_ids, gt).all(), "setup drifted: rare not in GT"
    ids, _ = eng.search([Query(vector=q, predicate=pred)])
    got = np.asarray(ids[0])
    assert np.isin(rare_ids, got).all(), got
    assert recall_at_k(got, gt) >= 0.9
