"""Dynamic inserts (ISSUE 5 acceptance): the append path — per-shard
capacity slabs, reverse-edge graph repair, incremental atlas updates —
must be indistinguishable (to within 2 recall points) from tearing the
index down and rebuilding it from scratch, after ANY tested interleaving
of insert_batch / search calls, at selectivities {0.5, 0.1, 0.02}, for
conjunctive and disjunctive predicates, on the single-device engine and a
4-shard virtual mesh — and ``search_batch`` must keep its one-dispatch /
one-host-sync contract throughout.

Ground truth is recomputed per checkpoint by brute force over the rows
valid at that moment, so every comparison is against the corpus the
engine actually serves.
"""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import AnchorAtlas, FiberIndex, build_alpha_knn
from repro.core.batched.engine import BatchedEngine, BatchedParams
from repro.core.predicate import Range
from repro.core.types import Dataset, FilterPredicate, Query

MULTI = len(jax.devices()) >= 4
SELS = (0.5, 0.1, 0.02)
GRAPH = dict(graph_k=16, r_max=48)
PARAMS = BatchedParams(k=10, beam_width=4)


# -- harness -----------------------------------------------------------------

def _full_dataset():
    """Corpus with engineered conjunctive selectivities {0.5, 0.1, 0.02}
    plus the two-field OR pair (union selectivities {0.1, 0.02})."""
    from repro.data.synth import add_or_pair_fields, make_selectivity_dataset

    return add_or_pair_fields(
        make_selectivity_dataset(SELS, n=1000, d=32, n_components=12,
                                 seed=7), sels=(0.1, 0.02))


def _harness_queries(ds):
    """(label, query) pairs: 6 per conjunctive selectivity + 4 per OR-pair
    selectivity, batched together so inserts are exercised against mixed
    conjunctive/disjunctive clause tables."""
    from repro.data.synth import make_or_queries, make_selectivity_queries

    out = []
    for code, sel in enumerate(SELS):
        for q in make_selectivity_queries(ds, code, 6):
            out.append((f"sel{sel}", q))
    for code, sel in enumerate((0.1, 0.02)):
        for q in make_or_queries(ds, code + 1, 4):
            out.append((f"or{sel}", q))
    return out


def _brute_gt(vectors, metadata, n_valid, q, k, vocab):
    """Exact filtered top-k over the currently valid rows."""
    meta = metadata[:n_valid]
    passing = np.nonzero(q.predicate.mask(meta, vocab))[0]
    if passing.size == 0:
        return passing
    sims = vectors[:n_valid][passing] @ q.vector
    return passing[np.argsort(-sims)[:k]]


def _recall(ids, gt):
    if gt.size == 0:
        return 1.0
    return np.intersect1d(np.asarray(ids), gt).size / gt.size


def _grouped_recalls(labeled, all_ids, vectors, metadata, n_valid, vocab,
                     k=10):
    by: dict = {}
    for (label, q), ids in zip(labeled, all_ids):
        gt = _brute_gt(vectors, metadata, n_valid, q, k, vocab)
        by.setdefault(label, []).append(_recall(ids, gt))
    return {label: float(np.mean(v)) for label, v in by.items()}


def _build_single_engine(vectors, metadata, vocab, capacity=None):
    n = vectors.shape[0]
    ds = Dataset(vectors[:n], metadata[:n],
                 [f"f{i}" for i in range(metadata.shape[1])], list(vocab))
    graph = build_alpha_knn(ds.vectors, k=GRAPH["graph_k"],
                            r_max=GRAPH["r_max"])
    atlas = AnchorAtlas.build(ds, seed=0)
    index = FiberIndex(ds.vectors, ds.metadata, graph, atlas)
    return BatchedEngine(index, PARAMS, vocab_sizes=vocab,
                         capacity=capacity, graph_k=GRAPH["graph_k"])


def _run_interleaving(make_engine, rebuild_engine, ds, chunks,
                      tol=0.02):
    """Execute an insert/search interleaving and, at every search point,
    compare per-selectivity filtered recall@10 of the grown engine against
    a from-scratch rebuild over the same rows in the same id order.
    ``chunks`` is a list of insert batch sizes; a search checkpoint runs
    before the first insert and after every chunk."""
    vocab = tuple(ds.vocab_sizes)
    labeled = _harness_queries(ds)
    queries = [q for _, q in labeled]
    base_n = ds.n - sum(chunks)
    eng = make_engine(ds.vectors[:base_n], ds.metadata[:base_n], vocab,
                      capacity=ds.n)
    written = base_n
    next_gid = base_n
    for ci in range(len(chunks) + 1):
        d0 = eng.dispatches
        ids_dyn, _ = eng.search(queries)
        assert eng.dispatches - d0 == 1, \
            "insert broke the one-dispatch contract"
        rec_dyn = _grouped_recalls(labeled, ids_dyn, ds.vectors,
                                   ds.metadata, written, vocab)
        if ci == 0:
            # checkpoint 0 is the freshly built base index: parity is
            # definitional, skip the redundant rebuild
            rec_reb = rec_dyn
        else:
            reb = rebuild_engine(ds.vectors[:written],
                                 ds.metadata[:written], vocab)
            ids_reb, _ = reb.search(queries)
            rec_reb = _grouped_recalls(labeled, ids_reb, ds.vectors,
                                       ds.metadata, written, vocab)
        for label in rec_dyn:
            assert rec_dyn[label] >= rec_reb[label] - tol, (
                ci, label, rec_dyn[label], rec_reb[label])
        if ci < len(chunks):
            b = chunks[ci]
            gids = eng.insert_batch(ds.vectors[written:written + b],
                                    ds.metadata[written:written + b])
            np.testing.assert_array_equal(
                np.asarray(gids), np.arange(next_gid, next_gid + b))
            written += b
            next_gid += b
    return eng


@pytest.fixture(scope="module")
def full_ds():
    return _full_dataset()


# -- rebuild-parity harness (the headline deliverable) -----------------------

def test_rebuild_parity_all_at_once(full_ds):
    """Insert 25% of the corpus in one batch: recall@10 per selectivity
    (conjunctive and disjunctive) within 2 points of a from-scratch
    rebuild."""
    _run_interleaving(_build_single_engine,
                      lambda v, m, vo: _build_single_engine(v, m, vo),
                      full_ds, [250])


def test_rebuild_parity_interleaved(full_ds):
    """search / insert / search / insert / search: parity must hold at
    every intermediate corpus, not just the final one."""
    _run_interleaving(_build_single_engine,
                      lambda v, m, vo: _build_single_engine(v, m, vo),
                      full_ds, [125, 125])


def test_sharded_rebuild_parity(full_ds):
    """The same harness through the 4-shard mesh engine: balance-aware
    placement + per-shard graph patch + atlas refresh vs a from-scratch
    ``build_sharded_index`` of the grown corpus."""
    if not MULTI:
        pytest.skip("needs >= 4 devices (multi-device CI job)")
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(data=4, model=1)

    def make(vectors, metadata, vocab, capacity=None):
        sidx = build_sharded_index(vectors, metadata, 4, capacity=capacity,
                                   **GRAPH)
        return ShardedEngine(sidx, mesh, PARAMS)

    _run_interleaving(make, lambda v, m, vo: make(v, m, vo), full_ds,
                      [125, 125])


SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src"); sys.path.insert(0, "tests")
    import numpy as np
    from test_insert import GRAPH, PARAMS, _full_dataset, _run_interleaving
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.launch.mesh import make_local_mesh

    ds = _full_dataset()
    mesh = make_local_mesh(data=4, model=1)

    def make(vectors, metadata, vocab, capacity=None):
        sidx = build_sharded_index(vectors, metadata, 4, capacity=capacity,
                                   **GRAPH)
        return ShardedEngine(sidx, mesh, PARAMS)

    eng = _run_interleaving(make, lambda v, m, vo: make(v, m, vo), ds,
                            [250])
    assert eng.insert_stats["inserted_rows"] == 250
    print("sharded-insert-parity ok")
""")


@pytest.mark.slow
def test_sharded_insert_parity_subprocess():
    """The 4-shard insert/rebuild parity harness on 8 virtual CPU devices,
    regardless of the session's real device count."""
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                       capture_output=True, text=True, timeout=420, cwd=".")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sharded-insert-parity ok" in r.stdout


# -- satellite: property-based interleavings ---------------------------------

def _tiny_ds(n=320, d=16, seed=3):
    from repro.data.synth import make_selectivity_dataset

    return make_selectivity_dataset((0.5, 0.2), n=n, d=d, n_components=6,
                                    seed=seed)


@settings(max_examples=4, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=40), min_size=1,
                max_size=3))
def test_property_random_interleavings(chunk_sizes):
    """Random insert/search interleavings through ``build_sharded_index``
    (S = what the session's devices allow): (a) post-insert filtered
    recall within 2 points of a fresh rebuild, (b) every inserted id is
    findable by its own vector under a predicate it satisfies, (c) the
    row-validity bitmaps admit exactly the written slab rows."""
    from repro.core.batched.bitmap import unpack_bits
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.launch.mesh import make_local_mesh

    ds = _tiny_ds()
    vocab = tuple(ds.vocab_sizes)
    total = sum(chunk_sizes)
    base_n = ds.n - total
    n_shards = min(4, 1 << (len(jax.devices()).bit_length() - 1))
    mesh = make_local_mesh(data=n_shards, model=1)
    p = BatchedParams(k=5, beam_width=2)

    def make(n_rows, capacity=None):
        sidx = build_sharded_index(ds.vectors[:n_rows], ds.metadata[:n_rows],
                                   n_shards, graph_k=8, r_max=16,
                                   capacity=capacity)
        return ShardedEngine(sidx, mesh, p)

    eng = make(base_n, capacity=ds.n)
    written = base_n
    for b in chunk_sizes:
        gids = eng.insert_batch(ds.vectors[written:written + b],
                                ds.metadata[written:written + b])
        written += b
        # (c) bitmap == written rows, exactly, on every shard
        got = np.asarray(unpack_bits(eng.valid_bm,
                                     eng._istate.shards[0].cap))
        want = np.stack([sl.valid for sl in eng._istate.shards])
        np.testing.assert_array_equal(got, want)
        assert int(want.sum()) == written
        # (b) each fresh insert findable by its own vector + a predicate
        # it satisfies
        rows = np.arange(written - b, written)
        queries = [Query(vector=ds.vectors[r],
                         predicate=FilterPredicate.make(
                             {0: [int(ds.metadata[r, 0])]}))
                   for r in rows[:8]]
        ids, _ = eng.search(queries)
        for g, got_ids in zip(gids[:8], ids):
            assert int(g) in np.asarray(got_ids).tolist()
    # (a) final recall parity vs a fresh rebuild of the grown corpus
    from repro.data.synth import make_selectivity_queries

    labeled = [("sel", q) for code in (0, 1)
               for q in make_selectivity_queries(ds, code, 10)]
    queries = [q for _, q in labeled]
    ids_dyn, _ = eng.search(queries)
    reb = make(written)
    ids_reb, _ = reb.search(queries)
    gts = [_brute_gt(ds.vectors, ds.metadata, written, q, 5, vocab)
           for _, q in labeled]
    rec_dyn = np.mean([_recall(a, gt) for a, gt in zip(ids_dyn, gts)])
    rec_reb = np.mean([_recall(a, gt) for a, gt in zip(ids_reb, gts)])
    assert rec_dyn >= rec_reb - 0.02 - 1e-9, (rec_dyn, rec_reb)


# -- satellite: unwritten rows can never surface -----------------------------

def test_unconstrained_search_never_returns_unwritten(full_ds):
    """An unconstrained predicate passes every VALID row; capacity-slab
    tail rows (zero vectors — cosine-similar to nothing, but adversarially
    'passing' any empty clause table) must be fenced by the validity
    bitmap alone."""
    ds = full_ds
    base_n = 600
    eng = _build_single_engine(ds.vectors[:base_n], ds.metadata[:base_n],
                               tuple(ds.vocab_sizes), capacity=ds.n)
    rng = np.random.default_rng(0)
    queries = [Query(vector=v, predicate=FilterPredicate.make({}))
               for v in ds.vectors[rng.integers(0, base_n, 6)]]
    ids, _ = eng.search(queries)
    for row in ids:
        row = np.asarray(row)
        assert row.size == PARAMS.k
        assert (row < base_n).all(), "unwritten capacity row surfaced"
    eng.insert_batch(ds.vectors[base_n:base_n + 50],
                     ds.metadata[base_n:base_n + 50])
    ids, _ = eng.search(queries)
    for row in ids:
        assert (np.asarray(row) < base_n + 50).all()


# -- unit tests for the append-path building blocks --------------------------

def test_assign_shards_balanced():
    from repro.core.graph import assign_shards_balanced

    plan = assign_shards_balanced([5, 2, 2], 6, 5)
    assert plan.tolist() == [1, 2, 1, 2, 1]
    fill = np.bincount(plan, minlength=3) + [5, 2, 2]
    assert fill.max() - fill.min() <= 1
    assert (fill <= 6).all()
    # capacity overflow must be loud
    with pytest.raises(ValueError):
        assign_shards_balanced([6, 6], 6, 1)
    # full shards are skipped even when least-filled would overflow
    plan = assign_shards_balanced([6, 0], 6, 6)
    assert plan.tolist() == [1] * 6


def test_patch_adjacency_reverse_edge_repair():
    from repro.core.graph import build_alpha_knn, patch_adjacency
    from repro.core.types import normalize

    rng = np.random.default_rng(1)
    n_before, n_new, d = 200, 40, 16
    vecs = normalize(rng.standard_normal((n_before + n_new, d)))
    g = build_alpha_knn(vecs[:n_before], k=8, r_max=12)
    r = g.r_pad
    adj = np.full((n_before + n_new, r), -1, np.int32)
    adj[:n_before] = g.neighbors
    stats = patch_adjacency(adj, vecs, n_before, n_before + n_new,
                            k=8, alpha=1.2)
    assert stats["edges_added"] > 0
    miss = total = 0
    for x in range(n_before, n_before + n_new):
        nbrs = adj[x][adj[x] >= 0]
        # k forward edges, possibly + reverse edges from later batch rows
        assert min(8, r) <= nbrs.size <= r, x
        assert (nbrs < n_before + n_new).all() and x not in nbrs
        assert nbrs.size == np.unique(nbrs).size
        for y in nbrs:
            total += 1
            miss += int(x not in adj[y])
    # reverse edges are the norm; they go missing only through the α-RNG
    # repair of saturated rows (which may also evict earlier additions)
    assert miss < total / 2, (miss, total)
    if miss:
        assert stats["repairs"] > 0
    # every row stays within width and free of duplicates
    for row in adj:
        live = row[row >= 0]
        assert live.size == np.unique(live).size


def test_recluster_trigger_and_drift():
    """Pouring inserts onto one spot must trip the occupancy/drift
    trigger, re-cluster that shard (same K), and keep search correct."""
    ds = _tiny_ds(n=300)
    eng = _build_single_engine(ds.vectors[:200], ds.metadata[:200],
                               tuple(ds.vocab_sizes), capacity=300)
    assert eng.insert_stats["reclusters"] == 0
    rng = np.random.default_rng(5)
    from repro.core.types import normalize
    spot = ds.vectors[3]
    hot_v = normalize(spot + 0.02 * rng.standard_normal((100, ds.d)))
    hot_m = np.tile(ds.metadata[3], (100, 1))
    gids = eng.insert_batch(hot_v, hot_m)
    st = eng.insert_stats
    assert st["reclusters"] >= 1
    assert st["inserted_rows"] == 100
    assert eng.datlas.n_clusters == eng.index.atlas.n_clusters  # K fixed
    q = Query(vector=hot_v[0],
              predicate=FilterPredicate.make({0: [int(hot_m[0, 0])]}))
    ids, _ = eng.search([q])
    assert int(gids[0]) in np.asarray(ids[0]).tolist()


def test_insert_capacity_and_vocab_guards():
    ds = _tiny_ds(n=260)
    eng = _build_single_engine(ds.vectors[:250], ds.metadata[:250],
                               tuple(ds.vocab_sizes), capacity=260)
    # past-capacity inserts now GROW the slab (DESIGN.md §12) instead of
    # raising; auto_grow=False restores the hard-capacity error
    eng.cfg = eng.cfg.with_knobs({"maintenance.auto_grow": False})
    with pytest.raises(ValueError, match="capacity"):
        eng.insert_batch(ds.vectors[:20], ds.metadata[:20])
    eng.cfg = eng.cfg.with_knobs({"maintenance.auto_grow": True})
    gids = eng.insert_batch(ds.vectors[:20], ds.metadata[:20])
    assert gids.size == 20
    assert eng.insert_stats["slab_growths"] == 1
    assert eng.cfg.serve.capacity == eng.state.shards[0].cap > 260
    # codes past the atlas value bitmaps are legal, as at build time: the
    # per-cluster envelopes serve them and the engine's domains grow to
    # cover them (the service checks its declared vocabularies instead)
    g_big = eng.insert_batch(ds.vectors[250:251],
                             np.full((1, ds.metadata.shape[1]), 10 ** 6,
                                     np.int32))
    assert min(eng.vocab_sizes) > 10 ** 6
    ids, _ = eng.search([Query(vector=ds.vectors[250],
                               predicate=Range(0, 10 ** 6, 10 ** 6))])
    assert np.asarray(ids[0]).tolist() == [int(g_big[0])]
    # an engine without capacity refuses inserts with guidance
    fixed = _build_single_engine(ds.vectors[:250], ds.metadata[:250],
                                 tuple(ds.vocab_sizes))
    with pytest.raises(ValueError, match="capacity"):
        fixed.insert_batch(ds.vectors[250:], ds.metadata[250:])
    # a build-once sharded index must refuse too, not silently absorb
    # rows into its ceil(n/S) padding slack
    from repro.core.batched.sharded import ShardedEngine, build_sharded_index
    from repro.launch.mesh import make_local_mesh

    sidx = build_sharded_index(ds.vectors[:250], ds.metadata[:250], 1,
                               graph_k=8, r_max=16)
    assert sidx.insert_state is None
    seng = ShardedEngine(sidx, make_local_mesh(data=1, model=1),
                         BatchedParams(k=5, beam_width=2))
    with pytest.raises(ValueError, match="capacity"):
        seng.insert_batch(ds.vectors[250:], ds.metadata[250:])


def test_serve_ingest_and_staleness():
    """Serving path: ingest routes to the live engine, new docs answer the
    very next query_batch, and staleness accounting reports the dynamic
    fraction + the sequential index's lag."""
    from repro.core.search import SearchParams
    from repro.serve.retrieval import RetrievalService

    ds = _tiny_ds(n=300)
    base = Dataset(ds.vectors[:260], ds.metadata[:260], ds.field_names,
                   ds.vocab_sizes)
    svc = RetrievalService.build(base, graph_k=8, r_max=24,
                                 params=SearchParams(k=5, max_hops=40),
                                 capacity=300)
    st = svc.staleness()
    assert st["inserted_rows"] == 0 and st["free_capacity"] == 40
    gids = svc.ingest(ds.vectors[260:280], ds.metadata[260:280])
    assert gids.tolist() == list(range(260, 280))
    preds = [FilterPredicate.make({0: [int(ds.metadata[r, 0])]})
             for r in range(260, 264)]
    ids, _ = svc.query_batch(ds.vectors[260:264], preds)
    for g, row in zip(gids, ids):
        assert int(g) in np.asarray(row).tolist()
    st = svc.staleness()
    assert st["inserted_rows"] == 20
    assert st["corpus_rows"] == 280
    assert st["free_capacity"] == 20
    assert 0 < st["dynamic_fraction"] < 1
    assert st["sequential_index_stale_rows"] == 20  # eager global build
    # a service without reserved capacity refuses ingest loudly
    svc2 = RetrievalService.build(base, graph_k=8, r_max=24,
                                  params=SearchParams(k=5))
    with pytest.raises(ValueError, match="capacity"):
        svc2.ingest(ds.vectors[260:280], ds.metadata[260:280])


# -- satellite (ISSUE 6): ingest must widen the memoized domains -------------

def test_not_sees_brand_new_code_after_insert():
    """``FiberIndex.vocab_sizes()`` is memoized at build time; an insert
    that introduces a brand-new code must extend both the engine's and the
    index's per-field domains, or ``Not`` / open-ended ``Range`` queries
    keep lowering against the stale domain and silently exclude every
    newly inserted row."""
    from repro.core.graph import build_alpha_knn
    from repro.core.predicate import In, Not, Range
    from repro.core.types import normalize

    ds = _tiny_ds(n=260)
    base_n = 200
    d0 = Dataset(ds.vectors[:base_n], ds.metadata[:base_n],
                 ds.field_names, list(ds.vocab_sizes))
    graph = build_alpha_knn(d0.vectors, k=GRAPH["graph_k"],
                            r_max=GRAPH["r_max"])
    atlas = AnchorAtlas.build(d0, seed=0)
    index = FiberIndex(d0.vectors, d0.metadata, graph, atlas)
    # engine derives (and the index memoizes) domains from the base rows
    eng = BatchedEngine(index, PARAMS, capacity=260,
                        graph_k=GRAPH["graph_k"])
    new_code = int(ds.metadata[:base_n, 0].max()) + 1
    assert eng.vocab_sizes[0] == new_code  # stale domain excludes it
    rng = np.random.default_rng(9)
    n_new = 40
    new_v = normalize(rng.standard_normal((n_new, ds.d))
                      ).astype(np.float32)
    new_m = np.zeros((n_new, ds.metadata.shape[1]), np.int32)
    new_m[:, 0] = new_code
    gids = eng.insert_batch(new_v, new_m)
    assert eng.vocab_sizes[0] == new_code + 1
    assert index.vocab_sizes()[0] == new_code + 1
    new_ids = set(int(g) for g in gids)
    for pred in (Not(In(0, [0])), Range(0, new_code - 1, None)):
        ids, _ = eng.search([Query(vector=new_v[0], predicate=pred)])
        row = np.asarray(ids[0])
        assert row.size > 0
        assert new_ids & set(row.tolist()), (
            f"{pred} missed every inserted new-code row: stale domain")
