"""Serving: generate loop + RAG retrieval bridge."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.search import SearchParams
from repro.launch.mesh import make_mesh
from repro.models.transformer import ShardEnv, init_params
from repro.serve.engine import ServeEngine
from repro.serve.retrieval import EncodedRetriever, RetrievalService


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced_config("llama3.2-1b")
    mesh = make_mesh((1, 1), ("data", "model"))
    env = ShardEnv(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, env, params


def test_generate_shapes_and_determinism(tiny_model):
    cfg, env, params = tiny_model
    eng = ServeEngine(cfg, env, params)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16)), jnp.int32)
    out1 = eng.generate(toks, max_new=8)
    out2 = eng.generate(toks, max_new=8)
    assert out1.shape == (2, 8)
    assert (np.asarray(out1) < cfg.vocab_size).all()
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_query_batch_matches_filters():
    """Batched serving path (device-resident engine) without the LM: one
    predicate per query, results satisfy their own filters and fill k."""
    from repro.core.types import Dataset, FilterPredicate, normalize

    rng = np.random.default_rng(4)
    n, d = 1200, 32
    vecs = normalize(rng.standard_normal((n, d)))
    meta = rng.integers(0, 6, (n, 4)).astype(np.int32)
    ds = Dataset(vecs, meta, [f"f{i}" for i in range(4)], [6] * 4)
    svc = RetrievalService.build(ds, graph_k=12, r_max=36,
                                 params=SearchParams(k=5, max_hops=60))
    preds = [FilterPredicate.make({0: [1]}),
             FilterPredicate.make({1: [2], 2: [3, 4]}),
             FilterPredicate.make({})]
    ids, stats = svc.query_batch(rng.standard_normal((3, d)), preds)
    assert stats["walks"].shape == (3,)
    for pred, row in zip(preds, ids):
        row = np.asarray(row)
        assert row.size > 0
        assert pred.mask(meta)[row].all()
    assert np.asarray(ids[2]).size == 5  # unconstrained fills k


def test_query_batch_empty_and_singleton_bucket():
    """Serving-path regressions (ISSUE 3): an empty batch returns
    ``([], {})`` without building or dispatching the engine, and a
    singleton batch pads to MIN_BUCKET so Q=1 arrivals share the smallest
    bucket's compiled program instead of compiling their own shape."""
    from repro.core.types import Dataset, FilterPredicate, normalize
    from repro.serve.retrieval import MIN_BUCKET, RetrievalService

    rng = np.random.default_rng(9)
    n, d = 600, 16
    vecs = normalize(rng.standard_normal((n, d)))
    meta = rng.integers(0, 5, (n, 3)).astype(np.int32)
    ds = Dataset(vecs, meta, [f"f{i}" for i in range(3)], [5] * 3)
    svc = RetrievalService.build(ds, graph_k=8, r_max=24,
                                 params=SearchParams(k=5, max_hops=40))

    ids, stats = svc.query_batch(np.zeros((0, d)), [])
    assert ids == [] and stats == {}
    assert svc._engine is None  # empty batch never touches the engine

    eng = svc.engine()
    seen: list[int] = []
    orig = eng.search

    def spy(queries, **kw):
        seen.append(len(queries))
        return orig(queries, **kw)

    eng.search = spy
    try:
        d0 = eng.dispatches
        pred = FilterPredicate.make({0: [1]})
        ids, stats = svc.query_batch(rng.standard_normal((1, d)), [pred])
        assert len(ids) == 1 and stats["walks"].shape == (1,)
        assert eng.dispatches - d0 == 1
        # a 3-query arrival lands in the same bucket -> same program
        svc.query_batch(rng.standard_normal((3, d)), [pred] * 3)
        assert seen == [MIN_BUCKET, MIN_BUCKET]
        assert eng.dispatches - d0 == 2
    finally:
        eng.search = orig
    if hasattr(eng._search, "_cache_size"):
        assert eng._search._cache_size() == 1


def test_query_batch_wide_clause_widths_share_program():
    """Two predicates wider than MAX_CLAUSES but with different widths
    must pack to the same power-of-two clause dim (silent per-width
    recompiles were ISSUE 3's third serving bug)."""
    from repro.core.batched.engine import clause_dim
    from repro.core.types import Dataset, FilterPredicate, Query, normalize
    from repro.kernels.ops import MAX_CLAUSES
    from repro.serve.retrieval import RetrievalService

    assert clause_dim(0) == clause_dim(MAX_CLAUSES) == MAX_CLAUSES
    assert clause_dim(5) == clause_dim(7) == 8 and clause_dim(9) == 16

    rng = np.random.default_rng(5)
    n, d, f_count = 600, 16, 8
    vecs = normalize(rng.standard_normal((n, d)))
    meta = rng.integers(0, 4, (n, f_count)).astype(np.int32)
    ds = Dataset(vecs, meta, [f"f{i}" for i in range(f_count)],
                 [4] * f_count)
    svc = RetrievalService.build(ds, graph_k=8, r_max=24,
                                 params=SearchParams(k=5, max_hops=40))
    eng = svc.engine()

    def wide_query(width):  # clauses from a real row -> matches >= 1 point
        row = meta[0]
        pred = FilterPredicate.make(
            {f: [int(row[f]), (int(row[f]) + 1) % 4] for f in range(width)})
        return Query(vector=normalize(rng.standard_normal(d))
                     .astype(np.float32), predicate=pred)

    q5, q7 = wide_query(5), wide_query(7)
    _, f5, a5, _ = eng._pack_queries([q5])
    _, f7, a7, _ = eng._pack_queries([q7])
    assert f5.shape == f7.shape == (1, 8)
    assert a5.shape == a7.shape
    eng.search([q5])
    eng.search([q7])
    if hasattr(eng._search, "_cache_size"):
        assert eng._search._cache_size() == 1


def test_encoded_retriever(tiny_model):
    """True end-to-end RAG bridge: the corpus is built from MODEL-encoded
    documents, then model-encoded queries retrieve under a filter."""
    from repro.core.types import Dataset, FilterPredicate
    from repro.models.transformer import encode

    cfg, env, params = tiny_model
    rng = np.random.default_rng(0)
    docs = jnp.asarray(rng.integers(0, cfg.vocab_size, (256, 12)), jnp.int32)
    vecs = np.asarray(jax.jit(lambda p, b: encode(p, b, cfg, env))(
        params, {"tokens": docs}))
    meta = rng.integers(0, 4, (256, 3)).astype(np.int32)
    ds = Dataset(vecs, meta, [f"f{i}" for i in range(3)], [4, 4, 4])
    svc = RetrievalService.build(ds, graph_k=8, r_max=24,
                                 params=SearchParams(k=5, max_hops=50))
    retr = EncodedRetriever(cfg, env, params, svc)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    pred = FilterPredicate.make({0: [1, 2]})
    out = retr.retrieve(toks, pred)
    passes = pred.mask(meta)
    got_any = False
    for ids, sims, stats in out:
        if len(ids):
            got_any = True
            assert passes[np.asarray(ids)].all()
    assert got_any
    # batched path: same encoder, lockstep retrieval
    ids_b, _ = retr.retrieve_batch(toks, [pred, pred])
    assert any(len(i) for i in ids_b)
    for ids in ids_b:
        ids = np.asarray(ids)
        if ids.size:
            assert passes[ids].all()


def test_query_batch_isolates_bad_query():
    """One query whose expression blows the MAX_DISJUNCTS bound must not
    kill the batch (ISSUE 6 satellite): it gets an empty result and a
    per-query error entry in stats, while its batch-mates — categorical
    and interval Range alike — answer normally."""
    from repro.core.predicate import And, In, Or, Range
    from repro.core.types import Dataset, normalize

    rng = np.random.default_rng(11)
    n, d = 600, 16
    vecs = normalize(rng.standard_normal((n, d)))
    meta = np.empty((n, 5), np.int32)
    meta[:, :4] = rng.integers(0, 5, (n, 4))
    meta[:, 4] = rng.integers(0, 1 << 20, n)  # big-vocab timestamp field
    ds = Dataset(vecs, meta, ["a", "b", "c", "e", "ts"],
                 [5, 5, 5, 5, 1 << 20])
    svc = RetrievalService.build(ds, graph_k=8, r_max=24,
                                 params=SearchParams(k=5, max_hops=40))
    good_cat = In(0, [1]) | In(1, [2])
    good_rng = Range(4, 0, 1 << 19)
    # 2^4 = 16 distinct disjuncts (distinct fields, nothing merges)
    bad = And(*[Or(In(f, [0]), In(f, [1])) for f in range(4)])
    with pytest.raises(ValueError, match="max_disjuncts"):
        # sanity: alone, the bad query is a loud compile error
        svc.engine().search([_q(vecs[0], bad)])
    ids, stats = svc.query_batch(
        rng.standard_normal((3, d)), [good_cat, bad, good_rng])
    assert len(ids) == 3
    assert np.asarray(ids[1]).size == 0          # bad query: empty result
    assert stats["errors"][0] is None and stats["errors"][2] is None
    assert "max_disjuncts" in stats["errors"][1]
    for pred, row in ((good_cat, ids[0]), (good_rng, ids[2])):
        row = np.asarray(row)
        assert row.size > 0
        assert pred.mask(meta, ds.vocab_sizes)[row].all()
    # an all-good batch carries no errors key at all
    _, stats_ok = svc.query_batch(rng.standard_normal((2, d)),
                                  [good_cat, good_rng])
    assert "errors" not in stats_ok


def _q(vec, pred):
    from repro.core.types import Query, normalize
    return Query(vector=normalize(vec), predicate=pred)
