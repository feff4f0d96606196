"""DeviceAtlas parity: batched anchor selection must reproduce the host
atlas exactly, and the batched engine must match sequential recall across
filter selectivities (ISSUE 1 acceptance criteria)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.atlas import AnchorAtlas
from repro.core.device_atlas import pack_predicates
from repro.core.search import SearchParams, run_queries
from repro.core.types import FilterPredicate, Query, normalize
from repro.data.ground_truth import recall_at_k

from conftest import SELECTIVITIES, engine_over, walk_config


def _host_round(atlas, q, processed, vectors):
    return atlas.select_anchors(q.vector, q.predicate, processed,
                                n_seeds=10, c_max=5, vectors=vectors)


def _device_round(datlas, qs, ct, proc, vectors, passes):
    q_vecs = jnp.asarray(np.stack([q.vector for q in qs]))
    return datlas.select_anchors_batch(q_vecs, ct, proc, vectors, passes,
                                       n_seeds=10, c_max=5)


def test_single_query_seed_parity(small_ds, small_atlas, small_queries):
    """select_anchors_batch at Q=1 == host select_anchors: same seed sets
    and same consumed clusters, across the full multi-round processed-set
    evolution of Algorithm 2."""
    datlas = small_atlas.to_device()
    vectors = jnp.asarray(small_ds.vectors)
    for q in small_queries[:12]:
        processed: set[int] = set()
        proc = jnp.zeros((1, small_atlas.n_clusters), bool)
        ct = tuple(jnp.asarray(x) for x in pack_predicates([q.predicate]))
        passes = jnp.asarray(q.predicate.mask(small_ds.metadata)[None])
        for _ in range(4):
            seeds_h, used_h = _host_round(small_atlas, q, processed,
                                          small_ds.vectors)
            seeds_d, used_d = _device_round(datlas, [q], ct, proc, vectors,
                                            passes)
            sd = np.asarray(seeds_d[0])
            assert set(sd[sd >= 0].tolist()) == set(seeds_h)
            ud = np.nonzero(np.asarray(used_d[0]))[0]
            assert set(ud.tolist()) == set(used_h)
            processed.update(used_h)
            proc = proc | used_d
            if not seeds_h:
                break


def test_batch_seed_parity(small_ds, small_atlas, small_queries):
    """The whole batch in one call matches per-query host selection, with
    processed-cluster bookkeeping carried across restart rounds."""
    datlas = small_atlas.to_device()
    vectors = jnp.asarray(small_ds.vectors)
    qs = small_queries
    ct = tuple(jnp.asarray(x) for x in
               pack_predicates([q.predicate for q in qs]))
    passes = jnp.asarray(np.stack(
        [q.predicate.mask(small_ds.metadata) for q in qs]))
    processed = [set() for _ in qs]
    proc = jnp.zeros((len(qs), small_atlas.n_clusters), bool)
    for _ in range(3):
        seeds_d, used_d = _device_round(datlas, qs, ct, proc, vectors,
                                        passes)
        seeds_d, used_d = np.asarray(seeds_d), np.asarray(used_d)
        for qi, q in enumerate(qs):
            seeds_h, used_h = _host_round(small_atlas, q, processed[qi],
                                          small_ds.vectors)
            sd = seeds_d[qi]
            assert set(sd[sd >= 0].tolist()) == set(seeds_h), qi
            assert set(np.nonzero(used_d[qi])[0].tolist()) == set(used_h), qi
            processed[qi].update(used_h)
        proc = proc | jnp.asarray(used_d)


# (the engineered-selectivity ``sel_sweep`` fixture lives in conftest.py,
# shared with the fused single-dispatch parity tests)


def test_engineered_selectivities(sel_sweep):
    _, _, queries = sel_sweep
    sels = sorted({q.selectivity for q in queries}, reverse=True)
    for got, want in zip(sels, SELECTIVITIES):
        assert abs(got - want) < 0.4 * want, (got, want)


def test_recall_parity_across_selectivities(sel_sweep):
    """Batched engine recall within epsilon of the sequential reference at
    every selectivity level (ISSUE 1 satellite)."""
    _, index, queries = sel_sweep
    ids_seq, _ = run_queries(index, queries,
                             SearchParams(k=10, walk="guided", beam_width=2))
    eng = engine_over(index, walk_config(k=10, beam_width=4))
    ids_b, _ = eng.search(queries)
    for v, target in enumerate(SELECTIVITIES):
        idx = [i for i, q in enumerate(queries)
               if q.predicate.clauses[0][1] == (v,)]
        rec_seq = float(np.mean([recall_at_k(ids_seq[i], queries[i].gt_ids)
                                 for i in idx]))
        rec_b = float(np.mean([recall_at_k(np.asarray(ids_b[i]),
                                           queries[i].gt_ids)
                               for i in idx]))
        assert rec_b > rec_seq - 0.1, (target, rec_b, rec_seq)


def test_high_cardinality_vocab_auto_v_cap():
    """Metadata codes beyond the default 256-value bitmap: to_device
    auto-sizes (word-aligned) and selection parity still holds; an
    explicit too-small v_cap fails loudly."""
    from repro.core.types import Dataset
    rng = np.random.default_rng(11)
    n, d = 900, 24
    vectors = normalize(rng.standard_normal((n, d)))
    meta = rng.integers(0, 500, (n, 2)).astype(np.int32)
    ds = Dataset(vectors, meta, ["a", "b"], [500, 500])
    atlas = AnchorAtlas.build(ds, seed=0)
    datlas = atlas.to_device()
    assert datlas.v_cap >= 500 and datlas.v_cap % 32 == 0
    vec_j = jnp.asarray(ds.vectors)
    q = Query(vector=normalize(rng.standard_normal(d)),
              predicate=FilterPredicate.make({0: [int(meta[0, 0])], 1: [499]}))
    ct = tuple(jnp.asarray(x) for x in
               pack_predicates([q.predicate], v_cap=datlas.v_cap))
    passes = jnp.asarray(q.predicate.mask(meta)[None])
    proc = jnp.zeros((1, atlas.n_clusters), bool)
    seeds_d, used_d = _device_round(datlas, [q], ct, proc, vec_j, passes)
    seeds_h, used_h = _host_round(atlas, q, set(), ds.vectors)
    sd = np.asarray(seeds_d[0])
    assert set(sd[sd >= 0].tolist()) == set(seeds_h)
    assert set(np.nonzero(np.asarray(used_d[0]))[0].tolist()) == set(used_h)
    with pytest.raises(ValueError, match="larger v_cap"):
        atlas.to_device(v_cap=256)
