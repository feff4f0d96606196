"""Walk edge cases (ISSUE 3 satellite): an all-seeds-invalid lane, k
larger than the number of passing points, and a predicate matching exactly
one point — each must hold the fused single-dispatch ``search`` and the
host-loop baseline in exact agreement inside one mixed batch.
"""
import numpy as np
import pytest

from repro.core.types import FilterPredicate, Query, normalize

from conftest import engine_over, walk_config


def _pred_with_count(meta: np.ndarray, lo: int, hi: int):
    """A conjunctive predicate whose pass count falls in [lo, hi]."""
    n, f_count = meta.shape
    for f in range(f_count):
        col = meta[:, f]
        vals, counts = np.unique(col[col >= 0], return_counts=True)
        for v, c in zip(vals, counts):
            if lo <= c <= hi:
                return FilterPredicate.make({f: [int(v)]}), int(c)
    for i in range(n):  # widen to 3-field conjunctions of a real row
        if (meta[i, :3] < 0).any():
            continue
        pred = FilterPredicate.make({f: [int(meta[i, f])] for f in range(3)})
        c = int(pred.mask(meta).sum())
        if lo <= c <= hi:
            return pred, c
    pytest.skip(f"corpus has no predicate with {lo}..{hi} passing points")


def _edge_queries(small_ds):
    rng = np.random.default_rng(11)
    meta = small_ds.metadata
    # value code beyond every vocab: passes nothing, seeds nothing
    nomatch = FilterPredicate.make({0: [max(small_ds.vocab_sizes) + 7]})
    assert int(nomatch.mask(meta).sum()) == 0
    one_pred, one_c = _pred_with_count(meta, 1, 1)
    assert one_c == 1
    few_pred, few_c = _pred_with_count(meta, 2, 9)
    qv = lambda: normalize(rng.standard_normal(small_ds.d)).astype(np.float32)
    queries = [Query(vector=qv(), predicate=nomatch),
               Query(vector=qv(), predicate=one_pred),
               Query(vector=qv(), predicate=few_pred),
               Query(vector=qv(), predicate=FilterPredicate.make({}))]
    return queries, few_c


def test_edge_lanes_fused_vs_hostloop(small_ds, small_index):
    """Exact fused/host-loop parity on the edge lanes, mixed into one
    batch with an unconstrained lane (so the batch itself stays live while
    degenerate lanes idle)."""
    queries, few_c = _edge_queries(small_ds)
    k = 10
    eng = engine_over(small_index, walk_config(k=k, beam_width=4))
    ids_f, st_f = eng.search(queries)
    ids_h, st_h = eng.search_hostloop(queries)
    for i, (a, b) in enumerate(zip(ids_f, ids_h)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), i
    np.testing.assert_array_equal(st_f["walks"], st_h["walks"])
    np.testing.assert_array_equal(st_f["hops"], st_h["hops"])

    nomatch_ids = np.asarray(ids_f[0])
    assert nomatch_ids.size == 0          # all seeds invalid -> no results
    assert st_f["walks"][0] == 0          # that lane never walks

    one_ids = np.asarray(ids_f[1])
    passes_one = queries[1].predicate.mask(small_ds.metadata)
    assert np.array_equal(one_ids, np.nonzero(passes_one)[0])  # the point

    few_ids = np.asarray(ids_f[2])
    assert 0 < few_ids.size <= few_c < k  # can't exceed the passing set
    passes_few = queries[2].predicate.mask(small_ds.metadata)
    assert passes_few[few_ids].all()
    assert np.asarray(ids_f[3]).size == k  # unconstrained lane fills k


def test_all_lanes_degenerate_batch(small_ds, small_index):
    """A batch made ONLY of no-match lanes: nobody can seed, the fused
    round loop must exit without a walk, and both paths agree."""
    nomatch = FilterPredicate.make({0: [max(small_ds.vocab_sizes) + 7]})
    rng = np.random.default_rng(3)
    queries = [Query(vector=normalize(rng.standard_normal(small_ds.d))
                     .astype(np.float32), predicate=nomatch)
               for _ in range(4)]
    eng = engine_over(small_index, walk_config(k=5, beam_width=4))
    ids_f, st_f = eng.search(queries)
    ids_h, st_h = eng.search_hostloop(queries)
    for a, b in zip(ids_f, ids_h):
        assert np.asarray(a).size == 0 and np.asarray(b).size == 0
    assert (st_f["walks"] == 0).all() and (st_h["walks"] == 0).all()
    assert (st_f["hops"] == 0).all() and (st_h["hops"] == 0).all()
