"""The lockstep engine on one shard vs the sequential reference: parity
+ invariants."""
import numpy as np

from repro.core.search import SearchParams, run_queries
from repro.data.ground_truth import recall_at_k

from conftest import engine_over, walk_config


def test_batched_recall_parity(small_index, small_queries):
    ids_ref, _ = run_queries(small_index, small_queries,
                             SearchParams(k=10, walk="guided", beam_width=2))
    rec_ref = np.mean([recall_at_k(i, q.gt_ids)
                       for i, q in zip(ids_ref, small_queries)])
    eng = engine_over(small_index, walk_config(k=10, beam_width=4))
    ids_b, stats = eng.search(small_queries)
    rec_b = np.mean([recall_at_k(np.asarray(i), q.gt_ids)
                     for i, q in zip(ids_b, small_queries)])
    assert rec_b > rec_ref - 0.08, (rec_b, rec_ref)


def test_batched_results_pass_filter(small_index, small_queries):
    eng = engine_over(small_index, walk_config(k=10, beam_width=4))
    ids_b, _ = eng.search(small_queries)
    for q, ids in zip(small_queries, ids_b):
        ids = np.asarray(ids)
        if ids.size:
            passes = q.predicate.mask(small_index.metadata)
            assert passes[ids].all()


def test_batched_results_distinct_across_restarts(small_index, small_queries):
    """A node re-reached after a restart must not occupy two result slots
    (cross-round dedup; the sequential engine dedupes via its results
    dict). Regression for the multi-walk duplicate-id bug."""
    eng = engine_over(small_index, walk_config(k=25, beam_width=4))
    ids_b, stats = eng.search(small_queries)
    assert (stats["walks"] > 1).any(), "sweep must exercise restarts"
    for ids in ids_b:
        ids = np.asarray(ids)
        assert ids.size == np.unique(ids).size


def test_batched_deterministic(small_index, small_queries):
    eng = engine_over(small_index, walk_config(k=10, beam_width=4))
    a, _ = eng.search(small_queries[:8], seed=3)
    b, _ = eng.search(small_queries[:8], seed=3)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
