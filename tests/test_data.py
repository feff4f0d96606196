"""Data pipelines: determinism, synth structure."""
import numpy as np

from repro.data.synth import SynthSpec, make_dataset, make_queries
from repro.data.tokens import TokenPipeline


def test_token_pipeline_deterministic():
    a = TokenPipeline(vocab_size=100, batch=2, seq_len=16, seed=7)
    b = TokenPipeline(vocab_size=100, batch=2, seq_len=16, seed=7)
    for s in (0, 5, 99):
        ba, bb = a.get_batch(s), b.get_batch(s)
        np.testing.assert_array_equal(ba["tokens"], bb["tokens"])
    assert not np.array_equal(a.get_batch(0)["tokens"],
                              a.get_batch(1)["tokens"])


def test_labels_are_next_tokens():
    p = TokenPipeline(vocab_size=50, batch=2, seq_len=8, seed=0)
    b = p.get_batch(0)
    assert b["tokens"].shape == b["labels"].shape


def test_synth_dataset_structure(small_ds):
    norms = np.linalg.norm(small_ds.vectors, axis=1)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-4)
    assert small_ds.metadata.min() >= -1
    for f in range(small_ds.n_fields):
        col = small_ds.metadata[:, f]
        assert col[col >= 0].max() < small_ds.vocab_sizes[f]


def test_query_selectivity_spread(small_queries):
    sels = np.asarray([q.selectivity for q in small_queries])
    assert sels.min() < 0.02 and sels.max() > 0.1   # spans paper's range
    assert all(q.gt_ids.size > 0 for q in small_queries)


def test_filtered_topk_shared_matches_per_query(small_ds):
    """The shared-mask batch form returns the per-query ground truth, and
    an empty mask gives every query an empty answer."""
    from repro.data.ground_truth import filtered_topk, filtered_topk_shared

    rng = np.random.default_rng(3)
    qs = small_ds.vectors[rng.integers(0, small_ds.n, 6)]
    passes = small_ds.metadata[:, 0] == 1
    got = filtered_topk_shared(small_ds.vectors, qs, passes, 10)
    for q, ids in zip(qs, got):
        np.testing.assert_array_equal(
            ids, filtered_topk(small_ds.vectors, q, passes, 10)[0])
    none = filtered_topk_shared(small_ds.vectors, qs,
                                np.zeros(small_ds.n, bool), 10)
    assert [a.size for a in none] == [0] * len(qs)
