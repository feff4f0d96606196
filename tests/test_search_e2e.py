"""End-to-end filtered search: recall, failure rate, restart recovery."""
import math

import numpy as np
import pytest

from repro.core.search import SearchParams, run_queries, search
from repro.data.ground_truth import recall_at_k


def test_guided_recall_and_failures(small_index, small_queries):
    params = SearchParams(k=10, walk="guided", beam_width=2)
    ids, stats = run_queries(small_index, small_queries, params)
    recs = [recall_at_k(i, q.gt_ids) for i, q in zip(ids, small_queries)]
    assert np.mean(recs) > 0.6
    assert np.mean([r == 0.0 for r in recs]) < 0.05   # near-zero failure


def test_beam_recall(small_index, small_queries):
    params = SearchParams(k=10, walk="beam", beam_width=40)
    ids, _ = run_queries(small_index, small_queries, params)
    recs = [recall_at_k(i, q.gt_ids) for i, q in zip(ids, small_queries)]
    assert np.mean(recs) > 0.6


def test_results_sorted_and_filtered(small_index, small_queries):
    params = SearchParams(k=10)
    for qi, q in enumerate(small_queries[:8]):
        ids, sims, _ = search(small_index, q.vector, q.predicate, params,
                              seed=qi)
        assert (np.diff(sims) <= 1e-6).all()            # descending
        passes = q.predicate.mask(small_index.metadata)
        assert passes[ids].all()


def test_restart_budget_respected(small_index, small_queries):
    params = SearchParams(k=10, jump_budget=2)
    _, stats = run_queries(small_index, small_queries, params)
    assert max(s.n_walks for s in stats) <= 3


@pytest.fixture(scope="module")
def wide_service():
    """The catalogue's width (d=2048, 24 Zipf fields, the paper's index
    settings) at n=4,096, with the within-component spread scaled by
    sqrt(64/d): the relative spread of the d=64 test corpus, so true
    neighbourhoods exist for a graph walk to follow."""
    from repro.configs.fns import PAPER
    from repro.core.config import FnsConfig
    from repro.data.synth import SynthSpec, make_dataset
    from repro.serve.retrieval import RetrievalService

    d = 2048
    spread = math.sqrt(64 / d)
    ds = make_dataset(SynthSpec(n=4096, d=d, n_fields=24,
                                noise=0.35 * spread, seed=0))
    cfg = FnsConfig().with_knobs({
        "graph.graph_k": PAPER.graph_k, "graph.r_max": PAPER.r_max,
        "graph.alpha": PAPER.alpha, "walk.k": PAPER.k,
        "walk.jump_budget": PAPER.jump_budget, "walk.c_max": PAPER.c_max,
        "walk.n_seeds": PAPER.n_seeds, "walk.max_hops": PAPER.max_hops,
        "walk.frontier_width": PAPER.frontier_width,
        "walk.stall_budget": PAPER.stall_budget})
    return ds, RetrievalService.build(ds, config=cfg), spread


@pytest.mark.parametrize("target", [0.5, 0.1, 0.02])
def test_walk_navigates_at_catalogue_width(wide_service, target):
    """At d=2048 the walk must find the filtered top-25 far better than a
    visit of as many rows drawn at random (hops x mean degree of n): what
    separates a walk that navigates from one that only samples."""
    from repro.core.types import FilterPredicate, normalize
    from repro.data.ground_truth import filtered_topk_shared

    ds, svc, spread = wide_service
    best = None
    for f in range(ds.n_fields):            # the field that fills closest
        col = ds.metadata[:, f]
        counts = np.bincount(col[col >= 0])
        vals, acc = [], 0
        for v in np.argsort(-counts, kind="stable"):
            if 0 < counts[v] and acc + counts[v] <= target * ds.n:
                vals.append(int(v))
                acc += int(counts[v])
        if not vals:
            continue
        err = abs(math.log(acc / (target * ds.n)))
        if best is None or err < best[0]:
            best = (err, f, vals)
    pred = FilterPredicate.make({best[1]: best[2]})
    passes = pred.mask(ds.metadata)
    rng = np.random.default_rng(1)
    src = rng.choice(np.nonzero(passes)[0], size=32)
    qv = normalize(ds.vectors[src] + 0.15 * spread
                   * rng.standard_normal((32, ds.d)))
    ids, stats = svc.query_batch(qv, [pred] * 32)
    gt = filtered_topk_shared(ds.vectors, qv, passes, 25)
    rec = np.mean([recall_at_k(np.asarray(i), g) for i, g in zip(ids, gt)])
    rows = stats["hops"] * svc.index.graph.degrees.mean()
    random_visit = np.mean(np.minimum(1.0, rows / ds.n))
    assert all(passes[np.asarray(i)].all() for i in ids)
    assert rec >= 0.85, (rec, random_visit)
    assert rec >= 2 * random_visit, (rec, random_visit)
