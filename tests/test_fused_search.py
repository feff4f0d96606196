"""Fused single-dispatch search vs the PR 1 host-loop engine (ISSUE 2
acceptance): identical result ids and identical walks/hops stats across the
engineered selectivities, exactly one jitted call per batch, and
bitmap-packed walk state (O(Q*n/32) bytes instead of dense (Q, n) bools).
"""
import numpy as np
import jax.numpy as jnp

from repro.core.batched.bitmap import n_words, pack_bits
from repro.core.batched.engine import INF, walk_batch
from repro.core.config import WalkConfig
from conftest import SELECTIVITIES, engine_over, walk_config


def test_fused_matches_hostloop_exactly(sel_sweep):
    """search (one fused dispatch) == search_hostloop (PR 1 per-round jit):
    same ids in the same order, same per-query walks and hops, the same
    rounds and lockstep iterations, at every selectivity in the sweep."""
    _, index, queries = sel_sweep
    eng = engine_over(index, walk_config(k=10, beam_width=4))
    ids_f, st_f = eng.search(queries)
    ids_h, st_h = eng.search_hostloop(queries)
    assert len(ids_f) == len(queries)
    for i, (a, b) in enumerate(zip(ids_f, ids_h)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            (i, queries[i].selectivity)
    np.testing.assert_array_equal(st_f["walks"], st_h["walks"])
    np.testing.assert_array_equal(st_f["hops"], st_h["hops"])
    # the device's own loop counters equal the host loop's count of its
    # rounds and of the lockstep iterations each round's walk ran
    assert (st_f["rounds"], st_f["iters"]) == (st_h["rounds"],
                                               st_h["iters"])
    assert 1 <= st_f["rounds"] <= eng.p.jump_budget + 1
    assert st_f["iters"] >= int(st_f["hops"].max())
    # the sweep exercises all three selectivity levels and restarts
    sels = sorted({q.selectivity for q in queries}, reverse=True)
    for got, want in zip(sels, SELECTIVITIES):
        assert abs(got - want) < 0.4 * want, (got, want)
    assert (st_f["walks"] >= 1).all()


def test_search_is_single_dispatch(sel_sweep):
    """One batch = one compiled-callable invocation: the fused program is
    called exactly once and the per-round path not at all."""
    _, index, queries = sel_sweep
    eng = engine_over(index, walk_config(k=10, beam_width=4))
    calls = {"search": 0, "round": 0, "passes": 0}
    orig_search, orig_round, orig_passes = (eng._search, eng._round,
                                            eng._passes)

    def _count(key, fn):
        def wrapped(*a, **k):
            calls[key] += 1
            return fn(*a, **k)
        return wrapped

    eng._search = _count("search", orig_search)
    eng._round = _count("round", orig_round)
    eng._passes = _count("passes", orig_passes)
    d0 = eng.dispatches
    ids, stats = eng.search(queries)
    assert calls == {"search": 1, "round": 0, "passes": 0}
    assert eng.dispatches - d0 == 1
    assert any(np.asarray(i).size for i in ids)
    # second batch: still exactly one dispatch each
    eng.search(queries[:8])
    assert calls["search"] == 2 and calls["round"] == 0


def test_walk_state_is_bitmap_packed(small_index, small_queries):
    """walk_batch consumes packed (Q, ceil(n/32)) uint32 pass bitmaps and
    carries packed visited state — no dense (Q, n) bool mask survives in
    the walk's interface."""
    n = small_index.vectors.shape[0]
    qs = small_queries[:4]
    passes = np.stack([q.predicate.mask(small_index.metadata) for q in qs])
    pass_bm = pack_bits(jnp.asarray(passes))
    assert pass_bm.shape == (4, n_words(n)) and pass_bm.dtype == jnp.uint32
    q_vecs = jnp.asarray(np.stack([q.vector for q in qs]))
    seeds = np.full((4, 6), -1, np.int32)
    for qi in range(4):
        ok = np.nonzero(passes[qi])[0][:6]
        seeds[qi, :ok.size] = ok
    out = walk_batch(jnp.asarray(small_index.vectors),
                     jnp.asarray(small_index.graph.neighbors),
                     pass_bm, q_vecs, jnp.asarray(seeds),
                     WalkConfig(k=5, beam_width=4))
    assert out["visited_bm"].shape == pass_bm.shape
    assert out["visited_bm"].dtype == jnp.uint32
    res_v = np.asarray(out["res_v"])
    res_i = np.asarray(out["res_i"])
    for qi in range(4):
        ids = res_i[qi][res_v[qi] < float(INF) / 2]
        assert ids.size > 0
        assert passes[qi][ids].all()


def test_fused_results_pass_filters(sel_sweep):
    _, index, queries = sel_sweep
    eng = engine_over(index, walk_config(k=10, beam_width=4))
    ids, _ = eng.search(queries)
    for q, row in zip(queries, ids):
        row = np.asarray(row)
        if row.size:
            passes = q.predicate.mask(index.metadata)
            assert passes[row].all()
            assert row.size == np.unique(row).size
