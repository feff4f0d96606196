"""The lockstep walk probes one 2-bit state map a hop: every output of
``walk_batch`` is bit for bit what the three-bitmap walk it replaced
gives, kept here as the oracle (``three_bitmap_walk``: a visited bitmap, an
in-results bitmap and the pass bitmap, one word gather each a hop)."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.batched.bitmap import (bit_of, gather_words, n_words,
                                       pack_bits, set_bits, unpack_bits)
from repro.core.batched.engine import (HIGHEST, INF, TERM_CONVERGED,
                                       TERM_EARLY, TERM_MAXHOP, TERM_RUNNING,
                                       TERM_STALL, _merge_queue, _pop,
                                       _running_first, stage_widths,
                                       walk_batch)
from repro.core.config import FnsConfig, WalkConfig

from conftest import engine_over

OUTPUTS = ("res_v", "res_i", "term", "hops", "p1_hops", "visited_bm",
           "iters", "slots")


def _test_bits(bm, idx):
    """The packed bitmap's bit of each index (False for a pad index)."""
    return bit_of(gather_words(bm, idx), idx)


def _expand_probing_bitmap(q_vecs, corpus, ids, bitmap):
    """The expansion op that gathered each id's pass bit from the packed
    (Q, n_words) bitmap itself."""
    safe = jnp.maximum(ids, 0)
    rows = corpus[safe].astype(jnp.float32)
    sims = jnp.einsum("qrd,qd->qr", rows, q_vecs.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    words = jnp.take_along_axis(bitmap, (safe >> 5).astype(jnp.int32), axis=1)
    bits = ((words >> (safe & 31).astype(jnp.uint32)) & 1).astype(bool)
    valid = ids >= 0
    neg = jnp.float32(-jnp.inf)
    return jnp.where(valid, sims, neg), jnp.where(valid & bits, sims, neg)


def three_bitmap_walk(vectors, adjacency, pass_bm, q_vecs, seeds,
               p: WalkConfig, init_results=None):
    """The lockstep walk round as it was before the state map: the
    visited, in-results and pass bitmaps probed by three word gathers a
    hop (``walk_batch``'s signature and outputs)."""
    n, d = vectors.shape
    Q = q_vecs.shape[0]
    R = adjacency.shape[1]
    k, B, F = p.k, p.beam_width, p.frontier_cap

    safe_seeds = jnp.maximum(seeds, 0)
    seed_valid = seeds >= 0
    seed_sims = jnp.einsum("qsd,qd->qs", vectors[safe_seeds], q_vecs,
                           precision=HIGHEST)
    seed_v = jnp.where(seed_valid, 1.0 - seed_sims, INF)

    visited = set_bits(jnp.zeros((Q, n_words(n)), jnp.uint32),
                       seeds, seed_valid)

    frontier_v, frontier_i = _merge_queue(
        jnp.full((Q, F), INF), jnp.full((Q, F), -1, jnp.int32),
        seed_v, seeds, F)
    beam_v = jnp.full((Q, B), INF)
    beam_i = jnp.full((Q, B), -1, jnp.int32)

    # cross-round dedup: a node carried in init_results must not re-enter
    # the result queue when a later restart re-reaches it (its value is a
    # pure function of (q, node), so dropping the re-merge is exactly the
    # sequential engine's dict dedup). Traversal is unaffected.
    if init_results is None:
        res0_v = jnp.full((Q, k), INF)
        res0_i = jnp.full((Q, k), -1, jnp.int32)
        in_res = jnp.zeros((Q, n_words(n)), jnp.uint32)
    else:
        res0_v, res0_i = init_results
        in_res = set_bits(jnp.zeros((Q, n_words(n)), jnp.uint32),
                          res0_i, res0_i >= 0)

    seed_pass = (_test_bits(pass_bm, seeds)
                 & ~_test_bits(in_res, seeds))
    res_v, res_i = _merge_queue(res0_v, res0_i,
                                jnp.where(seed_pass, seed_v, INF), seeds, k)

    lanes = dict(
        visited=visited, frontier_v=frontier_v, frontier_i=frontier_i,
        beam_v=beam_v, beam_i=beam_i, res_v=res_v, res_i=res_i,
        phase=jnp.ones((Q,), jnp.int32), stall=jnp.zeros((Q,), jnp.int32),
        # an unseeded lane would converge at its first check, untouched
        term=jnp.where(seed_valid.any(axis=1), TERM_RUNNING,
                       TERM_CONVERGED).astype(jnp.int32),
        hops=jnp.zeros((Q,), jnp.int32), p1_hops=jnp.zeros((Q,), jnp.int32),
    )
    # the per-lane constants the hop reads, gathered with the lanes
    consts = dict(q_vecs=q_vecs, pass_bm=pass_bm, in_res=in_res)

    def hop(s, c):
        q_vecs, pass_bm, in_res = c["q_vecs"], c["pass_bm"], c["in_res"]
        active = s["term"] == TERM_RUNNING
        phase = s["phase"]
        f_empty = s["frontier_v"][:, 0] >= INF / 2
        b_empty = s["beam_v"][:, 0] >= INF / 2
        # phase-1 queries with drained frontier fall to phase 2 now
        phase = jnp.where((phase == 1) & f_empty, 2, phase)
        use_frontier = (phase == 1)
        # pop one node per query
        fv, fi, nf_v, nf_i = _pop(s["frontier_v"], s["frontier_i"])
        bv, bi, nb_v, nb_i = _pop(s["beam_v"], s["beam_i"])
        x_v = jnp.where(use_frontier, fv, bv)
        x = jnp.where(use_frontier, fi, bi)
        frontier_v = jnp.where(use_frontier[:, None], nf_v, s["frontier_v"])
        frontier_i = jnp.where(use_frontier[:, None], nf_i, s["frontier_i"])
        beam_v = jnp.where(use_frontier[:, None], s["beam_v"], nb_v)
        beam_i = jnp.where(use_frontier[:, None], s["beam_i"], nb_i)
        # termination checks (phase-2 semantics, Alg. 4 lines 14-22)
        v_k = s["res_v"][:, k - 1]
        nothing = use_frontier & f_empty & b_empty | ~use_frontier & b_empty
        early = ~use_frontier & (x_v > v_k) & (v_k < INF / 2)
        stallout = ~use_frontier & (s["stall"] >= p.stall_budget)
        term = s["term"]
        term = jnp.where(active & nothing, TERM_CONVERGED, term)
        term = jnp.where(active & ~nothing & early, TERM_EARLY, term)
        term = jnp.where(active & ~nothing & ~early & stallout, TERM_STALL, term)
        live = term == TERM_RUNNING
        # ---- expand x (masked for dead queries) ----
        xs = jnp.maximum(x, 0)
        nbrs = adjacency[xs]                                    # (Q, R)
        sn = jnp.maximum(nbrs, 0)
        nvalid = (nbrs >= 0) & live[:, None]
        seen = _test_bits(s["visited"], sn)
        new = nvalid & ~seen
        visited = set_bits(s["visited"], sn, new)
        # one gather+dot yields both traversal distances and pass-masked
        # candidates (the kernel probes the pass bitmap in the same pass)
        sims, sims_p = _expand_probing_bitmap(q_vecs, vectors, nbrs, pass_bm)
        v_n = 1.0 - sims
        pass_r = jnp.isfinite(sims_p) & live[:, None]
        # results: merge new filtered, minus nodes a prior round already
        # banked (in_res is static within the round: nodes merged this
        # round are first-seen, so `new` already excludes them)
        in_res_r = _test_bits(in_res, sn)
        cand_v = jnp.where(new & pass_r & ~in_res_r, v_n, INF)
        res_v, res_i = _merge_queue(s["res_v"], s["res_i"], cand_v, nbrs, k)
        # local signals
        n_pass = pass_r.sum(1)
        vx = 1.0 - jnp.einsum("qd,qd->q", vectors[xs], q_vecs,
                              precision=HIGHEST)
        drift = jnp.where(
            n_pass > 0,
            (jnp.where(pass_r, v_n, 0.0).sum(1) / jnp.maximum(n_pass, 1)) - vx,
            jnp.inf)
        new_filtered = (new & pass_r).sum(1)
        stall = jnp.where(new_filtered > 0, 0, s["stall"] + 1)
        neg = drift < 0
        # ---- phase logic ----
        # phase 1, drift<0: push top-K_f filtered descending new neighbours
        push1 = jnp.where(
            (live & (phase == 1) & neg)[:, None] & new & pass_r
            & (v_n < vx[:, None]), v_n, INF)
        pv, sel = jax.lax.top_k(-push1, min(p.frontier_width, R))
        push1_v, push1_i = -pv, jnp.take_along_axis(nbrs, sel, axis=1)
        frontier_v, frontier_i = _merge_queue(frontier_v, frontier_i,
                                              push1_v, push1_i, F)
        # phase 1, drift>=0: fall to 2; beam <- frontier ∪ new neighbours
        to2 = live & (phase == 1) & ~neg
        cand2_v = jnp.concatenate(
            [jnp.where(to2[:, None], frontier_v, INF),
             jnp.where(to2[:, None] & new, v_n, INF)], axis=1)
        cand2_i = jnp.concatenate([frontier_i, nbrs], axis=1)
        merged_bv, merged_bi = _merge_queue(beam_v, beam_i, cand2_v, cand2_i, B)
        beam_v = jnp.where(to2[:, None], merged_bv, beam_v)
        beam_i = jnp.where(to2[:, None], merged_bi, beam_i)
        frontier_v = jnp.where(to2[:, None], INF, frontier_v)
        frontier_i = jnp.where(to2[:, None], -1, frontier_i)
        # phase 2: beam-merge unseen; maybe re-enter phase 1
        in2 = live & (phase == 2)
        b2_v = jnp.where(in2[:, None] & new, v_n, INF)
        beam_v, beam_i = _merge_queue(beam_v, beam_i, b2_v, nbrs, B)
        reenter = in2 & neg & (new_filtered > 0)
        re_v = jnp.where(reenter[:, None] & new & pass_r, v_n, INF)
        rv, rsel = jax.lax.top_k(-re_v, min(p.frontier_width, R))
        re_ids = jnp.take_along_axis(nbrs, rsel, axis=1)
        has_cand = (-rv[:, 0]) < INF / 2
        reenter = reenter & has_cand
        re_fv, re_fi = _merge_queue(jnp.full_like(frontier_v, INF),
                                    jnp.full_like(frontier_i, -1),
                                    -rv, re_ids, F)
        frontier_v = jnp.where(reenter[:, None], re_fv, frontier_v)
        frontier_i = jnp.where(reenter[:, None], re_fi, frontier_i)
        beam_v = jnp.where(reenter[:, None], INF, beam_v)
        beam_i = jnp.where(reenter[:, None], -1, beam_i)
        new_phase = jnp.where(to2, 2, phase)
        new_phase = jnp.where(reenter, 1, new_phase)
        hops = s["hops"] + live.astype(jnp.int32)
        p1_hops = s["p1_hops"] + (live & (phase == 1)).astype(jnp.int32)
        return dict(visited=visited, frontier_v=frontier_v,
                    frontier_i=frontier_i, beam_v=beam_v, beam_i=beam_i,
                    res_v=res_v, res_i=res_i, phase=new_phase, stall=stall,
                    term=term, hops=hops, p1_hops=p1_hops)

    def stage(s, c, t, slots, widths):
        """Walk at width widths[0] while more lanes run than the next
        width holds, then hand the running lanes to the next stage."""
        W = widths[0]
        nxt = widths[1] if len(widths) > 1 else 0

        def cond(carry):
            s, t, _ = carry
            running = (s["term"] == TERM_RUNNING).sum()
            return (t < p.max_hops) & (running > nxt)

        def body(carry):
            s, t, slots = carry
            with jax.named_scope("walk_hop"):
                return hop(s, c), t + 1, slots + W

        s, t, slots = jax.lax.while_loop(cond, body, (s, t, slots))
        if not nxt:
            return s, t, slots
        idx = _running_first(s["term"], nxt)
        take = functools.partial(jax.tree.map, lambda x: x[idx])
        sub, t, slots = stage(take(s), take(c), t, slots, widths[1:])
        return (jax.tree.map(lambda x, y: x.at[idx].set(y), s, sub),
                t, slots)

    zero = jnp.asarray(0, jnp.int32)
    out, t, slots = stage(lanes, consts, zero, zero, stage_widths(Q))
    term = jnp.where(out["term"] == TERM_RUNNING, TERM_MAXHOP, out["term"])
    return dict(res_v=out["res_v"], res_i=out["res_i"], term=term,
                hops=out["hops"], p1_hops=out["p1_hops"],
                visited_bm=out["visited"], iters=t, slots=slots)



Q_MAX = 64


@pytest.fixture(scope="module")
def lanes(sel_sweep):
    """64 lanes of the selectivity sweep's queries (its 36, then the first
    28 again), their packed pass bitmaps, and two rounds of anchor seeds as
    ``atlas_round`` selects them: the first round's, and a restart's from
    the clusters the first did not use."""
    _, index, queries = sel_sweep
    cfg = FnsConfig().with_knobs({"walk.k": 10, "walk.beam_width": 4})
    eng = engine_over(index, cfg)
    ix = eng.shards[0]
    q_vecs, fields, allowed, bounds = eng._pack_queries((queries * 2)[:Q_MAX])
    pass_bm = eng._passes(ix.metadata, fields, allowed, bounds)
    passes = unpack_bits(pass_bm, ix.vectors.shape[0])
    p = eng.p

    def select(gate):
        return ix.datlas.select_anchors_batch(
            q_vecs, (fields, allowed), gate, ix.vectors, passes,
            n_seeds=p.n_seeds, c_max=p.c_max,
            disjunct_quota=p.disjunct_quota)

    seeds, used = select(jnp.zeros((Q_MAX, ix.datlas.n_clusters), bool))
    restart, _ = select(used)
    return p, ix.vectors, ix.adjacency, q_vecs, pass_bm, seeds, restart


@functools.lru_cache(maxsize=None)
def _program(walk, p):
    return jax.jit(functools.partial(walk, p=p))


def _run(walk, p, vectors, adjacency, pass_bm, q_vecs, seeds, init):
    return jax.device_get(_program(walk, p)(
        vectors, adjacency, pass_bm, q_vecs, seeds, init_results=init))


def _padded_with_repeats(adjacency):
    """Every third row repeats ids within itself (each even column's id in
    the next column); the rows after them end in -1 padding from the
    middle on."""
    adj = np.asarray(adjacency).copy()
    R = adj.shape[1]
    adj[::3, 1::2] = adj[::3, 0::2][:, :R // 2]
    adj[1::3, R // 2:] = -1
    return jnp.asarray(adj)


CASES = ("first_round", "restart_round", "restart_overlapping_seeds",
         "all_pass", "padded_repeated_adjacency")


@pytest.mark.parametrize("Q", [8, 36, 64])
@pytest.mark.parametrize("case", CASES)
def test_walk_matches_three_bitmap_walk(lanes, case, Q):
    """Every output, bit for bit, for a round with and without carried
    results (a restart, and a restart whose carried results hold its own
    seeds), selective and all-pass bitmaps, and adjacency rows with -1
    padding and repeated ids; Q 8 runs one stage, 36 and 64 several."""
    p, vectors, adjacency, q_vecs, pass_bm, seeds, restart = lanes
    part = slice(0, Q)
    q_vecs, pass_bm = q_vecs[part], pass_bm[part]
    seeds, restart = seeds[part], restart[part]
    first = _run(three_bitmap_walk, p, vectors, adjacency, pass_bm, q_vecs,
                 seeds, None)
    carried = (jnp.asarray(first["res_v"]), jnp.asarray(first["res_i"]))
    n = vectors.shape[0]
    args = {
        "first_round": (adjacency, pass_bm, seeds, None),
        "restart_round": (adjacency, pass_bm, restart, carried),
        "restart_overlapping_seeds": (adjacency, pass_bm, seeds, carried),
        "all_pass": (adjacency, pack_bits(jnp.ones((Q, n), bool)), seeds,
                     None),
        "padded_repeated_adjacency": (_padded_with_repeats(adjacency),
                                      pass_bm, seeds, carried),
    }[case]
    adj, bm, s, init = args
    if case == "restart_overlapping_seeds":
        # the carried results hold passing seeds of this round
        banked = (np.asarray(s)[:, :, None]
                  == first["res_i"][:, None, :]).any(-1)
        assert banked.any()
    if case == "padded_repeated_adjacency":
        a = np.asarray(adj)
        assert (a == -1).any()
        assert any(np.unique(r[r >= 0]).size < (r >= 0).sum() for r in a)
    want = _run(three_bitmap_walk, p, vectors, adj, bm, q_vecs, s, init)
    got = _run(walk_batch, p, vectors, adj, bm, q_vecs, s, init)
    for key in OUTPUTS:
        assert got[key].dtype == want[key].dtype, key
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    assert int(got["iters"]) > 0
    assert got["visited_bm"].shape == (Q, n_words(n))
