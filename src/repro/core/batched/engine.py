"""Batched TPU-native drift-guided search (beyond-paper engine).

Runs Q queries in lockstep as one ``lax.while_loop``: all walk state is
fixed-shape (a packed uint32 state map of 2 bits a node, visited and
filter-pass, V-sorted fixed-capacity frontier/beam queues, running top-k
results), one iteration expands one node per active query, each
neighbour's visited and pass bits come from one word gather of the map,
and every expansion distance from one neighbour gather + full-f32 dot
(``ref.fiber_expand_walk``, the same XLA program on every platform).

A whole filtered search batch is ONE device dispatch (``search_batch``):
predicate evaluation (batched ``filter_eval``), the restart round loop
(an outer ``lax.while_loop`` over ``atlas_round`` — batched anchor
selection from the packed ``DeviceAtlas`` + the lockstep walk), and the
per-round walks/hops stats all run on device; the host syncs once per
batch to fetch results.

``Engine`` serves that program over a ``ShardedIndex`` of any shard
count: on one device it runs ``search_batch`` itself, on a mesh the same
program per shard under ``shard_map`` with an exact cross-shard merge
(DESIGN.md §7, §13). ``Engine.search_hostloop`` keeps the original
host-driven round loop (one jitted call per round, two scalar syncs) as
the parity baseline.

Vectorization deltas vs the sequential reference (recorded in DESIGN.md §3
and validated for recall parity in tests):
* queues hold only first-seen nodes (a node enters exactly one queue once);
* the phase-1 -> 2 fallback seeds the beam from (frontier ∪ this
  expansion's neighbours) rather than "all seen unexpanded nodes";
* the walk narrows in stages (Q, Q/2, ... down to 8 lanes): once the
  running lanes fit in half the width they are gathered into it, so a
  finished query leaves the hop instead of idling masked until the
  batch drains.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro import faults
from repro.core.batched.bitmap import (PASS, STATE_STRIDE, VISITED,
                                       bit_of, gather_words, pack_bits,
                                       popcount, set_bits, state_map,
                                       unpack_bits, visited_bits)
from repro.core.batched.insert import emit_device_atlas
from repro.core.batched.lifecycle import pad_state
from repro.core.batched.scopes import dispatch_program
from repro.core.batched.sharded import ShardedIndex, merge_topk, stack_slabs
from repro.core.config import (FnsConfig, KernelConfig, WalkConfig,
                               check_state_config)
from repro.core.device_atlas import (DeviceAtlas, pack_dnf, pack_predicates,
                                     stack_atlases, table_n_disj)
from repro.core.predicate import (DNF, And, AnyOf, FilterExpr, HasAny, Not,
                                  Or, as_dnf, disjunct_selectivity)
from repro.core.tags import first_columns, tag_columns
from repro.core.types import FilterPredicate, Query
from repro.kernels import ref
from repro.kernels.filter_eval import filter_eval_batch
from repro.kernels.ops import MAX_CLAUSES, on_tpu
from repro.launch.mesh import index_axis_size, query_axis_name
from repro.launch.shardings import index_shardings

INF = jnp.float32(3.4e38)
# the host twin of ``INF / 2``: a result slot at or above it is unfilled.
# A numpy scalar, so the unpack after the fetch runs no device op.
_HOST_FOUND = np.float32(3.4e38) / 2
HIGHEST = jax.lax.Precision.HIGHEST

TERM_RUNNING, TERM_CONVERGED, TERM_EARLY, TERM_STALL, TERM_MAXHOP = 0, 1, 2, 3, 4
# the narrowest stage of the walk: the f32 sublane count
LANE_FLOOR = 8

def _merge_queue(q_v, q_i, new_v, new_i, cap: int):
    """Merge sorted queue (Q,cap) with candidates (Q,m); keep cap smallest."""
    v = jnp.concatenate([q_v, new_v], axis=1)
    i = jnp.concatenate([q_i, new_i], axis=1)
    top_v, sel = jax.lax.top_k(-v, cap)
    return -top_v, jnp.take_along_axis(i, sel, axis=1)


def _pop(q_v, q_i):
    x_v, x_i = q_v[:, 0], q_i[:, 0]
    q_v = jnp.concatenate([q_v[:, 1:], jnp.full_like(q_v[:, :1], INF)], axis=1)
    q_i = jnp.concatenate([q_i[:, 1:], jnp.full_like(q_i[:, :1], -1)], axis=1)
    return x_v, x_i, q_v, q_i


def _eval_passes(metadata, fields, allowed, bounds=None,
                 kcfg: KernelConfig | None = None, tag_cols=()):
    """Batched predicate evaluation -> packed (Q, ceil(n/32)) uint32 pass
    bitmaps: the filter_eval Pallas corpus sweep on a TPU, the jnp oracle
    on the CPU (``ops.on_tpu`` refuses any other platform). Disjunctive
    (Q, D, C) tables carry their live-disjunct counts in the
    dead-disjunct sentinel; the kernels OR the per-disjunct
    conjunctive bitmaps in the same sweep (DESIGN.md §8). ``bounds``
    (Q, D, C, 2) marks interval clauses (evaluated as two comparisons,
    short-circuited rarest-first; None keeps legacy programs). ``kcfg``
    sizes the kernel's corpus tile (CPU oracle has no tiles). ``tag_cols``
    (static) names the schema's tag-set fields by their first column: a
    clause on one takes the tag test. Its ops sit under the device scope
    ``filter_eval``."""
    n_disj = table_n_disj(fields) if fields.ndim == 3 else None
    with jax.named_scope("filter_eval"):
        if on_tpu():
            tn = (kcfg or KernelConfig()).filter_tile
            return filter_eval_batch(metadata, fields, allowed, n_disj,
                                     bounds, tn=tn, interpret=False,
                                     tag_cols=tag_cols)
        return ref.filter_eval_batch(metadata, fields, allowed, n_disj,
                                     bounds, tag_cols)


def clause_passes(fields, allowed, bounds=None):
    """The vector passes ``filter_eval``'s sweep makes for a batch, by the
    kernel's cost model (a clause costs one pass per value word it uses):
    over every active clause of every live disjunct, the non-zero bitmap
    words of a value-set or tag clause, one for an interval clause. An
    int32 scalar."""
    words = (allowed != 0).sum(axis=-1, dtype=jnp.int32)
    if bounds is not None:
        words = jnp.where(bounds[..., 0] <= bounds[..., 1], 1, words)
    return jnp.where(fields >= 0, words, 0).sum(dtype=jnp.int32)


def stage_widths(Q: int) -> tuple[int, ...]:
    """Lane widths of the walk's stages for a batch of Q lanes: Q, then
    halves while a half holds ``LANE_FLOOR`` lanes."""
    widths = [Q]
    while widths[-1] // 2 >= LANE_FLOOR:
        widths.append(widths[-1] // 2)
    return tuple(widths)


def _running_first(term, width: int):
    """Indices of ``width`` lanes: the running ones, then the rest, each in
    lane order (a stable compaction)."""
    W = term.shape[0]
    key = jnp.where(term == TERM_RUNNING, W, 0) + (W - 1 - jnp.arange(W))
    return jax.lax.top_k(key, width)[1]


def _banked(ids, res0_i):
    """``ids (Q, m)`` -> ``(Q, m) bool``: which ids a prior round already
    banked among the lane's carried results ``res0_i (Q, k)`` (-1 pad;
    an exact compare, no gather)."""
    return ((ids[:, :, None] == res0_i[:, None, :])
            & (res0_i >= 0)[:, None, :]).any(-1)


def walk_batch(vectors, adjacency, pass_bm, q_vecs, seeds,
               p: WalkConfig, init_results=None):
    """One lockstep walk round.

    vectors (n, d) f32; adjacency (n, R) i32 (-1 pad); pass_bm
    (Q, ceil(n/32)) uint32 packed filter bitmaps; q_vecs (Q, d); seeds
    (Q, S) i32 (-1 pad). Returns dict of results + diagnostics, among
    them ``iters``, the lockstep iterations the loop ran, ``slots``,
    the lanes those iterations computed (Σ of each iteration's width), and
    ``visited_bm``, the packed (Q, ceil(n/32)) visited set.
    The per-point walk state is one packed state map (``state_map``,
    (Q, ceil(n/16)) uint32: a visited and a pass bit a node), built from
    ``pass_bm`` once a round, so a hop reads both bits of a neighbour with
    one word gather. A node the carried ``init_results`` already hold is
    found by an exact compare against their ids (``_banked``). Each
    iteration's ops sit under the device scope ``walk_hop``.

    The walk narrows in stages (``stage_widths``): a stage at width W
    runs while more than the next stage's width of its lanes are running;
    then the running lanes, in lane order, are gathered into the next
    stage (one whose lanes already fit runs no iteration and hands them
    on), and the narrow state is scattered back when it ends.
    Lanes are independent and a finished lane's returned state is frozen,
    so every output is what one full-width loop gives. A lane with no
    valid seed starts converged, with hops 0 and its results untouched.
    """
    n, d = vectors.shape
    Q = q_vecs.shape[0]
    R = adjacency.shape[1]
    k, B, F = p.k, p.beam_width, p.frontier_cap

    safe_seeds = jnp.maximum(seeds, 0)
    seed_valid = seeds >= 0
    seed_sims = jnp.einsum("qsd,qd->qs", vectors[safe_seeds], q_vecs,
                           precision=HIGHEST)
    seed_v = jnp.where(seed_valid, 1.0 - seed_sims, INF)

    sm0 = state_map(pass_bm, n)
    seed_words = gather_words(sm0, seeds, STATE_STRIDE)
    sm = set_bits(sm0, seeds, seed_valid, STATE_STRIDE, seed_words)

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
    else:
        res0_v, res0_i = init_results

    seed_pass = (bit_of(seed_words, seeds, STATE_STRIDE, PASS)
                 & ~_banked(seeds, res0_i))
    res_v, res_i = _merge_queue(res0_v, res0_i,
                                jnp.where(seed_pass, seed_v, INF), seeds, k)

    lanes = dict(
        sm=sm, frontier_v=frontier_v, frontier_i=frontier_i,
        beam_v=beam_v, beam_i=beam_i, res_v=res_v, res_i=res_i,
        phase=jnp.ones((Q,), jnp.int32), stall=jnp.zeros((Q,), jnp.int32),
        # an unseeded lane would converge at its first check, untouched
        term=jnp.where(seed_valid.any(axis=1), TERM_RUNNING,
                       TERM_CONVERGED).astype(jnp.int32),
        hops=jnp.zeros((Q,), jnp.int32), p1_hops=jnp.zeros((Q,), jnp.int32),
    )
    # the per-lane constants the hop reads, gathered with the lanes
    consts = dict(q_vecs=q_vecs, res0_i=res0_i)

    def hop(s, c):
        q_vecs, res0_i = c["q_vecs"], c["res0_i"]
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
        # the one probe: each neighbour's visited and pass bits
        words = gather_words(s["sm"], sn, STATE_STRIDE)
        seen = bit_of(words, sn, STATE_STRIDE, VISITED)
        new = nvalid & ~seen
        sm = set_bits(s["sm"], sn, new, STATE_STRIDE, words)
        # one gather+dot yields both traversal distances and pass-masked
        # candidates
        sims, sims_p = ref.fiber_expand_walk(
            q_vecs, vectors, nbrs, bit_of(words, nbrs, STATE_STRIDE, PASS))
        v_n = 1.0 - sims
        pass_r = jnp.isfinite(sims_p) & live[:, None]
        # results: merge new filtered, minus nodes a prior round already
        # banked (the carried ids are static within the round: nodes
        # merged this round are first-seen, so `new` already excludes them)
        cand_v = jnp.where(new & pass_r & ~_banked(nbrs, res0_i), v_n, INF)
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
        return dict(sm=sm, frontier_v=frontier_v,
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
                visited_bm=visited_bits(out["sm"], n), iters=t, slots=slots)


def atlas_round(datlas: DeviceAtlas, vectors, adjacency, pass_bm, passes,
                q_vecs, fields, allowed, processed, need, res_v, res_i,
                p: WalkConfig, bounds=None,
                kcfg: KernelConfig | None = None):
    """One full restart round for all Q queries on device: batched anchor
    selection from the packed atlas, then the lockstep walk. ``pass_bm``
    is the packed (Q, ceil(n/32)) uint32 filter bitmap the walk carries;
    ``passes`` is its dense (Q, n) bool unpack for the selection math —
    round-invariant, so callers unpack once per batch instead of once per
    round. Queries with ``need`` false see an all-processed atlas and so
    get no seeds; a query with no seeds starts the walk converged, with
    its results untouched. ``bounds`` rides with the clause
    tables for interval clauses (None = pure value-set batch). The
    selection's ops sit under the device scope ``anchor_select``."""
    gate = processed | ~need[:, None]
    tables = ((fields, allowed) if bounds is None
              else (fields, allowed, bounds))
    with jax.named_scope("anchor_select"):
        seeds, used = datlas.select_anchors_batch(
            q_vecs, tables, gate, vectors, passes,
            n_seeds=p.n_seeds, c_max=p.c_max,
            disjunct_quota=p.disjunct_quota)
    out = walk_batch(vectors, adjacency, pass_bm, q_vecs, seeds, p,
                     init_results=(res_v, res_i))
    found = (out["res_v"] < INF / 2).sum(axis=1)
    return dict(res_v=out["res_v"], res_i=out["res_i"],
                processed=processed | used, need=need & (found < p.k),
                seeded=seeds[:, 0] >= 0, hops=out["hops"],
                iters=out["iters"], slots=out["slots"])


def search_batch(datlas: DeviceAtlas, vectors, adjacency, metadata, q_vecs,
                 fields, allowed, p: WalkConfig,
                 valid_bm=None, bounds=None,
                 kcfg: KernelConfig | None = None, tag_cols=()):
    """A whole filtered search batch as ONE device program: batched
    predicate evaluation, then a ``lax.while_loop`` over restart rounds
    (each round = ``atlas_round``). "Anyone seeded?" / "anyone still short
    of k?" are device predicates in the loop condition; per-round walks and
    hops accumulate in fixed-shape carries. Mirrors the PR 1 host round
    loop exactly: a round where nobody seeded is discarded wholesale (it
    cannot change results) and ends the loop.

    ``valid_bm`` (optional, (ceil(n/32),) uint32) marks real corpus rows:
    rows with a 0 bit fail every predicate. Sharded indexes pad each shard
    to a common row count and use this to keep pad rows (zero vector,
    metadata -1) out of every pass set — including the unconstrained
    predicate, which an empty clause table would otherwise let through.

    ``tag_cols`` (static) is the schema's tag-set fields' first columns.
    Besides the results and the walk counters it returns
    ``clause_passes``, the vector passes of the predicate sweep.
    """
    Q = q_vecs.shape[0]
    pass_bm = _eval_passes(metadata, fields, allowed, bounds, kcfg, tag_cols)
    if valid_bm is not None:
        pass_bm = pass_bm & valid_bm[None, :]
    # the dense unpack feeds only selection math and is round-invariant:
    # hoist it out of the while_loop so each round reuses one buffer
    passes = unpack_bits(pass_bm, vectors.shape[0])
    rounds = p.jump_budget + 1
    init = dict(
        processed=jnp.zeros((Q, datlas.n_clusters), bool),
        # a query with zero passing points can never seed or gain results:
        # starting it need-False keeps inert lanes (e.g. serve-bucket pads)
        # from holding the loop open one extra no-op round
        need=popcount(pass_bm) > 0,
        res_v=jnp.full((Q, p.k), INF),
        res_i=jnp.full((Q, p.k), -1, jnp.int32),
        hops=jnp.zeros(Q, jnp.int32), walks=jnp.zeros(Q, jnp.int32),
        iters=jnp.asarray(0, jnp.int32), slots=jnp.asarray(0, jnp.int32),
        r=jnp.asarray(0, jnp.int32), go=jnp.asarray(True))

    def cond(c):
        return c["go"] & (c["r"] < rounds)

    def body(c):
        out = atlas_round(datlas, vectors, adjacency, pass_bm, passes,
                          q_vecs, fields, allowed, c["processed"], c["need"],
                          c["res_v"], c["res_i"], p=p, bounds=bounds,
                          kcfg=kcfg)
        seeded = out["seeded"]
        any_seeded = seeded.any()
        res_v = jnp.where(any_seeded, out["res_v"], c["res_v"])
        res_i = jnp.where(any_seeded, out["res_i"], c["res_i"])
        processed = jnp.where(any_seeded, out["processed"], c["processed"])
        need = jnp.where(any_seeded, out["need"], c["need"])
        hops = c["hops"] + jnp.where(any_seeded, out["hops"], 0)
        walks = c["walks"] + jnp.where(any_seeded,
                                       seeded.astype(jnp.int32), 0)
        return dict(processed=processed, need=need, res_v=res_v, res_i=res_i,
                    hops=hops, walks=walks, iters=c["iters"] + out["iters"],
                    slots=c["slots"] + out["slots"], r=c["r"] + 1,
                    go=any_seeded & need.any())

    out = jax.lax.while_loop(cond, body, init)
    return dict(res_v=out["res_v"], res_i=out["res_i"], hops=out["hops"],
                walks=out["walks"], rounds=out["r"], iters=out["iters"],
                slots=out["slots"],
                clause_passes=clause_passes(fields, allowed, bounds))


def clause_dim(n_clauses: int) -> int:
    """Compiled clause-table width for a batch whose widest predicate has
    ``n_clauses`` clauses: at least MAX_CLAUSES (so common small batches
    share one program), then the next power of two (so two different wide
    widths also share instead of silently recompiling per distinct width)."""
    if n_clauses <= MAX_CLAUSES:
        return MAX_CLAUSES
    return 1 << (n_clauses - 1).bit_length()


def disjunct_dim(n_disjuncts: int) -> int:
    """Compiled disjunct-table depth for a batch whose widest predicate has
    ``n_disjuncts`` disjuncts: 1 keeps the legacy conjunctive (Q, C) table
    (so purely-conjunctive traffic reuses its existing programs verbatim),
    any disjunction buckets to the next power of two ≥ 2."""
    if n_disjuncts <= 1:
        return 1
    return 1 << (n_disjuncts - 1).bit_length()


def _compile_query_dnf(pred, vocab_sizes, v_cap: int, tag_fields=()):
    """Per-query predicate normalization for the batch pack: conjunctive
    FilterPredicates whose every value fits the bitmap pass through
    verbatim (legacy tables stay byte-identical); everything else —
    expressions, precompiled DNFs, and FilterPredicates carrying codes
    beyond ``v_cap`` — compiles v_cap-aware so oversized values lower to
    interval clauses instead of unpackable bitmap bits. The predicate must
    read the schema's ``tag_fields`` as they are (``_check_tag_use``)."""
    _check_tag_use(pred, tag_fields)
    if isinstance(pred, FilterPredicate):
        if all(v < v_cap for _, vals in pred.clauses for v in vals):
            return pred
        pred = pred.expr()
    return as_dnf(pred, vocab_sizes, v_cap=v_cap)


def _check_tag_use(pred, tag_fields) -> None:
    """A ``HasAny`` leaf (or ``AnyOf`` clause) must name a tag-set field
    by its first column, and a value or interval constraint no column of
    a tag-set field: the kernel picks a clause's test by its field.
    ValueError otherwise."""
    tagged = tag_columns(tag_fields)

    def check(field: int, is_tag: bool) -> None:
        if is_tag and tagged.get(field, (None,))[0] != field:
            raise ValueError(f"HasAny on field {field}, which is not a "
                             f"tag-set field of the index")
        if not is_tag and field in tagged:
            raise ValueError(f"field {field} is a tag-set field: "
                             f"constrain it with HasAny, not by value")

    def walk(e) -> None:
        if isinstance(e, (And, Or)):
            for c in e.children:
                walk(c)
        elif isinstance(e, Not):
            walk(e.child)
        else:
            check(e.field, isinstance(e, HasAny))

    if isinstance(pred, FilterExpr):
        walk(pred)
        return
    for clauses in getattr(pred, "disjuncts", None) or (pred.clauses,):
        for f, spec in clauses:
            check(f, isinstance(spec, AnyOf))


def pack_query_batch(queries: list[Query], *, v_cap: int,
                     vocab_sizes=None, tag_fields=()):
    """The engine's host-side query pack: (Q, d) vector stack + clause
    tables with the clause dimension bucketed by ``clause_dim``.

    Predicates may be conjunctive ``FilterPredicate``s, ``FilterExpr``
    trees, or precompiled ``DNF``s; expressions compile against
    ``vocab_sizes`` (Not/Range lowering) with ``v_cap`` steering
    large-domain leaves to interval clauses. When every predicate lowers
    to ≤ 1 disjunct of pure value-sets the tables keep the legacy (Q, C)
    conjunctive shape — byte-identical to the pre-algebra pack, so
    existing compiled programs are reused — otherwise they widen to
    (Q, D, C) with D bucketed by ``disjunct_dim``. Returns
    (q_vecs, fields, allowed, bounds): ``bounds`` is the (Q, D, C, 2)
    interval table when any clause is an interval (its disjuncts packed
    rarest-first for the kernel's short-circuit), else None — the
    invariant is ``bounds is not None ⟹ fields.ndim == 3``. A batch with
    a tag clause (``HasAny`` on one of the ``tag_fields``) packs the
    (Q, D, C) form too."""
    q_vecs = jnp.asarray(np.stack([q.vector for q in queries]))
    dnfs = [_compile_query_dnf(q.predicate, vocab_sizes, v_cap, tag_fields)
            for q in queries]
    d_max = max((1 if isinstance(p, FilterPredicate) else p.n_disjuncts
                 for p in dnfs), default=0)
    has_iv = any(isinstance(p, DNF) and p.has_intervals for p in dnfs)
    has_tags = any(isinstance(p, DNF) and p.has_tags for p in dnfs)
    if d_max <= 1 and not has_iv and not has_tags:
        preds = [p if isinstance(p, FilterPredicate) else p.to_predicate()
                 for p in dnfs]
        n_cl = max((p.n_clauses for p in preds), default=0)
        f_np, a_np = pack_predicates(preds, max_clauses=clause_dim(n_cl),
                                     v_cap=v_cap)
        return q_vecs, jnp.asarray(f_np), jnp.asarray(a_np), None
    dnfs = [as_dnf(p) for p in dnfs]
    if has_iv:
        # rare disjuncts first: the interval kernel short-circuits the
        # tail once a tile saturates, so the broad disjuncts go last
        # (union semantics are order-independent; quota repair is
        # per-disjunct and follows the same order on every path)
        dnfs = [DNF(tuple(sorted(
            d.disjuncts,
            key=lambda c: disjunct_selectivity(c, vocab_sizes))))
            for d in dnfs]
    n_cl = max((p.max_clauses for p in dnfs), default=0)
    f_np, a_np, b_np, _ = pack_dnf(dnfs, max_disjuncts=disjunct_dim(d_max),
                                   max_clauses=clause_dim(n_cl), v_cap=v_cap)
    bounds = jnp.asarray(b_np) if has_iv else None
    return q_vecs, jnp.asarray(f_np), jnp.asarray(a_np), bounds


def _fence_pack(eng, queries: list[Query], batch: int):
    """Publish-generation fence (DESIGN.md §13) of ``Engine.dispatch``.

    Pack the batch, then check the engine's ``publish_generation`` — the
    counter every device publish (ingest refresh, tombstone, maintenance
    swap) bumps. If a publish landed between the pack and here, the packed
    tables may bake stale vocab domains and the arrays the caller is about
    to bind may be mid-swap: re-pack against the new state and try again.
    ``faults.fire("serve.pre-dispatch")`` sits in the window so tests can
    script the interleaving. Returns ``(packed, generation)`` with
    ``generation == eng.publish_generation`` at return time. The host
    span ``fns.pack`` covers it, with the batch's fence ``retries``."""
    retries = 0
    with TraceAnnotation("fns.pack", batch=batch) as span:
        while True:
            gen = eng.publish_generation
            packed = eng._pack_queries(queries)
            faults.fire("serve.pre-dispatch")
            if eng.publish_generation == gen:
                span.set_metadata(retries=retries)
                return packed, gen
            retries += 1
            eng.fence_retries += 1


def _found_ids(res_v: np.ndarray, res_i: np.ndarray, q_n: int,
               gids: np.ndarray | None = None) -> list[np.ndarray]:
    """Each of the first ``q_n`` queries' found ids, in program order, from
    one float32 host compare over the whole ``(Q, k)`` result (rows past
    ``q_n`` are lane pad). ``gids`` maps slab rows to global ids."""
    found = res_v[:q_n] < _HOST_FOUND
    ids = [row[m] for row, m in zip(res_i[:q_n], found)]
    return ids if gids is None else [gids[i] for i in ids]


def fetch_results(token: dict, finish=None):
    """Sync an in-flight batch: the batch's one host sync (host span
    ``fns.fetch``), then the result unpack (host span ``fns.unpack``,
    which carries the batch's ``rounds``, ``iters``, ``slots``,
    ``clause_passes`` and its queries' Σ ``hops``).

    ``token`` holds the program's output (``out``), the query count to
    keep (``q_n``), the batch number (``batch``), and optionally the
    global-id map (``gids``) and the publish generation (``generation``).
    The per-lane ``rounds``/``iters``/``slots`` of a sharded program
    reduce to their max, its per-lane ``clause_passes`` to their sum.
    ``finish(ids, stats)``, when given, runs inside the unpack span
    and its value is returned."""
    batch = token["batch"]
    with TraceAnnotation("fns.fetch", batch=batch):
        host = jax.device_get(token["out"])
    q_n = token["q_n"]
    rounds = int(np.max(host["rounds"]))
    iters = int(np.max(host["iters"]))
    slots = int(np.max(host["slots"]))
    passes = int(np.sum(host["clause_passes"]))
    hops = int(host["hops"][:q_n].sum())
    with TraceAnnotation("fns.unpack", batch=batch, rounds=rounds,
                         iters=iters, slots=slots, hops=hops,
                         clause_passes=passes):
        ids = _found_ids(host["res_v"], host["res_i"], q_n,
                         token.get("gids"))
        # [:q_n] drops the inert lane-pad rows a 2D dispatch may append
        stats = {"walks": host["walks"][:q_n].astype(np.int32),
                 "hops": host["hops"][:q_n].astype(np.int64),
                 "rounds": rounds, "iters": iters, "slots": slots,
                 "clause_passes": passes}
        if "generation" in token:
            stats["generation"] = token["generation"]
        return (ids, stats) if finish is None else finish(ids, stats)




class DeviceShard(NamedTuple):
    """The device arrays the search program reads: one shard's, or, with
    a leading shard dim, every shard's (partitioned over a mesh)."""

    datlas: DeviceAtlas
    vectors: jax.Array     # (m, d) f32
    adjacency: jax.Array   # (m, R) i32 shard-local ids, -1 padded
    metadata: jax.Array    # (m, F) i32
    valid_bm: jax.Array    # (ceil(m/32),) u32 packed row validity
    global_ids: jax.Array  # (m,) i32 local row -> global id, -1 = pad


class Engine:
    """One-dispatch filtered search over a ``ShardedIndex`` of S >= 1
    shards. Every knob arrives through one ``FnsConfig`` (``self.cfg``).

    The program follows from what the engine can observe:

    * a ``mesh`` spanning more than one device on its ``data`` axis (which
      must hold the S shards) or on a query axis runs the fused per-shard
      ``search_batch`` under ``shard_map``: index rows partitioned over
      ``data``, local result ids mapped to global ids, one
      ``lax.all_gather`` and an exact top-k merge (``merge_topk``) on
      device. On a 2D query×data mesh (DESIGN.md §13) the batch is also
      partitioned over the query axis: each of the ``q_lanes`` lane groups
      walks Q/q_lanes queries against every shard. Per-query state is
      row-independent, so results are bit-identical to replicated queries
      and to ``search_reference``;
    * no such mesh and one shard runs ``search_batch`` itself (XLA module
      ``jit_search_batch``) on the shard's arrays, and maps ids to global
      ids on the host;
    * no such mesh and S > 1 shards (a snapshot restored onto fewer
      devices, DESIGN.md §10) runs the per-shard programs one after the
      other on the default device with the same merge
      (``_run_reference``, also the oracle of ``search_reference``).

    Either way a batch is one dispatch and one host sync (S dispatches in
    the last case); ``dispatches`` counts them. ``dispatch``/``collect``
    split a batch into an async half (fenced pack + device call) and a
    sync half, so a serving pipeline can overlap batch N+1's staging with
    batch N's device time. The index arrays are placed once at
    construction and again at each publish, never per batch; the
    validity bitmap is the one liveness source the program reads.

    An index built with ``serve.capacity`` carries a host ``InsertState``
    (DESIGN.md §9, §12): ``insert_batch``/``delete_batch`` and the
    maintenance loop mutate it, and ``refresh_device`` re-places the
    shards they touched at the same shapes, so the compiled program is
    reused.
    """

    def __init__(self, sindex: ShardedIndex, config: FnsConfig | None = None,
                 mesh=None):
        self.cfg = config if config is not None else FnsConfig()
        self.p = self.cfg.walk
        s = sindex.n_shards
        q_axis = (query_axis_name(mesh, self.cfg.mesh.query_axes)
                  if mesh is not None and self.cfg.mesh.query_parallel
                  else None)
        if mesh is not None and index_axis_size(mesh) == 1 and q_axis is None:
            mesh = None  # one device's worth of shards and lanes
        if mesh is not None and index_axis_size(mesh) != s:
            raise ValueError(
                f"index has {s} shards but mesh axis 'data' spans "
                f"{index_axis_size(mesh)} devices")
        self.mesh, self.q_axis = mesh, q_axis
        self.q_lanes = int(mesh.shape[q_axis]) if q_axis is not None else 1
        self.n_shards = s
        self.v_cap = sindex.atlases[0].v_cap
        self.vocab_sizes = sindex.vocab_sizes
        self.tag_fields = sindex.tag_fields
        self._state = sindex.insert_state
        # host arrays, stacked over shards: what each publish places
        self._host = {k: getattr(sindex, k) for k in
                      ("vectors", "adjacency", "metadata", "global_ids",
                       "valid")}
        self._atlases = list(sindex.atlases)
        if mesh is not None:
            sh = index_shardings(mesh, query_axis=q_axis)
            self._put = functools.partial(jax.device_put, device=sh["rows"])
            # queries are staged on their sharding explicitly, so batch
            # N+1's host->device transfer overlaps batch N's device time
            self._q_put = functools.partial(jax.device_put,
                                            device=sh["queries"])
        self.stacked: DeviceShard | None = None  # on a mesh
        self.shards: tuple[DeviceShard, ...] = ()  # without one
        self._place(range(s))
        self.publish_generation = 0
        self._init_programs()

    @classmethod
    def from_state(cls, state, config: FnsConfig | None = None, mesh=None, *,
                   vocab_sizes=None) -> "Engine":
        """A live engine from a restored ``InsertState`` (DESIGN.md §10)
        on whatever mesh this process has, with ZERO graph/atlas rebuild
        (``ShardedIndex.from_state``). A mesh whose ``data`` axis spans
        more devices than the state has shards gets empty shards
        (``lifecycle.pad_state``, exact); one spanning fewer runs the
        shards one after the other on the default device, with the same
        results.

        A given ``config`` is checked against the state's shape-baked
        knobs (``ConfigMismatch`` on conflict: graph_k, v_cap, n_clusters
        and capacity are burned into the slabs); without one the engine
        takes the default tree with the state's baked knobs."""
        if config is None:
            config = FnsConfig().with_knobs(
                {"graph.graph_k": state.graph_k, "graph.alpha": state.alpha,
                 "atlas.v_cap": state.v_cap})
        else:
            check_state_config(
                config, graph_k=state.graph_k, v_cap=state.v_cap,
                n_clusters=state.shards[0].atlas.n_clusters,
                capacity=sum(sh.cap for sh in state.shards),
                where="Engine.from_state")
        target = index_axis_size(mesh) if mesh is not None else 1
        if target > len(state.shards):
            pad_state(state, target)
        elif target < len(state.shards):
            mesh = None
        return cls(ShardedIndex.from_state(state, vocab_sizes), config, mesh)

    def _init_programs(self) -> None:
        p, kcfg = self.p, self.cfg.kernel
        tag_cols = first_columns(self.tag_fields)
        search = functools.partial(search_batch, p=p, kcfg=kcfg,
                                   tag_cols=tag_cols)
        # named after search_batch, so the trace's module says which
        # program ran and its recorded scopes (scopes.py) are its own
        self._shard_search = jax.jit(functools.update_wrapper(
            search, search_batch))
        self._search = (self._mesh_program(search) if self.mesh is not None
                        else self._shard_search)
        # the host-loop baseline's programs; the CPU backend ignores
        # donation, so only the round state is donated on the TPU
        self._round = jax.jit(
            functools.partial(atlas_round, p=p, kcfg=kcfg),
            donate_argnums=(8, 9, 10, 11) if on_tpu() else ())
        self._passes = jax.jit(functools.partial(_eval_passes, kcfg=kcfg,
                                                 tag_cols=tag_cols))
        self.dispatches = 0
        self.fence_retries = 0

    def _mesh_program(self, search):
        k = self.p.k

        def sharded_search(ix: DeviceShard, q_vecs, fields, allowed,
                           bounds):
            ix = jax.tree.map(lambda leaf: leaf[0], ix)
            out = search(ix.datlas, ix.vectors, ix.adjacency, ix.metadata,
                         q_vecs, fields, allowed, valid_bm=ix.valid_bm,
                         bounds=bounds)
            gids = jnp.where(out["res_i"] >= 0,
                             ix.global_ids[jnp.maximum(out["res_i"], 0)], -1)
            res_v, res_i = merge_topk(
                jax.lax.all_gather(out["res_v"], "data"),
                jax.lax.all_gather(gids, "data"), k)
            # the slowest shard's rounds, iterations and slots, one per
            # query lane so that they follow the queries' layout; every
            # shard sweeps its lane's clause tables alike
            lane = {key: jax.lax.pmax(out[key], "data")[None]
                    for key in ("rounds", "iters", "slots", "clause_passes")}
            return dict(res_v=res_v, res_i=res_i,
                        hops=jax.lax.psum(out["hops"], "data"),
                        walks=jax.lax.psum(out["walks"], "data"), **lane)

        # index leaves are partitioned row-wise over ``data``; the query
        # tensors (and the bounds table, when the batch has interval
        # clauses) are replicated, or split on their batch dim over the
        # query axis, and the outputs follow the queries
        q = P(self.q_axis) if self.q_axis is not None else P()
        return jax.jit(jax.shard_map(sharded_search, mesh=self.mesh,
                                     in_specs=(P("data"), q, q, q, q),
                                     out_specs=q, check_vma=False))

    # -- placement and the document lifecycle (DESIGN.md §9, §12) ---------

    def _place(self, touched) -> None:
        """Put the touched shards' host arrays on the device(s): on a mesh
        the stacks are placed whole on the ``data`` sharding; without one
        each touched shard is placed on its own, so the program reads it
        with no slicing."""
        h = self._host
        if self.mesh is not None:
            put = self._put
            self.stacked = DeviceShard(
                put(stack_atlases(self._atlases)), put(h["vectors"]),
                put(h["adjacency"]), put(h["metadata"]),
                put(pack_bits(jnp.asarray(h["valid"]))),
                put(h["global_ids"]))
            return
        shards = list(self.shards) or [None] * self.n_shards
        for s in touched:
            shards[s] = DeviceShard(
                self._atlases[s], jnp.asarray(h["vectors"][s]),
                jnp.asarray(h["adjacency"][s]), jnp.asarray(h["metadata"][s]),
                pack_bits(jnp.asarray(h["valid"][s])),
                jnp.asarray(h["global_ids"][s]))
        self.shards = tuple(shards)

    def _live_state(self, what: str):
        if self._state is None:
            raise ValueError(
                f"index has no insert state; {what} need an index built "
                f"with spare capacity (config serve.capacity)")
        return self._state

    def insert_batch(self, vectors, metadata, *,
                     gids: np.ndarray | None = None) -> np.ndarray:
        """Append (vector, metadata) rows to the live index (DESIGN.md §9):
        balance-aware shard placement, slab writes + validity-bit flips,
        reverse-edge graph repair and incremental atlas updates run on the
        host mirror (with ``maintenance.defer_repair`` the repair half is
        queued for the maintenance loop); then the touched shards are
        re-placed at the same shapes, so the compiled program is reused.
        When the slabs outgrow their capacity, ``ensure_capacity``
        compacts or grows them first and the program retraces once.
        ``gids`` re-introduces deleted documents under their old ids
        (still-live ids are rejected). Returns the new rows' global ids.
        Ingest costs transfers only: ``dispatches`` is untouched."""
        from repro.core.batched.insert import insert_rows
        from repro.core.batched.lifecycle import ensure_capacity

        st, mcfg = self._live_state("inserts"), self.cfg.maintenance
        room = ensure_capacity(st, np.asarray(vectors).shape[0], mcfg)
        if room["grown"]:
            # keep the shape-baked knob truthful for snapshot/restore
            self.cfg = self.cfg.with_knobs(
                {"serve.capacity": room["new_cap"] * len(st.shards)})
        gids, touched = insert_rows(st, vectors, metadata, gids=gids,
                                    defer_repair=mcfg.defer_repair)
        moved = room["compacted"] or room["grown"]
        self.refresh_device(None if moved else touched)
        return gids

    def delete_batch(self, gids) -> int:
        """Tombstone documents by global id (DESIGN.md §12): clear their
        bits on the host mirror and re-place the packed validity bitmap —
        the one liveness source the fused search reads — so a delete costs
        one bit-pack + transfer. No recompile, no graph/atlas work
        (tombstones keep routing walks until compaction recycles them).
        Returns the number of rows tombstoned."""
        from repro.core.batched.lifecycle import delete_rows

        st = self._live_state("deletes")
        n, touched = delete_rows(st, gids)
        valid = self._host["valid"]
        for s in touched:
            valid[s] = st.shards[s].valid
        if self.mesh is not None:
            self.stacked = self.stacked._replace(
                valid_bm=self._put(pack_bits(jnp.asarray(valid))))
        else:
            self.shards = tuple(
                sh._replace(valid_bm=pack_bits(jnp.asarray(valid[s])))
                if s in touched else sh for s, sh in enumerate(self.shards))
        self.publish_generation += 1
        return n

    def refresh_device(self, touched: list[int] | None = None) -> None:
        """Re-place the touched shards (None = all) from the host mirror
        after host-side work (ingest, compaction, growth, deferred repair):
        copy their slabs into the host stacks, re-emit their atlases and
        place them. The engine hook ``MaintenanceLoop`` publishes through.
        Slab growth changes the shapes, so every shard is re-stacked and
        the program retraces once."""
        st = self._state
        if st is None:
            return
        if self._host["vectors"].shape[1] != st.shards[0].cap:
            self._host = stack_slabs(st.shards)
            touched = None
        if touched is None:
            touched = range(self.n_shards)
        for s in touched:
            sl = st.shards[s]
            for k, host in self._host.items():
                host[s] = getattr(sl, k)
            self._atlases[s] = emit_device_atlas(sl, self.v_cap,
                                                 st.tag_fields)
        self._place(touched)
        self.vocab_sizes = st.expand_vocab(self.vocab_sizes)
        self.publish_generation += 1

    @property
    def state(self):
        """The host ``InsertState`` mirror (None on a build-once index) —
        what the lifecycle/maintenance subsystem mutates."""
        return self._state

    @property
    def insert_stats(self) -> dict | None:
        """Ingest/staleness accounting, or None on a build-once index."""
        return self._state.stats() if self._state is not None else None

    @property
    def vectors(self) -> jax.Array:
        """The device vectors: stacked (S, m, d) over the mesh, else the
        first shard's (m, d)."""
        if self.stacked is not None:
            return self.stacked.vectors
        return self.shards[0].vectors

    # -- search ---------------------------------------------------------

    def _pack_queries(self, queries: list[Query]):
        return pack_query_batch(queries, v_cap=self.v_cap,
                                vocab_sizes=self.vocab_sizes,
                                tag_fields=self.tag_fields)

    def _pad_to_lanes(self, queries: list[Query]) -> list[Query]:
        """Pad the batch to a multiple of the query-axis size (shard_map
        needs the partitioned dim divisible by the axis). Pads are inert —
        ``FilterExpr.never()`` admits no point, so they never seed — and
        carry a unit basis vector: a zero vector would go NaN under cosine
        normalization and could poison the lane's top-k merge."""
        rem = len(queries) % self.q_lanes
        if rem == 0:
            return queries
        basis = np.zeros(np.asarray(queries[0].vector).shape, np.float32)
        basis[0] = 1.0
        dummy = Query(vector=basis, predicate=FilterExpr.never())
        return list(queries) + [dummy] * (self.q_lanes - rem)

    def _gid_map(self) -> np.ndarray | None:
        """A snapshot of one shard's row -> global-id map, None without an
        insert state (then rows are global ids). Identity until the first
        compaction moves rows."""
        if self._state is None:
            return None
        return self._state.shards[0].global_ids.copy()

    def dispatch(self, queries: list[Query], seed: int = 0, *,
                 batch: int = -1) -> dict:
        """Fenced pack + the batch's device call; returns an in-flight
        token without syncing the host. jax's async dispatch means the
        device crunches batch N while the host packs batch N+1 — the
        overlap the serve pipeline (serve/pipeline.py) is built on. The
        token snapshots the publish generation and, on one shard without
        a mesh, the global-id map, so a compaction that remaps rows
        between dispatch and collect can't mistranslate the in-flight
        batch's results. ``batch`` is the caller's batch number, carried
        by the host spans (``fns.pack``, ``fns.dispatch``, and at collect
        ``fns.fetch``, ``fns.unpack``); -1 outside a service."""
        del seed
        packed, gen = _fence_pack(self, self._pad_to_lanes(queries), batch)
        token = {"q_n": len(queries), "generation": gen, "batch": batch}
        if self.mesh is not None:
            token["out"] = dispatch_program(
                self._search, batch, self.stacked,
                *[self._q_put(a) for a in packed])
            self.dispatches += 1
        elif self.n_shards == 1:
            q_vecs, fields, allowed, bounds = packed
            ix = self.shards[0]
            token["out"] = dispatch_program(
                self._search, batch, ix.datlas, ix.vectors, ix.adjacency,
                ix.metadata, q_vecs, fields, allowed, valid_bm=ix.valid_bm,
                bounds=bounds)
            token["gids"] = self._gid_map()
            self.dispatches += 1
        else:
            with TraceAnnotation("fns.dispatch", batch=batch):
                token["out"] = self._run_reference(*packed)
            self.dispatches += self.n_shards
        return token

    def collect(self, token: dict, finish=None):
        """Sync an in-flight ``dispatch`` token: the batch's single host
        sync + result/stat post-processing (``fetch_results``).
        ``stats["generation"]`` is the publish generation the batch was
        dispatched against; ``stats["rounds"]``/``["iters"]``/``["slots"]``
        the restart rounds, lockstep iterations and lanes computed of the
        slowest shard."""
        return fetch_results(token, finish)

    def search(self, queries: list[Query], seed: int = 0, *,
               batch: int = -1, finish=None):
        """Filtered top-k for a batch: one device dispatch, one host sync.
        ``seed`` is kept for API compat; the device path is deterministic
        (seeds are nearest matching members, never random samples). Walks
        and hops sum over shards (every shard walks every query)."""
        del seed
        return self.collect(self.dispatch(queries, batch=batch), finish)

    def _run_reference(self, q_vecs, fields, allowed, bounds):
        """The per-shard ``search_batch`` programs one shard at a time on
        the default device, merged by ``merge_topk`` in shard order: the
        program of an engine without a mesh over S > 1 shards, and the
        ``search_reference`` oracle. No host sync. A mesh engine's shards
        are copied to the default device first: a program over
        mesh-sharded inputs would be partitioned, and a Pallas kernel
        cannot be partitioned automatically."""
        shards = self.shards
        if self.mesh is not None:
            local = functools.partial(jax.device_put,
                                      device=jax.devices()[0])
            shards = [jax.tree.map(lambda leaf: local(leaf[s]), self.stacked)
                      for s in range(self.n_shards)]
        per_v, per_i, hops, walks = [], [], 0, 0
        rounds = iters = slots = passes = 0
        for ix in shards:
            out = self._shard_search(ix.datlas, ix.vectors, ix.adjacency,
                                     ix.metadata, q_vecs, fields, allowed,
                                     valid_bm=ix.valid_bm, bounds=bounds)
            per_v.append(out["res_v"])
            per_i.append(jnp.where(
                out["res_i"] >= 0,
                ix.global_ids[jnp.maximum(out["res_i"], 0)], -1))
            hops = hops + out["hops"]
            walks = walks + out["walks"]
            rounds = jnp.maximum(rounds, out["rounds"])
            iters = jnp.maximum(iters, out["iters"])
            slots = jnp.maximum(slots, out["slots"])
            passes = jnp.maximum(passes, out["clause_passes"])
        res_v, res_i = merge_topk(jnp.stack(per_v), jnp.stack(per_i),
                                  self.p.k)
        return dict(res_v=res_v, res_i=res_i, hops=hops, walks=walks,
                    rounds=rounds, iters=iters, slots=slots,
                    clause_passes=passes)

    def search_reference(self, queries: list[Query]):
        """Single-device fused baseline: the per-shard ``search_batch``
        programs run shard-at-a-time on the default device, merged by the
        same ``merge_topk`` in the same shard order. The mesh path must
        match it bit-for-bit (tested at selectivities {0.5, 0.1, 0.02} on
        1D and 2D meshes)."""
        out = self._run_reference(*self._pack_queries(queries))
        return fetch_results({"out": out, "q_n": len(queries), "batch": -1})

    def search_hostloop(self, queries: list[Query], seed: int = 0):
        """The original semantics on one shard without a mesh: a host round
        loop, one jitted select+walk call and two scalar syncs per round.
        Kept as the exact-parity baseline for ``search`` (tests); it counts
        ``rounds``, ``iters`` and ``slots`` from its own loop."""
        del seed
        if self.mesh is not None or self.n_shards != 1:
            raise ValueError("search_hostloop runs one shard without a mesh")
        ix, p = self.shards[0], self.p
        Q = len(queries)
        q_vecs, fields, allowed, bounds = self._pack_queries(queries)
        pass_bm = self._passes(ix.metadata, fields, allowed, bounds)
        pass_bm = pass_bm & ix.valid_bm[None, :]
        self.dispatches += 1
        passes = unpack_bits(pass_bm, ix.vectors.shape[0])
        processed = jnp.zeros((Q, ix.datlas.n_clusters), bool)
        need = popcount(pass_bm) > 0  # mirror search_batch's need init
        res_v = jnp.full((Q, p.k), INF)
        res_i = jnp.full((Q, p.k), -1, jnp.int32)
        stats = {"walks": np.zeros(Q, np.int32), "hops": np.zeros(Q, np.int64),
                 "rounds": 0, "iters": 0, "slots": 0}
        for _ in range(p.jump_budget + 1):
            out = self._round(ix.datlas, ix.vectors, ix.adjacency,
                              pass_bm, passes, q_vecs, fields, allowed,
                              processed, need, res_v, res_i, bounds=bounds)
            self.dispatches += 1
            stats["rounds"] += 1
            stats["iters"] += int(out["iters"])
            stats["slots"] += int(out["slots"])
            seeded = np.asarray(out["seeded"])
            # the buffers donated into the call are dead now: rebind results
            # before any break (a no-seed round leaves them bitwise
            # unchanged, so this is still PR 1 semantics)
            res_v, res_i = out["res_v"], out["res_i"]
            if not seeded.any():
                break
            processed, need = out["processed"], out["need"]
            stats["hops"] += np.asarray(out["hops"])
            stats["walks"] += seeded
            if not bool(np.asarray(need).any()):
                break
        ids = _found_ids(np.asarray(res_v), np.asarray(res_i), Q,
                         self._gid_map())
        return ids, stats
