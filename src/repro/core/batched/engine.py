"""Batched TPU-native drift-guided search (beyond-paper engine).

Runs Q queries in lockstep as one ``lax.while_loop``: all walk state is
fixed-shape (packed uint32 visited/in-results/pass bitmaps, V-sorted
fixed-capacity frontier/beam queues, running top-k results), one iteration
expands one node per active query, and every expansion distance comes from
one neighbour gather + full-f32 dot that also probes the packed pass bitmap
(``ref.fiber_expand_walk``, the same XLA program on every platform).

A whole filtered search batch is ONE device dispatch (``search_batch``):
predicate evaluation (batched ``filter_eval``), the restart round loop
(an outer ``lax.while_loop`` over ``atlas_round`` — batched anchor
selection from the packed ``DeviceAtlas`` + the lockstep walk), and the
per-round walks/hops stats all run on device; the host syncs once per
batch to fetch results. ``BatchedEngine.search_hostloop`` keeps the PR 1
host-driven round loop (one jitted call per round, two scalar syncs) as
the parity baseline.

Vectorization deltas vs the sequential reference (recorded in DESIGN.md §3
and validated for recall parity in tests):
* queues hold only first-seen nodes (a node enters exactly one queue once);
* the phase-1 -> 2 fallback seeds the beam from (frontier ∪ this
  expansion's neighbours) rather than "all seen unexpanded nodes";
* converged queries idle (masked) until the batch drains.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro import faults
from repro.core.batched.bitmap import (n_words, pack_bits, popcount,
                                       set_bits, test_bits, unpack_bits)
from repro.core.config import (FnsConfig, KernelConfig, WalkConfig,
                               check_state_config, coerce_config)
from repro.core.device_atlas import (DeviceAtlas, pack_dnf, pack_predicates,
                                     table_n_disj)
from repro.core.predicate import DNF, as_dnf, disjunct_selectivity
from repro.core.search import FiberIndex, SearchParams
from repro.core.types import FilterPredicate, Query
from repro.kernels import ref
from repro.kernels.filter_eval import filter_eval_batch
from repro.kernels.ops import MAX_CLAUSES, on_tpu

INF = jnp.float32(3.4e38)
HIGHEST = jax.lax.Precision.HIGHEST

TERM_RUNNING, TERM_CONVERGED, TERM_EARLY, TERM_STALL, TERM_MAXHOP = 0, 1, 2, 3, 4

# the walk-budget section of the unified config tree (core/config.py) IS
# the engine's parameter object; the historical name stays importable and
# constructible so every existing call site keeps working
BatchedParams = WalkConfig


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
                 kcfg: KernelConfig | None = None):
    """Batched predicate evaluation -> packed (Q, ceil(n/32)) uint32 pass
    bitmaps: the filter_eval Pallas corpus sweep on a TPU, the jnp oracle
    on the CPU (``ops.on_tpu`` refuses any other platform). Disjunctive
    (Q, D, C) tables carry their live-disjunct counts in the
    dead-disjunct sentinel; the kernels OR the per-disjunct
    conjunctive bitmaps in the same sweep (DESIGN.md §8). ``bounds``
    (Q, D, C, 2) marks interval clauses (evaluated as two comparisons,
    short-circuited rarest-first; None keeps legacy programs). ``kcfg``
    sizes the kernel's corpus tile (CPU oracle has no tiles)."""
    n_disj = table_n_disj(fields) if fields.ndim == 3 else None
    if on_tpu():
        tn = (kcfg or KernelConfig()).filter_tile
        return filter_eval_batch(metadata, fields, allowed, n_disj, bounds,
                                 tn=tn, interpret=False)
    return ref.filter_eval_batch(metadata, fields, allowed, n_disj, bounds)


def walk_batch(vectors, adjacency, pass_bm, q_vecs, seeds,
               p: BatchedParams, init_results=None):
    """One lockstep walk round.

    vectors (n, d) f32; adjacency (n, R) i32 (-1 pad); pass_bm
    (Q, ceil(n/32)) uint32 packed filter bitmaps; q_vecs (Q, d); seeds
    (Q, S) i32 (-1 pad). Returns dict of results + diagnostics. All
    per-point walk state (visited / in-results / pass) is bitmap-packed:
    O(Q*n/32) bytes instead of three dense (Q, n) bool masks.
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

    seed_pass = test_bits(pass_bm, seeds) & ~test_bits(in_res, seeds)
    res_v, res_i = _merge_queue(res0_v, res0_i,
                                jnp.where(seed_pass, seed_v, INF), seeds, k)

    state = dict(
        visited=visited, frontier_v=frontier_v, frontier_i=frontier_i,
        beam_v=beam_v, beam_i=beam_i, res_v=res_v, res_i=res_i,
        phase=jnp.ones((Q,), jnp.int32), stall=jnp.zeros((Q,), jnp.int32),
        term=jnp.zeros((Q,), jnp.int32), hops=jnp.zeros((Q,), jnp.int32),
        p1_hops=jnp.zeros((Q,), jnp.int32), t=jnp.asarray(0, jnp.int32),
    )

    def cond(s):
        return (s["t"] < p.max_hops) & jnp.any(s["term"] == TERM_RUNNING)

    def body(s):
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
        seen = test_bits(s["visited"], sn)
        new = nvalid & ~seen
        visited = set_bits(s["visited"], sn, new)
        # one gather+dot yields both traversal distances and pass-masked
        # candidates (the kernel probes the pass bitmap in the same pass)
        sims, sims_p = ref.fiber_expand_walk(q_vecs, vectors, nbrs, pass_bm)
        v_n = 1.0 - sims
        pass_r = jnp.isfinite(sims_p) & live[:, None]
        # results: merge new filtered, minus nodes a prior round already
        # banked (in_res is static within the round: nodes merged this
        # round are first-seen, so `new` already excludes them)
        in_res_r = test_bits(in_res, sn)
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
                    term=term, hops=hops, p1_hops=p1_hops, t=s["t"] + 1)

    out = jax.lax.while_loop(cond, body, state)
    term = jnp.where(out["term"] == TERM_RUNNING, TERM_MAXHOP, out["term"])
    return dict(res_v=out["res_v"], res_i=out["res_i"], term=term,
                hops=out["hops"], p1_hops=out["p1_hops"],
                visited_bm=out["visited"])


def atlas_round(datlas: DeviceAtlas, vectors, adjacency, pass_bm, passes,
                q_vecs, fields, allowed, processed, need, res_v, res_i,
                p: BatchedParams, bounds=None,
                kcfg: KernelConfig | None = None):
    """One full restart round for all Q queries on device: batched anchor
    selection from the packed atlas, then the lockstep walk. ``pass_bm``
    is the packed (Q, ceil(n/32)) uint32 filter bitmap the walk carries;
    ``passes`` is its dense (Q, n) bool unpack for the selection math —
    round-invariant, so callers unpack once per batch instead of once per
    round. Queries with ``need`` false see an all-processed atlas and so
    get no seeds; a query with no seeds converges on its first walk
    iteration with its results untouched. ``bounds`` rides with the clause
    tables for interval clauses (None = pure value-set batch)."""
    gate = processed | ~need[:, None]
    tables = ((fields, allowed) if bounds is None
              else (fields, allowed, bounds))
    seeds, used = datlas.select_anchors_batch(
        q_vecs, tables, gate, vectors, passes,
        n_seeds=p.n_seeds, c_max=p.c_max, disjunct_quota=p.disjunct_quota)
    out = walk_batch(vectors, adjacency, pass_bm, q_vecs, seeds, p,
                     init_results=(res_v, res_i))
    found = (out["res_v"] < INF / 2).sum(axis=1)
    return dict(res_v=out["res_v"], res_i=out["res_i"],
                processed=processed | used, need=need & (found < p.k),
                seeded=seeds[:, 0] >= 0, hops=out["hops"])


def search_batch(datlas: DeviceAtlas, vectors, adjacency, metadata, q_vecs,
                 fields, allowed, p: BatchedParams,
                 valid_bm=None, bounds=None,
                 kcfg: KernelConfig | None = None):
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
    """
    Q = q_vecs.shape[0]
    pass_bm = _eval_passes(metadata, fields, allowed, bounds, kcfg)
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
                    hops=hops, walks=walks, r=c["r"] + 1,
                    go=any_seeded & need.any())

    out = jax.lax.while_loop(cond, body, init)
    return dict(res_v=out["res_v"], res_i=out["res_i"], hops=out["hops"],
                walks=out["walks"])


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


def _compile_query_dnf(pred, vocab_sizes, v_cap: int):
    """Per-query predicate normalization for the batch pack: conjunctive
    FilterPredicates whose every value fits the bitmap pass through
    verbatim (legacy tables stay byte-identical); everything else —
    expressions, precompiled DNFs, and FilterPredicates carrying codes
    beyond ``v_cap`` — compiles v_cap-aware so oversized values lower to
    interval clauses instead of unpackable bitmap bits."""
    if isinstance(pred, FilterPredicate):
        if all(v < v_cap for _, vals in pred.clauses for v in vals):
            return pred
        pred = pred.expr()
    return as_dnf(pred, vocab_sizes, v_cap=v_cap)


def pack_query_batch(queries: list[Query], *, v_cap: int,
                     vocab_sizes=None):
    """Host-side query pack shared by the single-device and sharded
    engines: (Q, d) vector stack + clause tables with the clause dimension
    bucketed by ``clause_dim``.

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
    invariant is ``bounds is not None ⟹ fields.ndim == 3``."""
    q_vecs = jnp.asarray(np.stack([q.vector for q in queries]))
    dnfs = [_compile_query_dnf(q.predicate, vocab_sizes, v_cap)
            for q in queries]
    d_max = max((1 if isinstance(p, FilterPredicate) else p.n_disjuncts
                 for p in dnfs), default=0)
    has_iv = any(isinstance(p, DNF) and p.has_intervals for p in dnfs)
    if d_max <= 1 and not has_iv:
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


def _fence_pack(eng, queries: list[Query]):
    """Publish-generation fence (DESIGN.md §13), shared by both engines.

    Pack the batch, then check the engine's ``publish_generation`` — the
    counter every device publish (ingest refresh, tombstone, maintenance
    swap) bumps. If a publish landed between the pack and here, the packed
    tables may bake stale vocab domains and the arrays the caller is about
    to bind may be mid-swap: re-pack against the new state and try again.
    ``faults.fire("serve.pre-dispatch")`` sits in the window so tests can
    script the interleaving. Returns ``(packed, generation)`` with
    ``generation == eng.publish_generation`` at return time."""
    while True:
        gen = eng.publish_generation
        packed = eng._pack_queries(queries)
        faults.fire("serve.pre-dispatch")
        if eng.publish_generation == gen:
            return packed, gen
        eng.fence_retries += 1


class BatchedEngine:
    """Single-dispatch batched search over a device-resident index.

    ``search`` issues exactly one jitted call per batch (predicate eval +
    restart loop + walks fused in ``search_batch``) and one host sync to
    fetch results; ``dispatches`` counts compiled-callable invocations so
    tests can assert that. ``search_hostloop`` keeps the PR 1 host-driven
    round loop (one jitted ``atlas_round`` per round) as the parity and
    migration baseline. On the TPU the per-round state buffers
    (processed/need/res_v/res_i) are donated into the round call.

    ``serve.capacity`` (DESIGN.md §9) turns the device index into an
    append-able capacity slab: arrays are sized to ``capacity`` rows, a
    row-validity bitmap masks the unwritten tail out of every pass set,
    and ``insert_batch`` grows the corpus in place (graph repair +
    incremental atlas update on a host mirror, then a same-shape device
    refresh — the compiled search program is reused, and ``self.index``
    keeps the build-time snapshot). ``graph.graph_k``/``graph.alpha`` are
    the append path's forward-edge count and α-RNG slack.

    Every knob arrives through one ``FnsConfig`` (``config=``, stored as
    ``self.cfg``); the historical kwargs (``params=``/positional
    BatchedParams, ``capacity=``, ``graph_k=``, ``alpha=``) are
    deprecation shims that warn once and fold into it.
    """

    def __init__(self, index: FiberIndex, config=None,
                 v_cap: int | None = None,
                 vocab_sizes=None, capacity: int | None = None,
                 graph_k: int | None = None, alpha: float | None = None,
                 params: BatchedParams | None = None):
        from repro.core.batched.insert import (InsertState,
                                               emit_device_atlas,
                                               make_shard_state)

        if config is None:
            config = params
        # this entry point's historical append-path default (graph_k=16)
        # predates the config tree's 32; applied silently unless a full
        # FnsConfig states otherwise
        cfg = coerce_config(config,
                            {"serve.capacity": capacity,
                             "graph.graph_k": graph_k,
                             "graph.alpha": alpha},
                            where="BatchedEngine",
                            defaults={"graph.graph_k": 16})
        # non-knob plumbing args (bitmap width, domains) stay
        # first-class: fold without deprecation noise
        if v_cap is not None:
            cfg = cfg.with_knobs({"atlas.v_cap": v_cap})
        self.cfg = cfg
        self.index = index
        self.p = cfg.walk
        v_cap = cfg.atlas.v_cap
        capacity = cfg.serve.capacity
        n = index.vectors.shape[0]
        if capacity is None:
            self.datlas = index.atlas.to_device(v_cap=v_cap)
            self.vectors = jnp.asarray(index.vectors)
            self.adjacency = jnp.asarray(index.graph.neighbors)
            self.metadata = jnp.asarray(index.metadata)
            self._state = None
            self._valid_bm = None
        else:
            if capacity < n:
                raise ValueError(f"capacity {capacity} < corpus size {n}")
            # widen the row width for the append path's 1.5x graph_k
            # forward edges (mirrors build_sharded_index)
            graph_k = cfg.graph.graph_k
            adj = np.asarray(index.graph.neighbors, np.int32)
            w = max(adj.shape[1], graph_k + graph_k // 2)
            if w > adj.shape[1]:
                adj = np.concatenate(
                    [adj, np.full((n, w - adj.shape[1]), -1, np.int32)],
                    axis=1)
            slab = make_shard_state(
                np.asarray(index.vectors, np.float32),
                np.asarray(index.metadata, np.int32),
                np.arange(n, dtype=np.int32), adj,
                index.atlas, cap=capacity)
            if v_cap is None:
                # same auto-sizing rule as AnchorAtlas.to_device
                from repro.core.device_atlas import auto_v_cap
                vmax = int(index.metadata.max()) if index.metadata.size \
                    else -1
                v_cap = auto_v_cap(vmax)
            self._state = InsertState(shards=[slab], v_cap=v_cap,
                                      graph_k=graph_k, alpha=cfg.graph.alpha,
                                      seed=0, next_gid=n)
            self._refresh_from_slab(v_cap)
        # per-field domains for Not/Range lowering in FilterExpr queries;
        # derived from observed codes when the dataset's declaration isn't
        # handed in (identical masks for any domain covering the corpus)
        self.vocab_sizes = (tuple(int(v) for v in vocab_sizes)
                            if vocab_sizes is not None
                            else index.vocab_sizes())
        self._init_programs()

    @classmethod
    def from_state(cls, state, config=None, vocab_sizes=None,
                   params: BatchedParams | None = None) -> "BatchedEngine":
        """Reconstruct a live capacity-slab engine from a restored
        ``InsertState`` (DESIGN.md §10) with ZERO graph/atlas rebuild: the
        slab already carries the patched adjacency and the incremental
        atlas, so everything derived (device atlas CSR, validity bitmap,
        the sequential-path FiberIndex view) is re-*emitted*, never
        re-built. Further ``insert_batch`` calls continue seamlessly.

        An explicit full ``FnsConfig`` is validated against the state's
        shape-baked knobs (``ConfigMismatch`` on disagreement — e.g. a
        snapshot built at graph_k=16 cannot restore under graph_k=32)."""
        from repro.core.batched.insert import emit_anchor_atlas, emit_graph

        if len(state.shards) != 1:
            raise ValueError(
                f"BatchedEngine.from_state needs a 1-shard state, got "
                f"{len(state.shards)} shards (use ShardedEngine)")
        if config is None:
            config = params
        cfg = coerce_config(config, {}, where="BatchedEngine.from_state")
        if isinstance(config, FnsConfig):
            check_state_config(
                cfg, graph_k=state.graph_k, v_cap=state.v_cap,
                n_clusters=state.shards[0].atlas.n_clusters,
                capacity=sum(sh.cap for sh in state.shards),
                where="BatchedEngine.from_state")
        else:
            # fold the restored state's baked values so self.cfg reports
            # the truth even for legacy callers
            cfg = cfg.with_knobs({"graph.graph_k": state.graph_k,
                                  "graph.alpha": state.alpha,
                                  "atlas.v_cap": state.v_cap})
        slab = state.shards[0]
        eng = cls.__new__(cls)
        eng.cfg = cfg
        eng.index = FiberIndex(
            slab.vectors[: slab.n_valid].copy(),
            slab.metadata[: slab.n_valid].copy(),
            emit_graph(slab), emit_anchor_atlas(slab))
        eng.p = cfg.walk
        eng._state = state
        eng._refresh_from_slab(state.v_cap)
        eng.vocab_sizes = (tuple(int(v) for v in vocab_sizes)
                           if vocab_sizes is not None
                           else eng.index.vocab_sizes())
        eng.index.extend_vocab(eng.vocab_sizes)
        eng._init_programs()
        return eng

    def _refresh_from_slab(self, v_cap: int) -> None:
        """(Re)place the device arrays from the host slab mirror at fixed
        shapes — shared by construction, ingest, and snapshot restore."""
        from repro.core.batched.insert import emit_device_atlas

        slab = self._state.shards[0]
        self.datlas = emit_device_atlas(slab, v_cap)
        self.vectors = jnp.asarray(slab.vectors)
        self.adjacency = jnp.asarray(slab.adjacency)
        self.metadata = jnp.asarray(slab.metadata)
        self._valid_bm = pack_bits(jnp.asarray(slab.valid))
        # getattr: the first refresh runs from __init__/from_state before
        # the counters exist
        self.publish_generation = getattr(self, "publish_generation", 0) + 1

    def _init_programs(self) -> None:
        params = self.p
        kcfg = self.cfg.kernel
        # the CPU backend ignores donation; search_batch's inputs have no
        # same-shaped output to alias, so only the round state is donated
        self._round = jax.jit(
            functools.partial(atlas_round, p=params, kcfg=kcfg),
            donate_argnums=(8, 9, 10, 11) if on_tpu() else ())
        self._search = jax.jit(
            functools.partial(search_batch, p=params, kcfg=kcfg))
        self._passes = jax.jit(functools.partial(_eval_passes, kcfg=kcfg))
        self.dispatches = 0
        self.publish_generation = getattr(self, "publish_generation", 0)
        self.fence_retries = 0

    def insert_batch(self, vectors, metadata, *,
                     gids: np.ndarray | None = None) -> np.ndarray:
        """Append (vector, metadata) rows to the live index: slab writes +
        validity-bit flips, reverse-edge graph repair, and the incremental
        atlas update run on the host mirror, then the device arrays are
        refreshed (no extra search dispatches; shapes only change when the
        slab outgrew its capacity, in which case ``ensure_capacity``
        compacts/grows first and the jitted program retraces once). With
        ``maintenance.defer_repair`` the repair half is queued for the
        maintenance loop instead. ``gids`` re-introduces deleted documents
        under their old ids (still-live ids are rejected). Returns the new
        rows' ids."""
        from repro.core.batched.insert import insert_rows
        from repro.core.batched.lifecycle import ensure_capacity

        if self._state is None:
            raise ValueError(
                "engine was built without spare capacity; construct "
                "BatchedEngine(..., capacity=...) to enable insert_batch")
        mcfg = self.cfg.maintenance
        room = ensure_capacity(self._state, np.asarray(vectors).shape[0],
                               mcfg)
        if room["grown"]:
            # keep the shape-baked knob truthful for snapshot/restore
            self.cfg = self.cfg.with_knobs(
                {"serve.capacity": room["new_cap"]})
        gids, _ = insert_rows(self._state, vectors, metadata, gids=gids,
                              defer_repair=mcfg.defer_repair)
        self._refresh_from_slab(self.datlas.v_cap)
        self.vocab_sizes = self._state.expand_vocab(self.vocab_sizes)
        # keep the sequential path's memoized domains in sync: Not /
        # open-ended-Range lowering reads index.vocab_sizes(), which would
        # otherwise silently miss codes first introduced by this ingest
        self.index.extend_vocab(self.vocab_sizes)
        return gids

    def delete_batch(self, gids) -> int:
        """Tombstone documents by global id (DESIGN.md §12): clear their
        validity bits on the host mirror and re-place the packed bitmap —
        the ONLY liveness source the fused search reads — so the cost is
        one bit-pack + transfer. No recompile, no graph or atlas work (the
        dead rows keep routing walks until compaction recycles them).
        Returns the number of rows tombstoned."""
        from repro.core.batched.lifecycle import delete_rows

        if self._state is None:
            raise ValueError(
                "engine was built without spare capacity; deletes need a "
                "capacity-slab engine (BatchedEngine(..., capacity=...))")
        n, _ = delete_rows(self._state, gids)
        self._valid_bm = pack_bits(jnp.asarray(self._state.shards[0].valid))
        self.publish_generation += 1
        return n

    def refresh_device(self, touched=None) -> None:
        """Re-place the device arrays from the host slab after host-side
        maintenance (compaction, growth, deferred repair). The uniform
        engine hook ``MaintenanceLoop`` publishes through."""
        del touched  # one shard: a refresh is always full
        if self._state is not None:
            self._refresh_from_slab(self.datlas.v_cap)

    @property
    def state(self):
        """The host ``InsertState`` mirror (None on a fixed-size engine) —
        what the lifecycle/maintenance subsystem mutates."""
        return self._state

    @property
    def insert_stats(self) -> dict | None:
        """Ingest/staleness accounting, or None on a fixed-size engine."""
        return self._state.stats() if self._state is not None else None

    def _pack_queries(self, queries: list[Query]):
        return pack_query_batch(queries, v_cap=self.datlas.v_cap,
                                vocab_sizes=self.vocab_sizes)

    def _to_gids(self, ids: list[np.ndarray]) -> list[np.ndarray]:
        """Map slab row indices to global ids. Identity until the first
        compaction moves rows (build + append assign gid == row), so this
        only matters on an index with a document lifecycle."""
        if self._state is None:
            return ids
        g = self._state.shards[0].global_ids
        return [g[i] for i in ids]

    def dispatch(self, queries: list[Query], seed: int = 0) -> dict:
        """Fenced pack + ONE jitted call; returns an in-flight token
        without syncing the host. jax's async dispatch means the device
        crunches batch N while the host packs batch N+1 — the overlap the
        serve pipeline (serve/pipeline.py) is built on. The token snapshots
        the global-id map and the publish generation, so a compaction that
        remaps rows between dispatch and collect can't mistranslate the
        in-flight batch's results."""
        del seed
        (q_vecs, fields, allowed, bounds), gen = _fence_pack(self, queries)
        out = self._search(self.datlas, self.vectors, self.adjacency,
                           self.metadata, q_vecs, fields, allowed,
                           valid_bm=self._valid_bm, bounds=bounds)
        self.dispatches += 1
        gids = (self._state.shards[0].global_ids.copy()
                if self._state is not None else None)
        return {"out": out, "q_n": len(queries), "generation": gen,
                "gids": gids}

    def collect(self, token: dict):
        """Sync an in-flight ``dispatch`` token: the batch's single host
        sync + result/stat post-processing. ``stats["generation"]`` is the
        scalar publish generation the batch was dispatched against."""
        host = jax.device_get(token["out"])
        q_n = token["q_n"]
        res_v, res_i = host["res_v"], host["res_i"]
        raw = [res_i[i][res_v[i] < INF / 2] for i in range(q_n)]
        g = token["gids"]
        ids = raw if g is None else [g[i] for i in raw]
        stats = {"walks": host["walks"][:q_n].astype(np.int32),
                 "hops": host["hops"][:q_n].astype(np.int64),
                 "generation": token["generation"]}
        return ids, stats

    def search(self, queries: list[Query], seed: int = 0):
        """Filtered top-k for a batch: one device dispatch, one host sync.
        ``seed`` is kept for API compat; the device path is deterministic
        (seeds are nearest matching members, never random samples)."""
        del seed
        return self.collect(self.dispatch(queries))

    def search_hostloop(self, queries: list[Query], seed: int = 0):
        """PR 1 semantics: host round loop, one jitted select+walk call and
        two scalar syncs per round. Kept as the exact-parity baseline for
        ``search`` (tests) and for incremental debugging."""
        del seed
        p = self.p
        Q = len(queries)
        q_vecs, fields, allowed, bounds = self._pack_queries(queries)
        pass_bm = self._passes(self.metadata, fields, allowed, bounds)
        if self._valid_bm is not None:  # capacity slab: mask unwritten rows
            pass_bm = pass_bm & self._valid_bm[None, :]
        self.dispatches += 1
        passes = unpack_bits(pass_bm, self.vectors.shape[0])
        processed = jnp.zeros((Q, self.datlas.n_clusters), bool)
        need = popcount(pass_bm) > 0  # mirror search_batch's need init
        res_v = jnp.full((Q, p.k), INF)
        res_i = jnp.full((Q, p.k), -1, jnp.int32)
        stats = {"walks": np.zeros(Q, np.int32), "hops": np.zeros(Q, np.int64)}
        for _ in range(p.jump_budget + 1):
            out = self._round(self.datlas, self.vectors, self.adjacency,
                              pass_bm, passes, q_vecs, fields, allowed,
                              processed, need, res_v, res_i, bounds=bounds)
            self.dispatches += 1
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
        res_v = np.asarray(res_v)
        res_i = np.asarray(res_i)
        ids = self._to_gids(
            [res_i[i][res_v[i] < INF / 2] for i in range(Q)])
        return ids, stats
