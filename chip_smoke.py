#!/usr/bin/env python3
"""End-to-end chip smoke of the fused filtered search at the paper's
product-catalogue shape: 105,100 unit vectors x d=2048 fp32, 24 categorical
Zipf fields (DESIGN.md §1), the paper's index settings (graph_k=64,
r_max=128, k=25), data made from ``--seed``.

    python chip_smoke.py              # one TPU chip, every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded engine on a
                                      # data=4 and a 2x2 query x data mesh
                                      # vs its one-device reference, only
    JAX_PLATFORMS=cpu python chip_smoke.py   # CPU rehearsal, n cut to 8,192

One chip, in one process:
  1. the ``filter_eval`` Pallas kernel against its jnp oracle on the real
     metadata at Q=256, all three clause-table forms, bit for bit;
  2. ``RetrievalService.build`` (host alpha-kNN graph + anchor atlas) and
     the device placement of the index;
  3. ``query_batch`` at Q=256: conjunctive predicates at selectivity ~0.5,
     ~0.1 and ~0.02, one disjunctive and one ``Range`` batch. Per batch:
     first-call seconds (compile + run), warm ms, recall@25 against the
     brute-force filtered top-k beside what a random visit of as many rows
     would find, a predicate check of every returned id, and a check that
     every returned score is the full-f32 cosine distance; per program
     form, whether the compiled search holds a Pallas ``tpu_custom_call``;
  4. the same batches through ``ServePipeline``: the same ids must return;
  5. ``ingest`` of 1,024 new rows, then batches that must find each one.

Earlier lines print ``key: value`` results; the last line is one JSON
object ``{"ok": ..., "device": {"platform", "kind", "count"}}``. The script
exits 0 only on a TPU with every check passed: without a chip it still runs
every phase (the CPU rehearsal) and then reports failure.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

CATALOGUE_N = 105_100      # the paper's product catalogue (DESIGN.md §1)
CPU_REHEARSAL_N = 8_192    # default n without a chip: every phase, quickly
D = 2048
N_FIELDS = 24
Q = 256
K = 25
INGEST_ROWS = 1024
CONJ_SELS = (0.5, 0.1, 0.02)
# recall@25 this script measured with JAX_PLATFORMS=cpu at FLOOR_AT (n,
# seed) (the CPU path: jnp oracles, full-f32 scores); a chip run at the
# same (n, seed) must reach each value minus FLOOR_SLACK. Other sizes and
# seeds have no floor.
CPU_RECALL = {"conj_sel0.5": 0.0852, "conj_sel0.1": 0.1663,
              "conj_sel0.02": 0.1347, "or_sel0.1": 0.1452,
              "range_sel0.1": 0.1405}
FLOOR_AT = (CATALOGUE_N, 0)
FLOOR_SLACK = 0.02
# a returned score further than this from the float64 cosine distance of
# its row was not computed in full f32 (bf16 operands miss by ~1e-4)
SCORE_ATOL = 1e-5


def say(key: str, value) -> None:
    """One result line, prefixed by the device it ran on."""
    import jax

    dev = jax.devices()[0]
    print(f"[{dev.platform}/{dev.device_kind}] {key}: {value}", flush=True)


def paper_config(**serve):
    """The paper's index/search settings (configs/fns.py PAPER) as the
    one FnsConfig every engine consumes."""
    from repro.configs.fns import PAPER
    from repro.core.config import FnsConfig

    knobs = {"graph.graph_k": PAPER.graph_k, "graph.r_max": PAPER.r_max,
             "graph.alpha": PAPER.alpha, "walk.k": PAPER.k,
             "walk.jump_budget": PAPER.jump_budget,
             "walk.c_max": PAPER.c_max, "walk.n_seeds": PAPER.n_seeds,
             "walk.frontier_width": PAPER.frontier_width,
             "walk.stall_budget": PAPER.stall_budget,
             "walk.max_hops": PAPER.max_hops}
    knobs.update({f"serve.{k}": v for k, v in serve.items()})
    return FnsConfig().with_knobs(knobs)


def make_corpus(n: int, extra: int, seed: int):
    """The catalogue corpus plus ``extra`` held-out rows for ingest, with
    the two engineered OR fields and the timestamp field the disjunctive
    and Range batches filter on."""
    from repro.core.types import Dataset
    from repro.data.synth import (SynthSpec, add_or_pair_fields,
                                  add_timestamp_field, make_dataset)

    full = make_dataset(SynthSpec(n=n + extra, d=D, n_fields=N_FIELDS,
                                  seed=seed))
    full = add_timestamp_field(add_or_pair_fields(full, seed=seed + 23),
                               seed=seed + 31)
    base = Dataset(full.vectors[:n], full.metadata[:n], full.field_names,
                   full.vocab_sizes)
    return base, full.vectors[n:], full.metadata[n:]


def _fill(counts: np.ndarray, goal: float) -> tuple[list[int], int]:
    """Values (most frequent first) whose counts sum as close to ``goal``
    as possible from below, or the single least-overshooting value."""
    vals, acc = [], 0
    for v in np.argsort(-counts, kind="stable"):
        c = int(counts[v])
        if c and acc + c <= goal:
            vals.append(int(v))
            acc += c
    if not vals:
        pos = np.nonzero(counts)[0]
        v = int(pos[np.argmin(counts[pos])])
        vals, acc = [v], int(counts[v])
    return vals, acc


def conjunctive_predicate(meta: np.ndarray, target: float):
    """A two-clause conjunction over the Zipf fields whose selectivity on
    ``meta`` is the closest to ``target`` that greedy value sets reach:
    field a takes values up to ~sqrt(target) of the rows, field b then
    values inside that set up to ``target``."""
    from repro.core.types import FilterPredicate

    n = meta.shape[0]
    best = None
    for fa in range(N_FIELDS):
        col = meta[:, fa]
        vals_a, _ = _fill(np.bincount(col[col >= 0]), math.sqrt(target) * n)
        in_a = np.isin(col, vals_a)
        for fb in range(N_FIELDS):
            if fb == fa:
                continue
            colb = meta[in_a, fb]
            vals_b, acc = _fill(np.bincount(colb[colb >= 0]), target * n)
            err = abs(math.log(max(acc, 1) / (target * n)))
            if best is None or err < best[0]:
                best = (err, {fa: vals_a, fb: vals_b})
    return FilterPredicate.make(best[1])


def queries_near(vectors, passes, n_q: int, seed: int):
    """Query vectors near random passing rows (recall is attainable)."""
    from repro.core.types import normalize

    rng = np.random.default_rng(seed)
    members = np.nonzero(passes)[0]
    src = members[rng.integers(members.size, size=n_q)]
    return normalize(vectors[src]
                     + 0.15 * rng.standard_normal((n_q, vectors.shape[1])))


def make_batches(ds, seed: int) -> dict:
    """name -> (query vectors (Q, d), shared predicate, pass mask)."""
    from repro.data.synth import make_or_queries, make_range_queries

    out = {}
    for i, sel in enumerate(CONJ_SELS):
        pred = conjunctive_predicate(ds.metadata, sel)
        passes = pred.mask(ds.metadata)
        out[f"conj_sel{sel}"] = (queries_near(ds.vectors, passes, Q,
                                              seed + 101 + i), pred, passes)
    for name, qs in (("or_sel0.1", make_or_queries(ds, 1, Q, seed=seed + 5)),
                     ("range_sel0.1", make_range_queries(ds, 0.1, Q,
                                                         seed=seed + 11))):
        pred = qs[0].predicate
        out[name] = (np.stack([q.vector for q in qs]), pred,
                     pred.mask(ds.metadata, ds.vocab_sizes))
    return out


def describe(pred) -> str:
    if hasattr(pred, "clauses"):
        return " & ".join(f"field{f} in <{len(v)} values>"
                          for f, v in pred.clauses)
    return repr(pred)


def device_line():
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def check_kernel_parity(ds, batches, cfg, checks: dict) -> None:
    """Phase 1: filter_eval kernel vs its jnp oracle at full width, bit for
    bit, in the three table forms the batches pack to; and the kernel's
    compiled program must hold a tpu_custom_call on the chip."""
    import jax
    import jax.numpy as jnp

    from repro.core.batched.engine import pack_query_batch
    from repro.core.device_atlas import auto_v_cap
    from repro.core.types import Query
    from repro.kernels import ops, ref

    v_cap = auto_v_cap(int(ds.metadata.max()))
    meta = jnp.asarray(ds.metadata)
    tn = cfg.kernel.filter_tile
    tpu = ops.on_tpu()
    for name, (qv, pred, _) in batches.items():
        qs = [Query(vector=v, predicate=pred) for v in qv]
        _, fields, allowed, bounds = pack_query_batch(
            qs, v_cap=v_cap, vocab_sizes=ds.vocab_sizes)
        form = ("rank2" if fields.ndim == 2 else
                "dnf" if bounds is None else "dnf_bounds")
        got = ops.filter_eval_batch(meta, fields, allowed, None, bounds,
                                    tn=tn)
        want = ref.filter_eval_batch(meta, fields, allowed, bounds=bounds)
        same = bool(np.array_equal(np.asarray(got), np.asarray(want)))
        checks[f"kernel_parity/{name}"] = same
        line = f"form={form} tables={tuple(fields.shape)} bit_identical={same}"
        if tpu:
            txt = jax.jit(ops.filter_eval_batch, static_argnames=("tn",)) \
                .lower(meta, fields, allowed, None, bounds, tn=tn) \
                .compile().as_text()
            has = "tpu_custom_call" in txt
            checks[f"kernel_custom_call/{name}"] = has
            line += f" tpu_custom_call={has}"
        say(f"filter_eval_kernel[{name}]", line)


def random_visit_recall(hops: np.ndarray, mean_degree: float,
                        n: int) -> float:
    """Expected recall@k of a visit that scores as many rows as the walk
    (``hops`` x the graph's mean degree per query) drawn uniformly from
    the corpus: each true neighbour is among them with probability
    rows/n. A walk that navigates beats it by a wide margin."""
    return float(np.mean(np.minimum(1.0, hops * mean_degree / n)))


def score_error(vectors, q_vecs, res_v, res_i) -> float:
    """Largest gap between a returned cosine distance and the float64
    one of the same (query, row) pair."""
    res_v, res_i = np.asarray(res_v), np.asarray(res_i)
    ok = res_i >= 0
    rows = vectors[np.where(ok, res_i, 0)].astype(np.float64)
    exact = 1.0 - np.einsum("qkd,qd->qk", rows,
                            np.asarray(q_vecs, np.float64))
    return float(np.abs(np.where(ok, res_v - exact, 0.0)).max())


def search_program_text(eng, queries) -> str:
    """The optimized HLO of the engine's fused search program for this
    batch's table form (compiled already by the batch itself)."""
    q_vecs, fields, allowed, bounds = eng._pack_queries(queries)
    return eng._search.lower(eng.datlas, eng.vectors, eng.adjacency,
                             eng.metadata, q_vecs, fields, allowed,
                             valid_bm=eng._valid_bm,
                             bounds=bounds).compile().as_text()


def run_one_chip(args, checks: dict) -> None:
    import jax

    from repro.core.types import FilterPredicate, Query
    from repro.data.ground_truth import filtered_topk_shared, recall_at_k
    from repro.kernels.ops import on_tpu
    from repro.serve.pipeline import ServePipeline
    from repro.serve.retrieval import RetrievalService

    tpu = on_tpu()
    floors = (args.n, args.seed) == FLOOR_AT
    (ds, new_v, new_m), t_data = timed(make_corpus, args.n, INGEST_ROWS,
                                       args.seed)
    say("corpus", f"n={ds.n} d={ds.d} fields={ds.n_fields} "
        f"(24 Zipf + orA/orB + ts) ingest_rows={len(new_v)} "
        f"seed={args.seed} make_s={t_data:.3f}")
    cfg = paper_config(capacity=args.n + INGEST_ROWS, queue_max_batch=Q)
    batches = make_batches(ds, args.seed)
    for name, (_, pred, passes) in batches.items():
        say(f"batch[{name}]", f"Q={Q} selectivity={passes.mean():.4f} "
            f"predicate={describe(pred)}")

    check_kernel_parity(ds, batches, cfg, checks)

    svc, t_host = timed(RetrievalService.build, ds, config=cfg)
    eng, t_dev = timed(svc.engine)
    jax.block_until_ready(eng.vectors)
    g = svc.index.graph
    say("build_s", f"{t_host + t_dev:.3f} (host graph+atlas {t_host:.3f}, "
        f"device placement {t_dev:.3f}; mean_degree "
        f"{g.degrees.mean():.1f} max_degree {int(g.degrees.max())} "
        f"adjacency_width {eng.adjacency.shape[1]})")

    results = {}
    forms_seen = {}
    for name, (qv, pred, passes) in batches.items():
        preds = [pred] * Q
        (ids, stats), t_first = timed(svc.query_batch, qv, preds)
        qs = [Query(vector=v, predicate=pred) for v in qv]
        packed = eng._pack_queries(qs)

        def fused():
            return jax.block_until_ready(eng._search(
                eng.datlas, eng.vectors, eng.adjacency, eng.metadata,
                *packed[:3], valid_bm=eng._valid_bm, bounds=packed[3]))

        out = fused()
        err = score_error(ds.vectors, packed[0], out["res_v"],
                          out["res_i"])
        checks[f"scores_full_f32/{name}"] = err <= SCORE_ATOL
        warm = "not measured (no chip)"
        if tpu:
            reps = [timed(svc.query_batch, qv, preds)[1] * 1e3
                    for _ in range(args.reps)]
            dev = [timed(fused)[1] * 1e3 for _ in range(args.reps)]
            warm = (f"{statistics.median(reps):.3f} query_batch ms "
                    f"(median of {args.reps}), "
                    f"{statistics.median(dev):.3f} ms fused program alone")
        gt = filtered_topk_shared(ds.vectors, qv, passes, K)
        rec = float(np.mean([recall_at_k(np.asarray(i), g_)
                             for i, g_ in zip(ids, gt)]))
        rand = random_visit_recall(stats["hops"], g.degrees.mean(), ds.n)
        ok_pred = all(passes[np.asarray(i, np.int64)].all() for i in ids)
        checks[f"predicate/{name}"] = ok_pred
        floor = f"none (measured at n={FLOOR_AT[0]} seed={FLOOR_AT[1]})"
        if floors:
            checks[f"recall_floor/{name}"] = \
                rec >= CPU_RECALL[name] - FLOOR_SLACK
            floor = f"{CPU_RECALL[name] - FLOOR_SLACK:.4f}"
        results[name] = ids
        say(f"query_batch[{name}]",
            f"first_call_s={t_first:.3f} warm={warm} recall@25={rec:.4f} "
            f"floor={floor} random_visit_recall={rand:.4f} "
            f"ids_pass_predicate={ok_pred} max_score_error={err:.2e} "
            f"mean_hops={stats['hops'].mean():.1f} "
            f"mean_walks={stats['walks'].mean():.2f}")
        key = ("rank2" if packed[1].ndim == 2 else
               "dnf" if packed[3] is None else "dnf_bounds")
        forms_seen.setdefault(key, qs)
    for form, qs in forms_seen.items():
        has = "tpu_custom_call" in search_program_text(eng, qs)
        if tpu:
            checks[f"search_custom_call/{form}"] = has
        say(f"search_program[{form}]", f"tpu_custom_call={has}")
    checks["all_three_forms"] = len(forms_seen) == 3

    # phase 4: the same traffic through the admission queue + pipeline
    pipe = ServePipeline(svc)
    tickets = {}
    t0 = time.perf_counter()
    for name, (qv, pred, _) in batches.items():
        tickets[name] = [pipe.submit(v, pred) for v in qv]
        pipe.pump()
    pipe.drain()
    t_pipe = time.perf_counter() - t0
    same = all(np.array_equal(np.sort(t.ids), np.sort(r))
               for name, ts in tickets.items()
               for t, r in zip(ts, results[name]))
    checks["pipeline_matches_query_batch"] = same
    say("serve_pipeline", f"batches={pipe.batches} tickets="
        f"{sum(map(len, tickets.values()))} wall_s={t_pipe:.3f} "
        f"ids_match_query_batch={same}")

    # phase 5: ingest, publish, then every new row must be found
    gen0 = eng.publish_generation
    gids, t_ing = timed(svc.ingest, new_v, new_m)
    say("ingest", f"rows={len(gids)} seconds={t_ing:.3f} "
        f"publish_generation {gen0}->{eng.publish_generation}")
    found = 0
    for lo in range(0, len(new_v), Q):
        rows = range(lo, min(lo + Q, len(new_v)))
        preds = []
        for r in rows:
            f = int(np.nonzero(new_m[r, :N_FIELDS] >= 0)[0][0])
            preds.append(FilterPredicate.make({f: [int(new_m[r, f])]}))
        ids, _ = svc.query_batch(new_v[lo:lo + Q], preds)
        found += sum(int(gids[r] in set(np.asarray(i).tolist()))
                     for r, i in zip(rows, ids))
    checks["ingested_rows_found"] = found == len(new_v)
    say("post_ingest_search", f"found {found}/{len(new_v)} new rows "
        f"in their own filtered top-{K}")


def run_four_chips(args, checks: dict) -> None:
    import jax

    from repro.core.types import Query
    from repro.data.ground_truth import filtered_topk_shared, recall_at_k
    from repro.launch.mesh import make_serving_mesh
    from repro.serve.retrieval import RetrievalService

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, JAX sees "
                           f"{len(jax.devices())}")
    # the one-chip corpus (its held-out ingest rows unused), so both runs
    # search the same rows
    (ds, _, _), t_data = timed(make_corpus, args.n, INGEST_ROWS, args.seed)
    say("corpus", f"n={ds.n} d={ds.d} fields={ds.n_fields} "
        f"seed={args.seed} make_s={t_data:.3f}")
    cfg = paper_config()
    # every table form: rank-2 (conjunctive), DNF (OR) and DNF + bounds
    # (Range), each its own mesh program
    batches = make_batches(ds, args.seed)
    for data, query in ((4, 1), (2, 2)):
        tag = f"mesh{data}x{query}"
        mesh = make_serving_mesh(data, query)
        svc = RetrievalService.build(ds, config=cfg, mesh=mesh)
        eng, t_build = timed(svc.sharded_engine)
        jax.block_until_ready(eng.vectors)
        spans = {d.id for d in mesh.devices.flat}
        placed = {d.id for d in eng.vectors.sharding.device_set}
        checks[f"{tag}/spans_4_devices"] = len(spans) == 4 == len(placed)
        say(f"{tag}", f"data={data} query={query} shards={eng.n_shards} "
            f"q_lanes={eng.q_lanes} mesh_devices={sorted(spans)} "
            f"index_devices={sorted(placed)} build_s={t_build:.3f}")
        for name, (qv, pred, passes) in batches.items():
            qs = [Query(vector=v, predicate=pred) for v in qv]
            d0 = eng.dispatches
            (ids, st), t_first = timed(eng.search, qs)
            one_dispatch = eng.dispatches - d0 == 1
            _, t_warm = timed(eng.search, qs)
            ids_r, st_r = eng.search_reference(qs)
            same = (all(np.array_equal(a, b) for a, b in zip(ids, ids_r))
                    and np.array_equal(st["hops"], st_r["hops"])
                    and np.array_equal(st["walks"], st_r["walks"]))
            gt = filtered_topk_shared(ds.vectors, qv, passes, K)
            rec = float(np.mean([recall_at_k(np.asarray(i), g_)
                                 for i, g_ in zip(ids, gt)]))
            checks[f"{tag}/bit_identical/{name}"] = same
            checks[f"{tag}/one_dispatch/{name}"] = one_dispatch
            say(f"{tag}[{name}]", f"bit_identical_to_search_reference={same} "
                f"one_dispatch={one_dispatch} first_call_s={t_first:.3f} "
                f"warm_ms={t_warm * 1e3:.3f} recall@25={rec:.4f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=None,
                    help=f"corpus rows (default {CATALOGUE_N} on a TPU, "
                         f"{CPU_REHEARSAL_N} on the CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=3,
                    help="warm repetitions per batch on the chip")
    args = ap.parse_args()

    import jax

    from repro.kernels.ops import on_tpu
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = device_line()
    say("compile_cache", cache)
    say("device", f"platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']} jax={jax.__version__}")
    if args.n is None:
        args.n = CATALOGUE_N if dev["platform"] == "tpu" else CPU_REHEARSAL_N
    if args.n != CATALOGUE_N:
        say("n_cut", f"n={args.n} instead of the catalogue's {CATALOGUE_N} "
            f"(d, fields and the index settings are unchanged)")
    checks: dict[str, bool] = {}
    t0 = time.perf_counter()
    try:
        tpu = on_tpu()
        (run_four_chips if args.chips == 4 else run_one_chip)(args, checks)
    except Exception:  # report the failure in the last line, then exit 1
        traceback.print_exc()
        checks["phases_completed"] = False
        tpu = False
    checks["platform_is_tpu"] = tpu
    failed = sorted(k for k, v in checks.items() if not v)
    say("total_s", f"{time.perf_counter() - t0:.3f}")
    if jax.devices()[0].platform == "tpu":
        stats = jax.devices()[0].memory_stats() or {}
        say("peak_device_bytes", stats.get("peak_bytes_in_use",
                                           "not reported"))
    say("failed_checks", failed or "none")
    ok = not failed
    print(json.dumps({"ok": ok, "device": dev}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
