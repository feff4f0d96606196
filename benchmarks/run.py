"""Benchmark driver: one section per paper table + kernel/engine benches.

Prints ``name,us_per_call,derived`` CSV lines (per harness contract) plus
human-readable tables, and writes results/benchmarks.json for EXPERIMENTS.md.

Every run starts with a kernel/oracle parity gate and exits nonzero on any
mismatch, so a drifting kernel can't silently poison the numbers.
``--smoke`` runs only the parity gate plus a tiny end-to-end search bench
(2 queries) — the CI guard that keeps these entrypoints from rotting.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _csv(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")


def kernel_oracle_parity(n: int = 800, d: int = 64) -> list[str]:
    """Fixed-shape parity probes at corpus width ``d``: the filter_eval
    Pallas entry points (interpret mode on the CPU, Mosaic on a TPU) vs
    their jnp oracles, bit for bit, and the walk's expansion op vs a
    float64 numpy gather+dot (full f32 on every platform: a TPU's default
    f32 matmul would round to bf16). Returns a list of mismatch
    descriptions (empty = all good)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.device_atlas import pack_dnf, pack_predicates
    from repro.core.predicate import (And, FilterExpr, In, Not, Or, Range,
                                      compile_to_dnf)
    from repro.core.types import FilterPredicate
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    q_n, r = 6, 24
    corpus_np = rng.standard_normal((n, d)).astype(np.float32)
    queries_np = rng.standard_normal((q_n, d)).astype(np.float32)
    bitmap_np = rng.integers(0, 2**32, (q_n, (n + 31) // 32),
                             dtype=np.uint32)
    ids_np = rng.integers(-1, n, (q_n, r)).astype(np.int32)
    meta = jnp.asarray(rng.integers(-1, 40, (n, 6)), jnp.int32)
    preds = [FilterPredicate.make({0: [3, 4], 2: [1]}),
             FilterPredicate.make({1: list(range(10))}),
             FilterPredicate.make({})] * 2
    f_np, a_np = pack_predicates(preds, v_cap=64)
    fields_b, allowed_b = jnp.asarray(f_np), jnp.asarray(a_np)
    fields1 = jnp.asarray([0, 5, -1, -1], jnp.int32)
    allowed1 = jnp.asarray(rng.integers(0, 2, (4, 256)), jnp.uint8)

    fails: list[str] = []

    def _chk(name, got, want, exact=False):
        got, want = np.asarray(got), np.asarray(want)
        ok = (np.array_equal(got, want) if exact
              else np.allclose(got, want, rtol=1e-4, atol=1e-4))
        if not ok:
            fails.append(f"{name} (n={n}, d={d}): kernel != oracle")

    safe = np.maximum(ids_np, 0)
    bits = (np.take_along_axis(bitmap_np, safe >> 5, axis=1)
            >> (safe & 31).astype(np.uint32)) & 1
    sims, sims_p = ref.fiber_expand_walk(
        jnp.asarray(queries_np), jnp.asarray(corpus_np),
        jnp.asarray(ids_np), jnp.asarray(bits == 1))
    want = np.einsum("qrd,qd->qr", corpus_np[safe].astype(np.float64),
                     queries_np.astype(np.float64))
    _chk("fiber_expand_walk/sims", sims,
         np.where(ids_np >= 0, want, -np.inf))
    _chk("fiber_expand_walk/sims_pass", sims_p,
         np.where((ids_np >= 0) & (bits == 1), want, -np.inf))
    _chk("filter_eval", ops.filter_eval(meta, fields1, allowed1),
         ref.filter_eval(meta, fields1, allowed1), exact=True)
    _chk("filter_eval_batch",
         ops.filter_eval_batch(meta, fields_b, allowed_b),
         ref.filter_eval_batch(meta, fields_b, allowed_b), exact=True)

    # disjunction path (DESIGN.md §8): DNF clause tables through the
    # in-kernel disjunct union vs the jnp oracle vs the expression tree
    vocab = [40] * 6
    exprs = [Or(In(0, [3, 4]), In(2, [1])),
             Not(In(1, list(range(10)))),
             And(In(0, [3, 4]), Or(In(2, [1]), In(5, [2]))),
             Or(Range(3, 5, 20), And(In(0, [1, 2]), Not(In(4, [0])))),
             FilterExpr.never(), FilterExpr.always()]
    dnfs = [compile_to_dnf(e, vocab) for e in exprs]
    # the Range leaf keeps this batch on the bounds-table path
    f_d, a_d, b_d, nd = pack_dnf(dnfs, v_cap=64)
    b_dj = None if b_d is None else jnp.asarray(b_d)
    out_dk = np.asarray(ops.filter_eval_batch(
        meta, jnp.asarray(f_d), jnp.asarray(a_d), jnp.asarray(nd), b_dj))
    _chk("filter_eval_batch/dnf", out_dk,
         ref.filter_eval_batch(meta, jnp.asarray(f_d), jnp.asarray(a_d),
                               bounds=b_dj),
         exact=True)
    meta_np = np.asarray(meta)
    for qi, e in enumerate(exprs):
        bits = np.unpackbits(out_dk[qi].view(np.uint8),
                             bitorder="little")[: meta_np.shape[0]]
        if not np.array_equal(bits.astype(bool), e.mask(meta_np, vocab)):
            fails.append(f"filter_eval_batch/dnf expr {qi}: "
                         f"kernel != expression-tree oracle")

    # interval path (DESIGN.md §8): Range clauses over a vocab far beyond
    # v_cap stay symbolic (f, lo, hi) bounds — kernel vs jnp oracle vs the
    # expression tree, bit-exact; table bytes independent of vocab width
    big_vocab = [40] * 5 + [1_000_000]
    meta_iv = meta.at[:, 5].set(jnp.asarray(
        rng.integers(-1, big_vocab[5], n), jnp.int32))
    iv_exprs = [Range(5, 100_000, 600_000),
                Not(Range(5, 250_000, None)),
                And(In(0, [3, 4]), Range(5, None, 900_000)),
                Or(Range(5, 0, 10_000), In(2, [1])),
                Range(5, 700_000, 10)]  # empty window -> never
    iv_dnfs = [compile_to_dnf(e, big_vocab, v_cap=64) for e in iv_exprs]
    f_i, a_i, b_i, nd_i = pack_dnf(iv_dnfs, v_cap=64)
    out_ik = np.asarray(ops.filter_eval_batch(
        meta_iv, jnp.asarray(f_i), jnp.asarray(a_i), jnp.asarray(nd_i),
        jnp.asarray(b_i)))
    _chk("filter_eval_batch/interval", out_ik,
         ref.filter_eval_batch(meta_iv, jnp.asarray(f_i), jnp.asarray(a_i),
                               bounds=jnp.asarray(b_i)),
         exact=True)
    meta_iv_np = np.asarray(meta_iv)
    for qi, e in enumerate(iv_exprs):
        bits = np.unpackbits(out_ik[qi].view(np.uint8),
                             bitorder="little")[: meta_iv_np.shape[0]]
        if not np.array_equal(bits.astype(bool),
                              e.mask(meta_iv_np, big_vocab)):
            fails.append(f"filter_eval_batch/interval expr {qi}: "
                         f"kernel != expression-tree oracle")
    return fails


def parity_gate() -> None:
    """Parity at the CI width and at the catalogue's d=2048 (several
    filter_eval corpus tiles), on whatever backend JAX runs."""
    fails = kernel_oracle_parity() + kernel_oracle_parity(n=16_384, d=2048)
    if fails:
        for f in fails:
            print(f"PARITY FAIL: {f}", file=sys.stderr)
        sys.exit(1)
    print(f"[parity {device_tag()}] all kernels match their oracles "
          f"(d=64 and d=2048)")


def device_tag() -> str:
    """platform/device_kind/count of the backend the numbers come from."""
    from benchmarks.search_bench import device_info

    info = device_info()
    return f"{info['backend']}/{info['device_kind']}/{info['device_count']}"


def durability_smoke() -> None:
    """Crash-recovery smoke (DESIGN.md §10), in this one process (a chip
    belongs to one process, so no child may need JAX): a durable service
    crashes at the ``ingest.post-slab-write`` fault hook (armed in-process,
    after the batch was journaled), is abandoned, and a fresh service
    recovers from the snapshot + journal it left; the kernels still match
    their oracles and filtered search works on the recovered index."""
    import tempfile

    import numpy as np

    from repro import faults
    from repro.core.search import SearchParams
    from repro.core.types import Dataset
    from repro.data.ground_truth import attach_ground_truth, recall_at_k
    from repro.data.synth import (make_selectivity_dataset,
                                  make_selectivity_queries)
    from repro.serve.retrieval import RetrievalService

    t0 = time.time()
    root = tempfile.mkdtemp(prefix="fns_smoke_crash_")
    ds = make_selectivity_dataset((0.5, 0.1, 0.02), n=420, d=16,
                                  n_components=6, seed=7)
    base = Dataset(ds.vectors[:360], ds.metadata[:360], ds.field_names,
                   ds.vocab_sizes)
    svc = RetrievalService.build(base, graph_k=8, r_max=24,
                                 params=SearchParams(k=5, max_hops=40),
                                 capacity=420)
    svc.enable_durability(root)
    svc.ingest(ds.vectors[360:390], ds.metadata[360:390])
    faults.arm("ingest.post-slab-write")
    try:
        svc.ingest(ds.vectors[390:420], ds.metadata[390:420])
        raise AssertionError("the armed fault point did not fire")
    except faults.InjectedFault:
        pass
    finally:
        faults.disarm()
    del svc  # the crashed service is never touched again
    rec_svc = RetrievalService.recover(root)
    rows = rec_svc.staleness()["corpus_rows"]
    # both ingests were journaled before the crash: nothing may be lost
    assert rows == 420, rec_svc.staleness()
    parity_gate()  # the kernels still match their oracles post-recovery
    qs = make_selectivity_queries(ds, 1, 4)
    attach_ground_truth(ds, qs, k=5)
    ids, _ = rec_svc.query_batch(np.stack([q.vector for q in qs]),
                                 [q.predicate for q in qs])
    rec = float(np.mean([recall_at_k(np.asarray(i), q.gt_ids)
                         for i, q in zip(ids, qs)]))
    assert rec >= 0.5, f"recovered-index recall {rec:.3f} is broken"
    _csv("durability/smoke_recover", (time.time() - t0) * 1e6,
         f"recall={rec:.3f} rows={rows}")
    print(f"[durability smoke {time.time()-t0:.0f}s] "
          f"crash -> recover -> parity OK (recall={rec:.3f})")


def smoke() -> None:
    """CI smoke: parity gate + tiny end-to-end search bench (2 queries) +
    a SIGKILL/recover round trip on a durable service."""
    from benchmarks.search_bench import main as search_main

    parity_gate()
    t0 = time.time()
    res = search_main(smoke=True)
    cell = next(v for k, v in res.items() if k != "config")
    assert cell["dispatches_per_batch"] == 1, cell
    assert 0.0 <= cell["recall"] <= 1.0
    _csv("search/smoke", 1e6 / cell["qps"],  # us/query, same unit as main()
         f"recall={cell['recall']:.3f}")
    sh = next(v for k, v in res.items() if k.startswith("sharded"))
    assert sh["dispatches_per_batch"] == 1, sh
    assert 0.0 <= sh["recall"] <= 1.0
    _csv("search/smoke_sharded", 1e6 / sh["qps"],
         f"recall={sh['recall']:.3f} shards={sh['n_shards']}")
    # disjunctive path: the or2 row ran its own kernel/oracle bitmap
    # parity gate inside or_search_bench (raises on mismatch)
    od = next(v for k, v in res.items() if k.startswith("or2_sel"))
    assert od["dispatches_per_batch"] == 1, od
    assert 0.0 <= od["recall"] <= 1.0
    assert od["n_disjuncts"] == 2
    _csv("search/smoke_or2", 1e6 / od["qps"], f"recall={od['recall']:.3f}")
    # dynamic-insert path: the append must complete and the grown index
    # must still answer in one dispatch with sane recall
    ins = next(v for k, v in res.items() if k.startswith("insert/"))
    assert ins["rows_per_s"] > 0, ins
    pi = next(v for k, v in res.items() if k.startswith("post_insert/"))
    assert pi["dispatches_per_batch"] == 1, pi
    assert 0.0 <= pi["recall"] <= 1.0
    _csv("search/smoke_insert", 1e6 / ins["rows_per_s"],
         f"post_recall={pi['recall']:.3f}")
    # serving pipeline: Q=1024 tickets through the admission queue /
    # batch former + double-buffered dispatch, with the p50/p99 sojourn
    # SLO row the BENCH_search.json trajectory tracks (DESIGN.md §13)
    slo = next(v for k, v in res.items() if k.startswith("serve_slo/q1024"))
    assert slo["batches"] >= 2, slo  # the queue really cut >1 bucket
    assert slo["p50_ms"] > 0.0 and slo["p99_ms"] >= slo["p50_ms"], slo
    assert 0.0 <= slo["recall"] <= 1.0
    _csv("search/smoke_serve_slo", 1e6 / slo["qps"],
         f"p50_ms={slo['p50_ms']:.1f} p99_ms={slo['p99_ms']:.1f} "
         f"batches={slo['batches']}")
    # durability rows: snapshot/restore/recover each completed and the
    # recovered index still answers in one fused dispatch
    pr = next(v for k, v in res.items() if k.startswith("post_recover/"))
    assert pr["dispatches_per_batch"] == 1, pr
    assert 0.0 <= pr["recall"] <= 1.0
    _csv("search/smoke_recover",
         res["durability/recover"]["ms"] * 1e3,
         f"post_recall={pr['recall']:.3f}")
    print(f"[smoke search bench {time.time()-t0:.0f}s] OK")
    durability_smoke()


def main() -> None:
    from benchmarks import tables as T
    from benchmarks.kernel_bench import (anchor_select_bench, engine_bench,
                                         kernel_microbench)
    from benchmarks.search_bench import OUT_PATH as SEARCH_OUT
    from benchmarks.search_bench import (durability_bench, insert_bench,
                                         or_search_bench, search_bench,
                                         slo_bench, write_baseline)

    results: dict = {}
    t_all = time.time()
    parity_gate()

    t0 = time.time()
    results["table2"] = T.table2_recall()
    print("\n== Table 2: Recall@25 (vs HNSW baselines) ==")
    print(f"{'method':26s} {'recall':>7s} {'>=0.8':>6s} {'=1.0':>6s} "
          f"{'zero':>6s} {'ms/q':>7s}")
    for m, r in results["table2"].items():
        print(f"{m:26s} {r['recall']:7.3f} {r['ge08']:6.1%} {r['eq1']:6.1%} "
              f"{r['zero']:6.2%} {r['ms']:7.2f}")
        _csv(f"table2/{m}", r["ms"] * 1000, f"recall={r['recall']:.3f}")
    print(f"[table2 {time.time()-t0:.0f}s]")

    t0 = time.time()
    results["table3"] = T.table3_walk_stats()
    print("\n== Table 3: Walk statistics ==")
    for m, r in results["table3"].items():
        prog = " ".join(f"w{j}={v:.3f}" for j, v in
                        r["recall_after_walk"].items())
        print(f"{m:12s} walks={r['mean_walks']:.2f} "
              f"1walk={r['resolved_1walk']:.1%} hops={r['mean_hops']:.1f} "
              f"recall={r['recall']:.3f} | {prog}")
        _csv(f"table3/{m}", r["mean_hops"], f"walks={r['mean_walks']:.2f}")
    print(f"[table3 {time.time()-t0:.0f}s]")

    t0 = time.time()
    run = T.stall_analysis_run()
    results["table4"] = T.table4_regimes(run)
    print("\n== Table 4: Regimes by selectivity (guided B=4) ==")
    print(f"{'bin':>9s} {'N':>4s} {'recall':>7s} {'hops':>7s} {'walks':>6s} "
          f"{'cut':>6s} {'fold':>6s} {'basin':>6s}")
    for row in results["table4"]:
        print(f"{row['bin']:>9s} {row['n']:4d} {row['recall']:7.3f} "
              f"{row['hops']:7.1f} {row['walks']:6.2f} "
              f"{row['topological_cut']:6.1%} {row['geometric_fold']:6.1%} "
              f"{row['genuine_basin']:6.1%}")
        _csv(f"table4/{row['bin']}", row["hops"],
             f"recall={row['recall']:.3f}")

    results["table5"] = T.table5_termination(run)
    print("\n== Table 5: Termination reasons by selectivity ==")
    print(f"{'bin':>9s} {'early':>7s} {'stall':>7s} {'maxhop':>7s} "
          f"{'conv':>7s}")
    for row in results["table5"]:
        print(f"{row['bin']:>9s} {row['early_stop']:7.1%} "
              f"{row['stall_budget']:7.1%} {row['max_hops']:7.1%} "
              f"{row['converged']:7.1%}")

    results["table6"] = T.table6_diagnostics(run)
    print("\n== Table 6: Stall-point diagnostics by regime ==")
    print(f"{'regime':16s} {'count':>6s} {'rho':>8s} {'|B-|':>6s} "
          f"{'drift':>8s} {'V(x*)':>7s} {'recall':>7s}")
    for reg, r in results["table6"].items():
        print(f"{reg:16s} {r['count']:6d} {r['rho']:8.4f} {r['b_minus']:6.1f} "
              f"{r['drift']:8.4f} {r['potential']:7.4f} {r['recall']:7.3f}")
    print(f"[tables 4-6 {time.time()-t0:.0f}s]")

    results["graph_stats"] = T.graph_statistics()
    print("\n== Graph statistics (paper §6) ==")
    for g, s in results["graph_stats"].items():
        print(f"{g:10s} edges={s['total_edges']:>9d} "
              f"mean={s['mean_degree']:6.1f} min={s['min_degree']:3d} "
              f"max={s['max_degree']:4d} mem={s['memory_mb']:6.1f}MB")

    t0 = time.time()
    results["kernels"] = kernel_microbench()
    print(f"\n== Kernel microbench (XLA ops, {device_tag()}) ==")
    for k, us in results["kernels"].items():
        print(f"{k:28s} {us:10.1f} us/call")
        _csv(f"kernel/{k}", us, device_tag())
    results["anchor_select"] = anchor_select_bench()
    print("\n== Anchor selection: host loop vs device batch (qps) ==")
    for name, qps in results["anchor_select"].items():
        print(f"{name:20s} {qps:10.1f} q/s")
        _csv(f"anchor_select/{name}", 1e6 / qps, f"qps={qps:.0f}")
    results["engine"] = engine_bench()
    e = results["engine"]
    print(f"\n== Engine: sequential vs batched ({device_tag()}) ==")
    print(f"reference: {e['reference_qps']:7.1f} qps recall={e['reference_recall']:.3f}")
    print(f"batched:   {e['batched_qps']:7.1f} qps recall={e['batched_recall']:.3f}")
    _csv("engine/reference", 1e6 / e["reference_qps"],
         f"recall={e['reference_recall']:.3f}")
    _csv("engine/batched", 1e6 / e["batched_qps"],
         f"recall={e['batched_recall']:.3f}")
    print(f"[kernels+engine {time.time()-t0:.0f}s]")

    t0 = time.time()
    results["search"] = search_bench()
    results["search"].update(or_search_bench())  # disjunctive or2 rows
    results["search"].update(insert_bench())     # dynamic-insert rows
    results["search"].update(durability_bench())  # snapshot/journal rows
    results["search"].update(slo_bench())        # serving p50/p99 SLO rows
    write_baseline(results["search"])
    print("\n== Fused single-dispatch search (Q x selectivity) ==")
    for name, r in results["search"].items():
        if name == "config":
            continue
        if name.startswith("insert/"):
            print(f"{name:14s} rows/s={r['rows_per_s']:8.1f} "
                  f"batch={r['batch_ms']:7.1f}ms "
                  f"repairs={r['reverse_edge_repairs']}")
            _csv(f"search/{name}", 1e6 / r["rows_per_s"],
                 f"rows_per_s={r['rows_per_s']:.0f}")
            continue
        if name.startswith("durability/"):
            kv = " ".join(f"{k}={v:.1f}" if isinstance(v, float)
                          else f"{k}={v}" for k, v in r.items())
            print(f"{name:28s} {kv}")
            if "ms" in r:
                _csv(name, r["ms"] * 1e3, "wall_ms_x1000")
            else:
                _csv(name, 1e6 / r["rows_per_s"],
                     f"rows_per_s={r['rows_per_s']:.0f}")
            continue
        if name.startswith("serve_slo/"):
            print(f"{name:32s} qps={r['qps']:8.1f} "
                  f"p50={r['p50_ms']:7.1f}ms p99={r['p99_ms']:7.1f}ms "
                  f"batches={r['batches']}")
            _csv(f"search/{name}", 1e6 / r["qps"],
                 f"p50_ms={r['p50_ms']:.1f} p99_ms={r['p99_ms']:.1f}")
            continue
        print(f"{name:14s} qps={r['qps']:8.1f} p50={r['p50_ms']:7.1f}ms "
              f"p99={r['p99_ms']:7.1f}ms recall={r['recall']:.3f} "
              f"mask={r.get('mask_state_bytes', 0)/1024:.0f}KiB")
        _csv(f"search/{name}", 1e6 / r["qps"], f"recall={r['recall']:.3f}")
    print(f"[search bench {time.time()-t0:.0f}s] -> {SEARCH_OUT}")

    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.json", "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"\n[total {time.time()-t_all:.0f}s] -> results/benchmarks.json")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()
