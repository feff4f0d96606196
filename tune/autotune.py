"""λ-weighted latency/recall autotuner for the fused engine's walk knobs
(DESIGN.md §11).

The fused single-dispatch engine's recall at moderate selectivity has sat
on a plateau (~0.51 at sel 0.5 with the default walk budgets) because the
defaults were chosen for latency, never searched. This module searches
the RUNTIME-TUNABLE part of the config space — ``walk.*`` only, so the
result applies to any already-built index with the bench's shape-baked
knobs — by coordinate descent over a small per-knob value grid, scoring

    score(cfg) = Σ_sel  recall(cfg, sel) − λ · p50_ms(cfg, sel)

on the exact ``benchmarks/search_bench`` fixture (same corpus recipe,
same query pools, same ``measure_batch`` protocol), subject to a hard
feasibility gate: every selectivity's p50 must stay within
``latency_budget`` × the untuned baseline's p50 (default 1.20× inside the
tuner, leaving headroom under the 1.25× acceptance bar the BENCH rows
are held to).

λ is the exchange rate between recall points and milliseconds: at the
default λ=0.003/ms, one point of recall (0.01) buys ~3.3ms of p50 — so a
knob that adds 3ms must add more than ~1 recall point to survive. Raise
λ to prefer latency, lower it to prefer recall; the feasibility gate
bounds the damage of a too-low λ either way.

Writes ``results/tuned_cpu.json``: the winning flattened config + its
fingerprint, per-selectivity rows (re-measured at the bench's full rep
count), the baseline it beat, and the accepted coordinate-descent steps.
``benchmarks/search_bench.tuned_search_bench`` consumes the artifact to
emit the committed ``tuned/*`` BENCH rows, and the CI bench-regression
gate replays it at smoke scale.

Run:  PYTHONPATH=src:. python tune/autotune.py [--lam 0.003] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.search_bench import (SELECTIVITIES,  # noqa: E402
                                     bench_config, build_search_fixture,
                                     device_info, engine_over,
                                     make_query_pools, measure_batch)

# the searched subspace: every axis is a walk.* knob (runtime-tunable by
# definition — see core/config.py SHAPE_BAKED), with a small monotone
# value grid around the default. Order matters for coordinate descent:
# the biggest lever (beam width: frontier pops per step) goes first so
# later axes refine around its choice.
AXES: dict[str, tuple] = {
    "walk.beam_width": (4, 6, 8, 12, 16),
    "walk.n_seeds": (6, 10, 16, 24, 32),
    "walk.c_max": (3, 5, 8),
    "walk.frontier_width": (3, 5, 8),
    "walk.frontier_cap": (8, 16, 32),
    "walk.stall_budget": (50, 100, 200),
    "walk.jump_budget": (1, 2, 3, 5),
}

SEARCH_REPS = 5    # per-candidate timing reps (scoring)
FINAL_REPS = 20    # winner + baseline re-measurement (reporting)


def measure_config(cfg, index, pools, q_n: int, reps: int) -> dict:
    """Per-selectivity rows for one config on the shared fixture."""
    eng = engine_over(index, cfg)
    return {sel: measure_batch(eng, pools[sel][:q_n], reps)
            for sel in pools}


def score_rows(rows: dict, lam: float) -> float:
    return sum(r["recall"] - lam * r["p50_ms"] for r in rows.values())


def feasible(rows: dict, base_rows: dict, budget: float) -> bool:
    return all(rows[sel]["p50_ms"] <= budget * base_rows[sel]["p50_ms"]
               for sel in base_rows)


def autotune(*, lam: float = 0.003, latency_budget: float = 1.20,
             n: int = 8000, d: int = 64, k: int = 10, graph_k: int = 16,
             seed: int = 7, q_n: int = 64, selectivities=SELECTIVITIES,
             max_sweeps: int = 2, log=print) -> dict:
    """Coordinate descent over ``AXES`` from the bench default config.

    One sweep tries every alternative value on every axis in turn,
    accepting a move iff it is feasible AND improves the λ-score; sweeps
    repeat until a full pass accepts nothing (or ``max_sweeps``). The
    walk space is mildly coupled (seeds × beam × budgets), but the score
    surface is monotone enough per-axis that two sweeps recover the
    interactions that matter at this scale."""
    cfg = bench_config(k=k, graph_k=graph_k)
    log(f"[autotune] building fixture n={n} d={d} graph_k={graph_k}")
    ds, index = build_search_fixture(selectivities, n=n, d=d, seed=seed,
                                     config=cfg)
    pools = make_query_pools(ds, selectivities, q_n, k)

    base_rows = measure_config(cfg, index, pools, q_n, SEARCH_REPS)
    base_score = score_rows(base_rows, lam)
    log(f"[autotune] baseline score={base_score:.4f} " + " ".join(
        f"sel{s}: r={r['recall']:.3f} p50={r['p50_ms']:.1f}ms"
        for s, r in base_rows.items()))

    best_cfg, best_rows, best_score = cfg, base_rows, base_score
    history = []
    trail = [cfg]  # accepted configs, oldest first, for the final fallback
    for sweep in range(max_sweeps):
        improved = False
        for axis, values in AXES.items():
            current = best_cfg.flatten()[axis]
            for v in values:
                if v == current:
                    continue
                cand = best_cfg.with_knobs({axis: v})
                rows = measure_config(cand, index, pools, q_n, SEARCH_REPS)
                sc = score_rows(rows, lam)
                ok = feasible(rows, base_rows, latency_budget)
                log(f"[autotune]   {axis}={v}: score={sc:.4f} "
                    f"{'ok' if ok else 'OVER-BUDGET'}")
                if ok and sc > best_score:
                    best_cfg, best_rows, best_score = cand, rows, sc
                    current = v
                    improved = True
                    history.append({"axis": axis, "value": v,
                                    "score": sc, "sweep": sweep})
                    trail.append(cand)
                    log(f"[autotune] -> accept {axis}={v} "
                        f"(score {sc:.4f})")
        if not improved:
            break

    # re-measure winner and baseline at the reporting rep count, and hold
    # the winner to the budget at THIS rep count too: the descent's 5-rep
    # timings are noisy enough that a borderline config can sneak through,
    # so fall back along the accepted trail until the re-measured p50s fit
    final_base = measure_config(cfg, index, pools, q_n, FINAL_REPS)
    while True:
        best_cfg = trail.pop()
        final_rows = measure_config(best_cfg, index, pools, q_n, FINAL_REPS)
        if feasible(final_rows, final_base, latency_budget) or not trail:
            break
        log(f"[autotune] final re-measure over budget; reverting "
            f"{history.pop()['axis']}")
    return {
        **device_info(),
        "lambda": lam,
        "latency_budget": latency_budget,
        "fixture": {"n": n, "d": d, "k": k, "graph_k": graph_k,
                    "seed": seed, "q_n": q_n,
                    "selectivities": list(selectivities)},
        "fingerprint": best_cfg.fingerprint(),
        "config": best_cfg.flatten(),
        "score": score_rows(final_rows, lam),
        "rows": {f"q{q_n}/sel{s}": r for s, r in final_rows.items()},
        "baseline": {"fingerprint": cfg.fingerprint(),
                     "score": score_rows(final_base, lam),
                     "rows": {f"q{q_n}/sel{s}": r
                              for s, r in final_base.items()}},
        "history": history,
    }


def main(argv=None) -> dict:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--lam", type=float, default=0.003,
                    help="latency weight: recall units per p50 ms")
    ap.add_argument("--budget", type=float, default=1.20,
                    help="per-selectivity p50 cap as a multiple of baseline")
    ap.add_argument("--out", default=os.path.join("results",
                                                  "tuned_cpu.json"))
    ap.add_argument("--sweeps", type=int, default=2)
    args = ap.parse_args(argv)
    result = autotune(lam=args.lam, latency_budget=args.budget,
                      max_sweeps=args.sweeps)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1, default=float)
    print(f"[autotune] wrote {args.out} fingerprint={result['fingerprint']}")
    for key, row in result["rows"].items():
        base = result["baseline"]["rows"][key]
        print(f"[autotune] {key}: recall {base['recall']:.3f} -> "
              f"{row['recall']:.3f}, p50 {base['p50_ms']:.1f} -> "
              f"{row['p50_ms']:.1f}ms")
    return result


if __name__ == "__main__":
    main()
