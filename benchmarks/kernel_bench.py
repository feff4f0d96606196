"""Kernel + engine microbenchmarks: wall-clock on whatever backend JAX
runs (a CPU number here measures XLA's CPU backend, not the chip)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import FnsConfig, WalkConfig
from repro.core.device_atlas import pack_predicates
from repro.core.search import SearchParams, run_queries
from repro.kernels import ref
from benchmarks.datasets import K, get_indexes
from benchmarks.search_bench import engine_over


def _time(fn, *args, reps=5):
    fn(*args)  # warm/compile
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6  # us


def kernel_microbench():
    """us/call for the jnp ops of the search path: the walk's expansion
    (gather + full-f32 dot, the same XLA program on every platform) and the
    filter_eval oracle the Pallas kernel must beat on a TPU
    (interpret-mode Pallas timings are not meaningful and are excluded)."""
    rng = np.random.default_rng(0)
    n, d, Q, R = 8192, 256, 32, 128
    corpus = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    queries = jnp.asarray(rng.standard_normal((Q, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, n, (Q, R)), jnp.int32)
    bits = jnp.asarray(rng.integers(0, 2, (Q, R)), bool)
    meta = jnp.asarray(rng.integers(-1, 40, (n, 24)), jnp.int32)
    fields = jnp.asarray([0, 5, -1, -1], jnp.int32)
    allowed = jnp.asarray(rng.integers(0, 2, (4, 256)), jnp.uint8)
    out = {}
    f2 = jax.jit(ref.fiber_expand_walk)
    out["fiber_expand_walk"] = _time(f2, queries, corpus, ids, bits)
    f3 = jax.jit(ref.filter_eval)
    out["filter_eval_ref"] = _time(f3, meta, fields, allowed)
    return out


def anchor_select_bench(batch_sizes=(16, 64, 256), reps: int = 5):
    """Anchor-selection throughput (queries/s): host per-query Python loop
    over ``AnchorAtlas.select_anchors`` vs one batched device call to
    ``DeviceAtlas.select_anchors_batch``, at Q in ``batch_sizes``."""
    ds, qs, idx_alpha, _, _ = get_indexes()
    atlas = idx_alpha.atlas
    datlas = atlas.to_device()
    vectors = jnp.asarray(ds.vectors)
    out = {}
    for q_n in batch_sizes:
        sub = [qs[i % len(qs)] for i in range(q_n)]
        q_vecs = jnp.asarray(np.stack([q.vector for q in sub]))
        passes = jnp.asarray(np.stack(
            [q.predicate.mask(ds.metadata) for q in sub]))
        ct = tuple(jnp.asarray(x) for x in
                   pack_predicates([q.predicate for q in sub]))
        proc = jnp.zeros((q_n, atlas.n_clusters), bool)
        t0 = time.time()
        for _ in range(reps):
            for q in sub:
                atlas.select_anchors(q.vector, q.predicate, set(),
                                     n_seeds=10, c_max=5,
                                     vectors=ds.vectors)
        out[f"host_q{q_n}"] = q_n * reps / (time.time() - t0)
        fn = jax.jit(lambda qv, pr, ps: datlas.select_anchors_batch(
            qv, ct, pr, vectors, ps, n_seeds=10, c_max=5))
        jax.block_until_ready(fn(q_vecs, proc, passes))  # compile
        t0 = time.time()
        for _ in range(reps):
            res = fn(q_vecs, proc, passes)
        jax.block_until_ready(res)
        out[f"device_q{q_n}"] = q_n * reps / (time.time() - t0)
    return out


def engine_bench():
    """Measured QPS: sequential reference vs batched lockstep engine."""
    ds, qs, idx_alpha, _, _ = get_indexes()
    sub = qs[:128]
    t0 = time.time()
    ids_ref, _ = run_queries(idx_alpha, sub,
                             SearchParams(k=K, walk="guided", beam_width=2))
    t_ref = time.time() - t0
    cfg = FnsConfig(walk=WalkConfig(k=K, beam_width=4))
    eng = engine_over(idx_alpha, cfg)
    eng.search(sub)  # compile at the timed batch shape
    t0 = time.time()
    ids_b, _ = eng.search(sub)
    t_b = time.time() - t0
    from repro.data.ground_truth import recall_at_k
    rec_ref = float(np.mean([recall_at_k(i, q.gt_ids)
                             for i, q in zip(ids_ref, sub)]))
    rec_b = float(np.mean([recall_at_k(np.asarray(i), q.gt_ids)
                           for i, q in zip(ids_b, sub)]))
    return {"reference_qps": len(sub) / t_ref, "reference_recall": rec_ref,
            "batched_qps": len(sub) / t_b, "batched_recall": rec_b}
