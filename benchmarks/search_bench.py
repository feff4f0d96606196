"""End-to-end filtered-search benchmark for the fused single-dispatch
engine: QPS, p50/p99 batch latency, and recall over the Q x selectivity
grid (Q in {16, 64, 256}, selectivity in {0.5, 0.1, 0.02}).

Writes ``BENCH_search.json`` at the repo root (results/ is gitignored and
this baseline is meant to be committed) — the first datapoint of the
serving perf trajectory. Each cell also records the walk mask-state footprint
(3 packed uint32 bitmaps: visited / in-results / pass = 3 * Q * ceil(n/32)
* 4 bytes) so regressions back to dense (Q, n) bool masks are visible.

``sharded_search_bench`` adds rows for the mesh-sharded engine
(``sharded<S>/qN/selX``): same corpus recipe, partitioned over the ``data``
axis, one shard_map dispatch per batch.

``or_search_bench`` adds disjunctive rows (``or2_sel0.1``, ``or2_sel0.02``):
two-field ``Or`` predicates with engineered union selectivity, compiled to
DNF clause tables and evaluated by the in-kernel disjunct union
(DESIGN.md §8) — still one fused dispatch per batch.

``range_search_bench`` adds interval rows (``range_sel0.5/0.1/0.02``):
prefix ``Range`` windows over a 2^20-code timestamp field, compiled to
symbolic bounds tables (never value-sets), with a built-in kernel/oracle
parity gate and a matched categorical-indicator baseline
(``recall_catbase``) in every row (DESIGN.md §8).

``insert_bench`` adds dynamic-insert rows (``insert/b<B>``: rows/sec of
the *acknowledged* append path — deferred-repair hot path since DESIGN.md
§12, with the drained graph repair timed separately as ``maintenance_ms``
— at batch sizes {64, 256, 1024}; ``post_insert/q64/sel0.1``: search QPS
+ recall on the grown, fully repaired index) — the ingest trajectory next
to the search trajectory it must not degrade (DESIGN.md §9).

``lifecycle_bench`` adds document-lifecycle rows (``delete_churn/b512``:
rows/sec of a 50% delete/re-insert churn with one budgeted maintenance
step per cycle; ``post_churn/q64/sel0.1``: search QPS + recall after the
final compaction, over a corpus identical to the never-churned one) —
the delete trajectory (DESIGN.md §12).

``--smoke`` (or smoke=True) runs a tiny corpus with 2 queries (fused +
sharded + disjunctive + insert paths): the CI entrypoint guard, not a
measurement.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core.atlas import AnchorAtlas
from repro.core.batched.engine import Engine
from repro.core.batched.sharded import ShardedIndex
from repro.core.config import FnsConfig
from repro.core.graph import build_alpha_knn
from repro.core.search import FiberIndex
from repro.data.ground_truth import attach_ground_truth, recall_at_k
from repro.data.synth import (add_or_pair_fields, add_timestamp_field,
                              add_window_indicator_fields, make_or_queries,
                              make_range_queries, make_selectivity_dataset,
                              make_selectivity_queries)

SELECTIVITIES = (0.5, 0.1, 0.02)
OR_SELECTIVITIES = (0.1, 0.02)
BATCH_SIZES = (16, 64, 256)
OUT_PATH = "BENCH_search.json"
TUNED_PATH = os.path.join("results", "tuned_cpu.json")


def bench_config(*, k: int = 10, graph_k: int = 16,
                 knobs: dict | None = None) -> FnsConfig:
    """The benchmark's single FnsConfig origin: every engine below is
    constructed from (a knob-overridden copy of) this tree, so a bench row
    and a serving engine built from the same fingerprint run the same
    program. The historical bench values (r_max = 3*graph_k, lockstep
    beam 4) are expressed as knobs here, not re-hard-coded at call sites."""
    cfg = FnsConfig().with_knobs({"walk.k": k, "walk.beam_width": 4,
                                  "graph.graph_k": graph_k,
                                  "graph.r_max": 3 * graph_k})
    return cfg.with_knobs(knobs) if knobs else cfg


def build_search_fixture(selectivities=SELECTIVITIES, *, n: int = 8000,
                         d: int = 64, seed: int = 7,
                         config: FnsConfig):
    """The shared corpus recipe (selectivity-planted clusters -> α-kNN
    graph -> anchor atlas), built from one config. Returns (ds, index);
    the autotuner and every bench family reuse this so their numbers are
    comparable."""
    ds = make_selectivity_dataset(selectivities, n=n, d=d, n_components=24,
                                  seed=seed)
    graph = build_alpha_knn(ds.vectors, config=config.graph)
    atlas = AnchorAtlas.build(ds, n_clusters=config.atlas.n_clusters,
                              seed=config.atlas.kmeans_seed)
    return ds, FiberIndex(ds.vectors, ds.metadata, graph, atlas)


def engine_over(index: FiberIndex, config: FnsConfig, *,
                vocab_sizes=None) -> Engine:
    """An engine over a built single-device index, as one shard."""
    return Engine(ShardedIndex.from_index(index, config,
                                          vocab_sizes=vocab_sizes), config)


def make_query_pools(ds, selectivities, q_max: int, k: int) -> dict:
    """Per-selectivity query pools with ground truth attached."""
    pools = {}
    for si, s in enumerate(selectivities):
        qs = make_selectivity_queries(ds, si, q_max)
        attach_ground_truth(ds, qs, k=k)
        pools[s] = qs
    return pools


def measure_batch(eng, batch, reps: int) -> dict:
    """Shared measurement protocol for every bench family: one warmup/
    compile call, ``reps`` timed searches, p50/p99/qps/recall/walk stats
    and the dispatch count of the warmup call."""
    q_n = len(batch)
    d0 = eng.dispatches
    ids, stats = eng.search(batch)  # compile at this batch shape
    disp = eng.dispatches - d0
    lat = []
    for _ in range(reps):
        t0 = time.time()
        ids, stats = eng.search(batch)
        lat.append(time.time() - t0)
    lat_ms = np.asarray(lat) * 1e3
    rec = float(np.mean([recall_at_k(np.asarray(i), q.gt_ids)
                         for i, q in zip(ids, batch)]))
    return {
        "qps": q_n * reps / float(np.sum(lat)),
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "recall": rec,
        "mean_walks": float(np.mean(stats["walks"])),
        "mean_hops": float(np.mean(stats["hops"])),
        "dispatches_per_batch": disp,
    }


def device_info() -> dict:
    """The backend every number of a result was measured on: platform,
    device kind and device count (a CPU number is not a chip number)."""
    import jax

    dev = jax.devices()[0]
    return {"backend": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def search_bench(batch_sizes=BATCH_SIZES, selectivities=SELECTIVITIES, *,
                 n: int = 8000, d: int = 64, k: int = 10, reps: int = 20,
                 graph_k: int = 16, seed: int = 7,
                 config: FnsConfig | None = None,
                 key_prefix: str = "") -> dict:
    """Fused single-dispatch engine over the Q x selectivity grid. Returns
    {"qN/selS": {qps, p50_ms, p99_ms, recall, walks, hops, mask_state_bytes,
    dispatches_per_batch}} plus a "config" entry carrying the full knob
    provenance (fingerprint + flattened FnsConfig) next to the run shape.
    ``config`` overrides the k/graph_k kwargs; ``key_prefix`` namespaces
    the row keys (the tuned rows use ``tuned/``)."""
    cfg = config if config is not None else bench_config(k=k,
                                                         graph_k=graph_k)
    k = cfg.walk.k
    ds, index = build_search_fixture(selectivities, n=n, d=d, seed=seed,
                                     config=cfg)
    eng = engine_over(index, cfg)
    n_words = (n + 31) // 32
    out: dict = {}
    if not key_prefix:
        out["config"] = {"n": n, "d": d, "k": k, "reps": reps,
                         "graph_k": cfg.graph.graph_k,
                         **device_info(),
                         "fingerprint": cfg.fingerprint(),
                         "knobs": cfg.flatten()}
    pools = make_query_pools(ds, selectivities, max(batch_sizes), k)
    for q_n in batch_sizes:
        for sel in selectivities:
            row = measure_batch(eng, pools[sel][:q_n], reps)
            row["mask_state_bytes"] = 3 * q_n * n_words * 4
            if key_prefix:
                row["fingerprint"] = cfg.fingerprint()
            out[f"{key_prefix}q{q_n}/sel{sel}"] = row
    return out


def tuned_search_bench(tuned_path: str = TUNED_PATH, batch_sizes=(64,),
                       selectivities=SELECTIVITIES, *, n: int = 8000,
                       d: int = 64, k: int = 10, reps: int = 20,
                       graph_k: int = 16, seed: int = 7) -> dict:
    """Tuned-engine rows (``tuned/qN/selS``): the ``search_bench`` grid
    re-run under the autotuner's chosen walk knobs (``tune/autotune.py``
    artifact at ``tuned_path``). Only ``walk.*`` knobs are taken from the
    artifact — shape-baked knobs stay the fixture's, so the rows differ
    from the untuned ones by runtime-tunable parameters alone and each
    carries the tuned config's fingerprint."""
    with open(tuned_path) as f:
        tuned = json.load(f)
    cfg = bench_config(k=k, graph_k=graph_k,
                       knobs={p: v for p, v in tuned["config"].items()
                              if p.startswith("walk.") and p != "walk.k"})
    return search_bench(batch_sizes, selectivities, n=n, d=d, reps=reps,
                        seed=seed, config=cfg, key_prefix="tuned/")


def or_search_bench(batch_sizes=(64,), or_sels=OR_SELECTIVITIES, *,
                    n: int = 8000, d: int = 64, k: int = 10, reps: int = 20,
                    graph_k: int = 16, seed: int = 7) -> dict:
    """Disjunctive rows: the ``search_bench`` corpus recipe with two extra
    engineered or-pair fields, queried with two-field ``Or`` expressions
    whose union selectivity ≈ each entry of ``or_sels``. Keys are
    ``or2_sel<sel>`` (Q fixed per batch size, default 64). Each row also
    asserts kernel/oracle bitmap parity on its batch — a drifting
    disjunction kernel can't silently report a good number."""
    import jax.numpy as jnp

    from repro.core.batched.bitmap import pack_bits
    from repro.core.batched.engine import _eval_passes

    cfg = bench_config(k=k, graph_k=graph_k)
    ds = add_or_pair_fields(
        make_selectivity_dataset(SELECTIVITIES, n=n, d=d, n_components=24,
                                 seed=seed), sels=or_sels)
    graph = build_alpha_knn(ds.vectors, config=cfg.graph)
    atlas = AnchorAtlas.build(ds, seed=cfg.atlas.kmeans_seed)
    index = FiberIndex(ds.vectors, ds.metadata, graph, atlas)
    eng = engine_over(index, cfg, vocab_sizes=ds.vocab_sizes)
    n_words = (n + 31) // 32
    out: dict = {}
    q_max = max(batch_sizes)
    pools = {}
    for ci, sel in enumerate(or_sels):
        qs = make_or_queries(ds, ci + 1, q_max)
        attach_ground_truth(ds, qs, k=k)
        pools[sel] = qs
    for q_n in batch_sizes:
        for sel in or_sels:
            batch = pools[sel][:q_n]
            # disjunction kernel vs expression-tree oracle, bit-exact
            _, f_t, a_t, b_t = eng._pack_queries(batch)
            got = np.asarray(_eval_passes(eng.shards[0].metadata, f_t,
                                          a_t, b_t))
            want = np.asarray(pack_bits(jnp.asarray(np.stack(
                [q.predicate.mask(ds.metadata, ds.vocab_sizes)
                 for q in batch]))))
            if not np.array_equal(got, want):
                raise AssertionError(
                    f"disjunction kernel/oracle bitmap mismatch at "
                    f"or2_sel{sel}")
            key = (f"or2_sel{sel}" if len(batch_sizes) == 1
                   else f"q{q_n}/or2_sel{sel}")
            row = measure_batch(eng, batch, reps)
            row.update(n_disjuncts=2,
                       clause_table_shape=list(np.asarray(f_t).shape),
                       mask_state_bytes=3 * q_n * n_words * 4)
            out[key] = row
    return out


def range_search_bench(batch_sizes=(64,), range_sels=SELECTIVITIES, *,
                       n: int = 8000, d: int = 64, k: int = 10,
                       reps: int = 20, graph_k: int = 16,
                       seed: int = 7) -> dict:
    """Range-predicate rows (``range_sel<sel>``): the ``search_bench``
    corpus with an extra ~10^6-vocab timestamp field, queried with prefix
    ``Range`` windows of engineered selectivity. These compile to symbolic
    interval clauses — the clause tables stay O(clauses), never O(window
    width) — and each row asserts kernel/oracle bitmap parity on its batch
    and records the bounds-table footprint next to the recall number.
    Each row also re-runs the SAME query vectors against a binary
    indicator field marking exactly the window's rows (the matched
    categorical baseline through the legacy value-set path) and reports
    that recall as ``recall_catbase`` — the interval path must stay
    within 2 points of it."""
    import jax.numpy as jnp

    from repro.core.batched.bitmap import pack_bits
    from repro.core.batched.engine import _eval_passes
    from repro.core.types import FilterPredicate, Query

    cfg = bench_config(k=k, graph_k=graph_k)
    ds = add_timestamp_field(
        make_selectivity_dataset(SELECTIVITIES, n=n, d=d, n_components=24,
                                 seed=seed))
    ds = add_window_indicator_fields(ds, range_sels)
    graph = build_alpha_knn(ds.vectors, config=cfg.graph)
    atlas = AnchorAtlas.build(ds, seed=cfg.atlas.kmeans_seed)
    index = FiberIndex(ds.vectors, ds.metadata, graph, atlas)
    eng = engine_over(index, cfg, vocab_sizes=ds.vocab_sizes)
    n_words = (n + 31) // 32
    out: dict = {}
    q_max = max(batch_sizes)
    pools = {}
    for sel in range_sels:
        qs = make_range_queries(ds, sel, q_max)
        attach_ground_truth(ds, qs, k=k)
        pools[sel] = qs
    for q_n in batch_sizes:
        for sel in range_sels:
            batch = pools[sel][:q_n]
            # interval kernel vs expression-tree oracle, bit-exact
            _, f_t, a_t, b_t = eng._pack_queries(batch)
            got = np.asarray(_eval_passes(eng.shards[0].metadata, f_t,
                                          a_t, b_t))
            want = np.asarray(pack_bits(jnp.asarray(np.stack(
                [q.predicate.mask(ds.metadata, ds.vocab_sizes)
                 for q in batch]))))
            if not np.array_equal(got, want):
                raise AssertionError(
                    f"interval kernel/oracle bitmap mismatch at "
                    f"range_sel{sel}")
            key = (f"range_sel{sel}" if len(batch_sizes) == 1
                   else f"q{q_n}/range_sel{sel}")
            row = measure_batch(eng, batch, reps)
            # matched categorical baseline: same vectors, same mask,
            # filtered through the indicator field's value-set bitmap
            wf = ds.field_names.index(f"win{sel}")
            twin_pred = FilterPredicate.make({wf: [1]})
            twins = [Query(vector=q.vector, predicate=twin_pred,
                           selectivity=q.selectivity) for q in batch]
            attach_ground_truth(ds, twins, k=k)
            cat_row = measure_batch(eng, twins, reps)
            row.update(
                ts_domain=ds.vocab_sizes[ds.field_names.index("ts")],
                recall_catbase=cat_row["recall"],
                bounds_table_bytes=(0 if b_t is None
                                    else int(np.asarray(b_t).nbytes)),
                clause_table_shape=list(np.asarray(f_t).shape),
                mask_state_bytes=3 * q_n * n_words * 4)
            out[key] = row
    return out


def sharded_search_bench(batch_sizes=(64,), selectivities=SELECTIVITIES, *,
                         n: int = 8000, d: int = 64, k: int = 10,
                         reps: int = 20, graph_k: int = 16, seed: int = 7,
                         n_shards: int | None = None) -> dict:
    """Sharded engine rows (DESIGN.md §7): same corpus recipe as
    ``search_bench``, partitioned over the mesh ``data`` axis. Defaults to
    the largest power-of-two shard count the session's devices allow (run
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` on CPU to
    get a real multi-shard row). Keys look like ``sharded4/q64/sel0.1``."""
    import jax

    from repro.core.batched.sharded import build_sharded_index
    from repro.launch.mesh import make_local_mesh

    n_dev = len(jax.devices())
    s = n_shards or min(8, 1 << (n_dev.bit_length() - 1))
    cfg = bench_config(k=k, graph_k=graph_k)
    ds = make_selectivity_dataset(selectivities, n=n, d=d, n_components=24,
                                  seed=seed)
    sidx = build_sharded_index(ds.vectors, ds.metadata, s, config=cfg)
    mesh = make_local_mesh(data=s, model=1)
    eng = Engine(sidx, cfg, mesh)
    m_words = (sidx.rows_per_shard + 31) // 32
    out: dict = {}
    q_max = max(batch_sizes)
    pools = {}
    for si, sel in enumerate(selectivities):
        qs = make_selectivity_queries(ds, si, q_max)
        attach_ground_truth(ds, qs, k=k)
        pools[sel] = qs
    for q_n in batch_sizes:
        for sel in selectivities:
            row = measure_batch(eng, pools[sel][:q_n], reps)
            row.update(n_shards=s,
                       mask_state_bytes_per_shard=3 * q_n * m_words * 4)
            out[f"sharded{s}/q{q_n}/sel{sel}"] = row
    return out


def insert_bench(batch_sizes=(64, 256, 1024), *, n: int = 8000, d: int = 64,
                 k: int = 10, reps: int = 20, graph_k: int = 16,
                 seed: int = 7, q_post: int = 64) -> dict:
    """Dynamic-insert rows (DESIGN.md §9): the ``search_bench`` corpus is
    built on a base prefix with capacity for the full n, then the held-out
    rows are appended through ``Engine.insert_batch`` at each batch
    size — ``insert/b<B>`` rows report rows/sec of the whole append path
    (slab writes + reverse-edge graph repair + incremental atlas + device
    refresh). A final ``post_insert/q64/sel0.1`` row re-measures search QPS
    and recall on the grown index, so ingest-induced recall or latency
    drift shows up next to the static rows it must match.

    Since the maintenance subsystem (DESIGN.md §12) the ingest hot path
    runs with ``maintenance.defer_repair``: the acknowledged batch pays
    slab writes + validity bits + nearest-cluster assignment only, and
    the graph repair the old inline path charged per-insert is drained by
    the background loop — timed separately as ``maintenance_ms`` so both
    halves of the cost stay visible. ``post_insert`` is measured after
    the drain, so its recall covers the fully repaired graph."""
    from repro.serve.maintenance import MaintenanceLoop

    cfg = bench_config(k=k, graph_k=graph_k,
                       knobs={"serve.capacity": n,
                              "maintenance.defer_repair": True})
    ds = make_selectivity_dataset(SELECTIVITIES, n=n, d=d, n_components=24,
                                  seed=seed)
    total_ins = sum(batch_sizes)
    if total_ins >= n:
        raise ValueError(f"insert batches ({total_ins}) exceed corpus {n}")
    base_n = n - total_ins
    graph = build_alpha_knn(ds.vectors[:base_n], config=cfg.graph)
    from repro.core.types import Dataset
    base = Dataset(ds.vectors[:base_n], ds.metadata[:base_n],
                   ds.field_names, ds.vocab_sizes)
    atlas = AnchorAtlas.build(base, seed=cfg.atlas.kmeans_seed)
    index = FiberIndex(base.vectors, base.metadata, graph, atlas)
    eng = engine_over(index, cfg, vocab_sizes=ds.vocab_sizes)
    out: dict = {}
    loop = MaintenanceLoop(eng, cfg.maintenance)
    written = base_n
    for b in batch_sizes:
        before = eng.insert_stats
        t0 = time.time()
        eng.insert_batch(ds.vectors[written:written + b],
                         ds.metadata[written:written + b])
        dt = time.time() - t0
        t1 = time.time()
        loop.run_until_idle()  # the deferred graph repair, off the clock
        mnt = time.time() - t1
        written += b
        st = eng.insert_stats  # counters are cumulative: report the delta
        out[f"insert/b{b}"] = {
            "rows_per_s": b / dt, "batch_ms": dt * 1e3,
            "maintenance_ms": mnt * 1e3,
            "corpus_rows": st["corpus_rows"],
            "reclusters": st["reclusters"] - before["reclusters"],
            "reverse_edge_repairs": (st["reverse_edge_repairs"]
                                     - before["reverse_edge_repairs"])}
    qs = make_selectivity_queries(ds, 1, q_post)
    attach_ground_truth(ds, qs, k=k)
    row = measure_batch(eng, qs, reps)
    row["dynamic_fraction"] = eng.insert_stats["dynamic_fraction"]
    out[f"post_insert/q{q_post}/sel0.1"] = row
    return out


def lifecycle_bench(*, n: int = 8000, d: int = 64, k: int = 10,
                    reps: int = 20, graph_k: int = 16, seed: int = 7,
                    churn_frac: float = 0.5, batch: int = 512,
                    q_post: int = 64) -> dict:
    """Document-lifecycle rows (DESIGN.md §12): the full corpus with 25%
    slab slack is churned — each cycle tombstones ``batch`` random live
    documents and re-inserts the same documents under their original ids
    (so ground truth stays exact), with one budgeted maintenance step per
    cycle, until ``churn_frac`` of the corpus has turned over.

    * ``delete_churn/b<batch>``: rows/sec of the churn loop (each churned
      row = one delete + one re-insert + its amortized maintenance),
      plus how many compactions the maintenance loop ran inside it;
    * ``post_churn/q{q_post}/sel0.1``: search QPS + recall AFTER a final
      forced compaction — the recovered steady state, next to the static
      and ``post_insert`` rows it must match (the corpus is by
      construction identical to the never-churned one)."""
    from repro.core.batched.lifecycle import compact_state
    from repro.serve.maintenance import MaintenanceLoop

    cfg = bench_config(k=k, graph_k=graph_k,
                       knobs={"serve.capacity": n + n // 4,
                              "maintenance.defer_repair": True})
    ds = make_selectivity_dataset(SELECTIVITIES, n=n, d=d, n_components=24,
                                  seed=seed)
    graph = build_alpha_knn(ds.vectors, config=cfg.graph)
    atlas = AnchorAtlas.build(ds, seed=cfg.atlas.kmeans_seed)
    index = FiberIndex(ds.vectors, ds.metadata, graph, atlas)
    eng = engine_over(index, cfg, vocab_sizes=ds.vocab_sizes)
    loop = MaintenanceLoop(eng, cfg.maintenance)
    rng = np.random.default_rng(seed)
    target = int(churn_frac * n)
    churned = 0
    cycles = 0
    t0 = time.time()
    while churned < target:
        dead = rng.choice(n, size=batch, replace=False)
        eng.delete_batch(dead)
        eng.insert_batch(ds.vectors[dead], ds.metadata[dead], gids=dead)
        loop.step()  # one budgeted unit per cycle, the serving cadence
        churned += batch
        cycles += 1
    dt = time.time() - t0
    out: dict = {}
    st = eng.insert_stats
    out[f"delete_churn/b{batch}"] = {
        "rows_per_s": 2 * churned / dt,  # deletes + re-inserts
        "churned_rows": churned, "cycle_ms": dt * 1e3 / cycles,
        "compactions": st["compactions"],
        "maintenance_steps": loop.steps,
        "repair_backlog_rows": st["repair_backlog_rows"]}
    t1 = time.time()
    loop.run_until_idle()
    compact_state(eng.state, cfg.maintenance, force=True)
    eng.refresh_device()
    out[f"delete_churn/b{batch}"]["final_compact_ms"] = \
        (time.time() - t1) * 1e3
    qs = make_selectivity_queries(ds, 1, q_post)
    attach_ground_truth(ds, qs, k=k)
    row = measure_batch(eng, qs, reps)
    row["tombstoned_rows"] = eng.insert_stats["tombstoned_rows"]
    out[f"post_churn/q{q_post}/sel0.1"] = row
    return out


def durability_bench(*, n: int = 8000, d: int = 64, k: int = 10,
                     reps: int = 20, graph_k: int = 16, seed: int = 7,
                     chunk: int = 250, n_chunks: int = 4,
                     q_post: int = 64) -> dict:
    """Crash-consistency rows (DESIGN.md §10): the ``insert_bench`` corpus
    grown through ``serve.ingest`` with a durability root attached —

    * ``durability/journal_append``: rows/sec of the CRC-framed, fsynced
      write-ahead append (the tax every durable ingest pays up front);
    * ``durability/snapshot``: wall-ms + on-disk MB of a full engine-state
      snapshot through the atomic checkpoint format;
    * ``durability/restore``: wall-ms to reconstruct a serving engine from
      that snapshot alone (zero graph/atlas rebuild — this number is the
      point of the whole design: restore cost ~ deserialize, not rebuild);
    * ``durability/recover``: restore + journal-suffix replay through the
      normal insert path, with the replay rate derived from the delta;
    * ``post_recover/q{q_post}/sel0.1``: search QPS + recall on the
      recovered index, next to the ``post_insert`` row it must match.
    """
    import shutil
    import tempfile

    from repro.core.search import SearchParams
    from repro.core.types import Dataset
    from repro.serve.retrieval import RetrievalService

    ds = make_selectivity_dataset(SELECTIVITIES, n=n, d=d, n_components=24,
                                  seed=seed)
    grown = chunk * n_chunks * 2
    base_n = n - grown
    if base_n <= 0:
        raise ValueError(f"durability chunks ({grown}) exceed corpus {n}")
    base = Dataset(ds.vectors[:base_n], ds.metadata[:base_n],
                   ds.field_names, ds.vocab_sizes)
    svc = RetrievalService.build(
        base, config=bench_config(k=k, graph_k=graph_k,
                                  knobs={"serve.capacity": n}),
        params=SearchParams(k=k))
    root = tempfile.mkdtemp(prefix="fns_durability_bench_")
    out: dict = {}
    try:
        svc.enable_durability(root, snapshot_now=False)
        # journaled ingest: the append rate here includes the WAL fsync
        t0 = time.time()
        written = base_n
        for _ in range(n_chunks):
            svc.ingest(ds.vectors[written:written + chunk],
                       ds.metadata[written:written + chunk])
            written += chunk
        dt = time.time() - t0
        out["durability/journal_append"] = {
            "rows_per_s": n_chunks * chunk / dt,
            "journal_bytes": os.path.getsize(os.path.join(root,
                                                          "journal.bin"))}
        t0 = time.time()
        svc.snapshot()
        snap_s = time.time() - t0
        snap_bytes = sum(
            os.path.getsize(os.path.join(dirpath, f))
            for dirpath, _, files in os.walk(os.path.join(root, "snapshots"))
            for f in files)
        out["durability/snapshot"] = {"ms": snap_s * 1e3,
                                      "mb": snap_bytes / 2**20,
                                      "corpus_rows": written}
        # the journal suffix recover() will replay through the insert path
        for _ in range(n_chunks):
            svc.ingest(ds.vectors[written:written + chunk],
                       ds.metadata[written:written + chunk])
            written += chunk
        t0 = time.time()
        RetrievalService.restore(root)
        restore_s = time.time() - t0
        out["durability/restore"] = {"ms": restore_s * 1e3,
                                     "corpus_rows": written - chunk * n_chunks}
        t0 = time.time()
        svc2 = RetrievalService.recover(root)
        recover_s = time.time() - t0
        replay_s = max(recover_s - restore_s, 1e-9)
        out["durability/recover"] = {
            "ms": recover_s * 1e3,
            "replayed_rows": n_chunks * chunk,
            "replay_rows_per_s": n_chunks * chunk / replay_s}
        assert svc2.staleness()["corpus_rows"] == written, (
            svc2.staleness(), written)
        qs = make_selectivity_queries(ds, 1, q_post)
        attach_ground_truth(ds, qs, k=k)
        row = measure_batch(svc2.engine(), qs, reps)
        out[f"post_recover/q{q_post}/sel0.1"] = row
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def slo_bench(*, q_total: int = 1024, n: int = 8000, d: int = 64,
              k: int = 10, graph_k: int = 16, seed: int = 7,
              queue_max_batch: int = 256, n_shards: int = 2,
              q_lanes: int = 4) -> dict:
    """Serving-SLO rows (DESIGN.md §13): ``q_total`` tickets pushed
    through the admission-queue → dispatch → collect pipeline, reporting
    per-ticket sojourn p50/p99 (admit-to-result, the number a latency SLO
    is written against) and end-to-end QPS.

    With ≥ ``n_shards * q_lanes`` devices the SAME sharded index runs
    twice on one 2D mesh — once with query replication forced (the
    one-batch-per-mesh baseline: every device walks all Q) and once
    query-sharded (each lane group walks Q/q_lanes) — so the
    ``speedup_vs_replicated`` field on the lanes row is the tentpole's
    scaling proof: throughput past one-batch-per-mesh on identical
    hardware and an identical index. On smaller sessions (the smoke CI
    job) a single-device pipeline row still exercises the queue,
    bucketing, and overlap machinery."""
    import jax

    from repro.core.search import SearchParams
    from repro.serve.pipeline import ServePipeline
    from repro.serve.retrieval import RetrievalService

    cfg = bench_config(k=k, graph_k=graph_k,
                       knobs={"serve.queue_max_batch": queue_max_batch,
                              "serve.queue_budget_ms": 0.0})
    ds = make_selectivity_dataset(SELECTIVITIES, n=n, d=d, n_components=24,
                                  seed=seed)
    qs = make_selectivity_queries(ds, 1, q_total)
    attach_ground_truth(ds, qs, k=k)
    out: dict = {}

    def run(svc, key, extra):
        pipe = ServePipeline(svc)
        tickets = [pipe.submit(q.vector, q.predicate) for q in qs]
        t0 = time.time()
        while not all(t.done for t in tickets):
            if pipe.pump() == 0 and len(pipe.queue) == 0:
                pipe.drain()
        wall = time.time() - t0
        soj = np.asarray([t.sojourn_ms for t in tickets])
        rec = float(np.mean([recall_at_k(np.asarray(t.ids), q.gt_ids)
                             for t, q in zip(tickets, qs)]))
        out[key] = {"qps": q_total / wall,
                    "p50_ms": float(np.percentile(soj, 50)),
                    "p99_ms": float(np.percentile(soj, 99)),
                    "recall": rec, "batches": pipe.batches,
                    "queue_max_batch": queue_max_batch,
                    "queue_depth": pipe.depth, **extra}
        return out[key]

    if len(jax.devices()) >= n_shards * q_lanes:
        from repro.core.batched.sharded import build_sharded_index
        from repro.launch.mesh import make_serving_mesh

        sidx = build_sharded_index(ds.vectors, ds.metadata, n_shards,
                                   config=cfg)
        mesh = make_serving_mesh(data=n_shards, query=q_lanes)
        prefix = f"serve_slo/q{q_total}/mesh{n_shards}x{q_lanes}"
        cfg_rep = cfg.with_knobs({"mesh.query_parallel": False})
        eng_rep = Engine(sidx, cfg_rep, mesh)
        svc_rep = RetrievalService(None, SearchParams(k=k), mesh=mesh,
                                   config=cfg_rep, _ds=ds, _engine=eng_rep)
        base = run(svc_rep, f"{prefix}/replicated",
                   {"n_shards": n_shards, "q_lanes": 1})
        eng_2d = Engine(sidx, cfg, mesh)
        svc_2d = RetrievalService(None, SearchParams(k=k), mesh=mesh,
                                  config=cfg, _ds=ds, _engine=eng_2d)
        row = run(svc_2d, f"{prefix}/lanes",
                  {"n_shards": n_shards, "q_lanes": eng_2d.q_lanes})
        row["speedup_vs_replicated"] = row["qps"] / base["qps"]
    else:
        svc = RetrievalService.build(ds, config=cfg,
                                     params=SearchParams(k=k))
        run(svc, f"serve_slo/q{q_total}/pipeline1",
            {"n_shards": 1, "q_lanes": 1})
    return out


def write_baseline(results: dict, path: str = OUT_PATH) -> None:
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        json.dump(results, f, indent=1, default=float)


def main(smoke: bool = False) -> dict:
    if smoke:
        results = search_bench(batch_sizes=(2,), selectivities=(0.5,),
                               n=600, d=16, k=5, reps=1, graph_k=8)
        # exercise the shard_map path too (S=1 on a single-device session)
        results.update(sharded_search_bench(
            batch_sizes=(2,), selectivities=(0.5,), n=600, d=16, k=5,
            reps=1, graph_k=8))
        # and the disjunction path: Or-of-two-fields through the DNF
        # tables + in-kernel union, with its built-in bitmap parity gate
        results.update(or_search_bench(
            batch_sizes=(2,), or_sels=(0.3,), n=600, d=16, k=5, reps=1,
            graph_k=8))
        # and the interval path: a Range window over a ~10^6-vocab
        # timestamp field through the symbolic bounds tables, with its
        # built-in kernel/oracle bitmap parity gate
        results.update(range_search_bench(
            batch_sizes=(2,), range_sels=(0.3,), n=600, d=16, k=5, reps=1,
            graph_k=8))
        # and the dynamic-insert path: append through the capacity slab,
        # then search the grown index
        results.update(insert_bench(batch_sizes=(8,), n=600, d=16, k=5,
                                    reps=1, graph_k=8, q_post=2))
        # and the lifecycle path: delete/re-insert churn + compaction,
        # then search the recycled index
        results.update(lifecycle_bench(n=600, d=16, k=5, reps=1,
                                       graph_k=8, churn_frac=0.1,
                                       batch=16, q_post=2))
        # and the durability path: journaled ingest -> snapshot ->
        # restore/recover -> search the recovered index
        results.update(durability_bench(n=600, d=16, k=5, reps=1,
                                        graph_k=8, chunk=8, n_chunks=2,
                                        q_post=2))
        # and the serving pipeline: Q=1024 tickets through the admission
        # queue + double-buffered dispatch/collect, with p50/p99 sojourn
        # SLO numbers (query-sharded vs replicated when devices allow)
        results.update(slo_bench(q_total=1024, n=600, d=16, k=5,
                                 graph_k=8, queue_max_batch=256))
        # and the tuned-config path when the autotuner artifact is
        # committed: same tiny corpus under the tuned walk knobs (the CI
        # bench-regression gate compares these rows to its baseline)
        if os.path.exists(TUNED_PATH):
            results.update(tuned_search_bench(
                batch_sizes=(2,), selectivities=(0.5,), n=600, d=16, k=5,
                reps=1, graph_k=8))
    else:
        results = search_bench()
        # tuned rows directly after the untuned grid: the acceptance bar
        # compares their p50s, so the pair must be measured back-to-back
        # under the same machine state, not at opposite ends of the run
        if os.path.exists(TUNED_PATH):
            results.update(tuned_search_bench())
        results.update(sharded_search_bench())
        results.update(or_search_bench())
        results.update(range_search_bench())
        results.update(insert_bench())
        results.update(lifecycle_bench())
        results.update(durability_bench())
        results.update(slo_bench())
        write_baseline(results)
    return results


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    res = main(smoke="--smoke" in sys.argv)
    for name, r in res.items():
        if name == "config":
            continue
        if name.startswith("insert/"):
            print(f"{name:14s} rows/s={r['rows_per_s']:8.1f} "
                  f"batch={r['batch_ms']:7.1f}ms "
                  f"maint={r['maintenance_ms']:7.1f}ms "
                  f"repairs={r['reverse_edge_repairs']}")
            continue
        if name.startswith("delete_churn/"):
            print(f"{name:14s} rows/s={r['rows_per_s']:8.1f} "
                  f"cycle={r['cycle_ms']:7.1f}ms "
                  f"compactions={r['compactions']} "
                  f"steps={r['maintenance_steps']}")
            continue
        if name.startswith("durability/"):
            kv = " ".join(f"{k}={v:.1f}" if isinstance(v, float)
                          else f"{k}={v}" for k, v in r.items())
            print(f"{name:28s} {kv}")
            continue
        if name.startswith("serve_slo/"):
            extra = (f" speedup={r['speedup_vs_replicated']:.2f}x"
                     if "speedup_vs_replicated" in r else "")
            print(f"{name:32s} qps={r['qps']:8.1f} "
                  f"p50={r['p50_ms']:7.1f}ms p99={r['p99_ms']:7.1f}ms "
                  f"recall={r['recall']:.3f} batches={r['batches']}"
                  + extra)
            continue
        mask_b = r.get("mask_state_bytes",
                       r.get("mask_state_bytes_per_shard", 0))
        print(f"{name:14s} qps={r['qps']:8.1f} p50={r['p50_ms']:7.1f}ms "
              f"p99={r['p99_ms']:7.1f}ms recall={r['recall']:.3f} "
              f"mask={mask_b/1024:.0f}KiB "
              f"dispatch={r['dispatches_per_batch']}")
