"""Full document lifecycle (ISSUE 9 acceptance): deletes, tombstone
compaction, and the background maintenance loop must be invisible to
search quality — after ANY tested interleaving of insert / delete /
compact / maintenance / search, filtered recall@10 over the CURRENTLY
LIVE rows stays within 2 points of tearing the index down and rebuilding
it from scratch over exactly those rows, at selectivities
{0.5, 0.1, 0.02}, on the single-device engine and a 4-shard mesh — and a
service SIGKILLed at any lifecycle/maintenance fault point recovers to
the acknowledged live set with the same recall parity.

Ground truth is gid-addressed: documents survive slot moves
(compaction), so every comparison keys on global ids, never row numbers.
The rebuild engine's row ids are mapped through the live-gid order.
"""
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

import jax

try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic shim
    from _hypothesis_fallback import example, given, settings, strategies as st

from test_durability import _corpus, _labeled_queries, _query
from test_insert import (CONFIG, GRAPH, _build_single_engine, _full_dataset,
                         _recall, _tiny_ds)

from repro import faults
from repro.core import AnchorAtlas, FiberIndex, build_alpha_knn
from repro.core.batched import lifecycle
from repro.core.config import FnsConfig
from repro.core.search import SearchParams
from repro.core.types import Dataset, FilterPredicate, Query
from repro.serve.maintenance import MaintenanceLoop
from repro.serve.retrieval import RetrievalService

from conftest import engine_over, walk_config

MULTI = len(jax.devices()) >= 4
SELS = (0.5, 0.1, 0.02)
BASE_N = 480
CHUNK = 40


# -- gid-addressed ground truth ----------------------------------------------

def _live_view(state):
    """(vectors, metadata, gids) over the LIVE rows of every shard, in
    ascending gid order — the corpus a from-scratch rebuild would see."""
    vs, ms, gs = [], [], []
    for sh in state.shards:
        live = sh.live[: sh.n_valid]
        vs.append(sh.vectors[: sh.n_valid][live])
        ms.append(sh.metadata[: sh.n_valid][live])
        gs.append(sh.global_ids[: sh.n_valid][live])
    v = np.concatenate(vs)
    m = np.concatenate(ms)
    g = np.concatenate(gs).astype(np.int64)
    order = np.argsort(g)
    return v[order], m[order], g[order]


def _gid_gt(lv, lm, lg, q, k, vocab):
    """Exact filtered top-k over the live rows, as global ids."""
    passing = np.nonzero(q.predicate.mask(lm, vocab))[0]
    if passing.size == 0:
        return lg[passing]
    sims = lv[passing] @ q.vector
    return lg[passing[np.argsort(-sims)[:k]]]


def _gid_recalls(labeled, all_ids, lv, lm, lg, vocab, k=10):
    by: dict = {}
    for (label, q), ids in zip(labeled, all_ids):
        gt = _gid_gt(lv, lm, lg, q, k, vocab)
        by.setdefault(label, []).append(_recall(ids, gt))
    return {label: float(np.mean(v)) for label, v in by.items()}


def _live_gids(state) -> set:
    out = set()
    for sh in state.shards:
        live = sh.live[: sh.n_valid]
        out.update(int(g) for g in sh.global_ids[: sh.n_valid][live])
    return out


def _checkpoint(eng, labeled, vocab, rebuild, tol=0.02, tag=""):
    """Search the dynamic engine and a from-scratch rebuild over its live
    rows; per-label recall parity within ``tol``, one dispatch per
    search."""
    queries = [q for _, q in labeled]
    lv, lm, lg = _live_view(eng.state)
    d0 = eng.dispatches
    ids_dyn, _ = eng.search(queries)
    assert eng.dispatches - d0 == 1, \
        f"{tag}: lifecycle op broke the one-dispatch contract"
    rec_dyn = _gid_recalls(labeled, ids_dyn, lv, lm, lg, vocab)
    reb = rebuild(lv, lm)
    ids_reb, _ = reb.search(queries)
    # the rebuild has no lifecycle: its ids are rows into the live view
    ids_reb = [lg[r[r >= 0]] for r in (np.asarray(i) for i in ids_reb)]
    rec_reb = _gid_recalls(labeled, ids_reb, lv, lm, lg, vocab)
    for label in rec_dyn:
        assert rec_dyn[label] >= rec_reb[label] - tol, (
            tag, label, rec_dyn[label], rec_reb[label])
    return rec_dyn


# -- the deterministic lifecycle schedule (single + sharded) -----------------

def _lifecycle_queries(ds):
    """Denser than test_insert's harness (12 conjunctive + 8 OR per
    selectivity): deletes add tombstone-routing variance on BOTH sides of
    the parity comparison, so the per-label recall means need more
    queries to estimate the 2-point bound without sampling noise."""
    from repro.data.synth import make_or_queries, make_selectivity_queries

    out = []
    for code, sel in enumerate(SELS):
        for q in make_selectivity_queries(ds, code, 12):
            out.append((f"sel{sel}", q))
    for code, sel in enumerate((0.1, 0.02)):
        for q in make_or_queries(ds, code + 1, 8):
            out.append((f"or{sel}", q))
    return out


def _run_lifecycle_schedule(make_engine, ds, tol=0.02):
    """insert / delete / checkpoint / compact / checkpoint / re-insert
    (explicit gid reuse) / checkpoint — parity at every search point."""
    vocab = tuple(ds.vocab_sizes)
    labeled = _lifecycle_queries(ds)
    base_n = 750
    eng = make_engine(ds.vectors[:base_n], ds.metadata[:base_n], vocab,
                      capacity=ds.n)

    def rebuild(v, m):
        return make_engine(v, m, vocab, capacity=None)

    eng.insert_batch(ds.vectors[750:875], ds.metadata[750:875])
    rng = np.random.default_rng(5)
    dead = np.sort(rng.choice(875, size=120, replace=False))
    assert eng.delete_batch(dead) == 120
    _checkpoint(eng, labeled, vocab, rebuild, tol, "post-delete")

    rep = lifecycle.compact_state(eng.state, force=True)
    assert rep["reclaimed"] == 120
    eng.refresh_device()
    assert eng.state.tombstones == 0
    _checkpoint(eng, labeled, vocab, rebuild, tol, "post-compaction")

    # recycled slots take re-insertion of 60 deleted docs under their
    # ORIGINAL ids, plus the last 125 fresh rows of the corpus
    back = dead[:60]
    gids = eng.insert_batch(ds.vectors[back], ds.metadata[back], gids=back)
    np.testing.assert_array_equal(np.asarray(gids), back)
    eng.insert_batch(ds.vectors[875:1000], ds.metadata[875:1000])
    _checkpoint(eng, labeled, vocab, rebuild, tol, "post-reinsert")

    stats = eng.insert_stats
    assert stats["deleted_rows"] == 120
    assert stats["compactions"] >= 1
    assert stats["tombstoned_rows"] == 0
    want = (set(range(1000)) - set(dead.tolist())) | set(back.tolist())
    assert _live_gids(eng.state) == want
    return eng


def test_lifecycle_rebuild_parity_single(full_ds):
    """The headline deliverable on the single-device engine: every
    checkpoint (post-delete, post-compaction, post-reinsert) within 2
    recall points of a from-scratch rebuild over the live rows."""
    _run_lifecycle_schedule(_build_single_engine, full_ds)


def test_lifecycle_rebuild_parity_sharded(full_ds):
    """The same schedule through the 4-shard mesh engine."""
    if not MULTI:
        pytest.skip("needs >= 4 devices (multi-device CI job)")
    from repro.core.batched.engine import Engine
    from repro.core.batched.sharded import build_sharded_index
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(data=4, model=1)

    def make(vectors, metadata, vocab, capacity=None):
        sidx = build_sharded_index(vectors, metadata, 4, capacity=capacity,
                                   **GRAPH)
        return Engine(sidx, CONFIG, mesh)

    _run_lifecycle_schedule(make, full_ds)


LIFECYCLE_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src"); sys.path.insert(0, "tests")
    import numpy as np
    from test_insert import CONFIG, GRAPH, _full_dataset
    from test_lifecycle import _run_lifecycle_schedule
    from repro.core.batched.engine import Engine
    from repro.core.batched.sharded import build_sharded_index
    from repro.launch.mesh import make_local_mesh

    ds = _full_dataset()
    mesh = make_local_mesh(data=4, model=1)

    def make(vectors, metadata, vocab, capacity=None):
        sidx = build_sharded_index(vectors, metadata, 4, capacity=capacity,
                                   **GRAPH)
        return Engine(sidx, CONFIG, mesh)

    eng = _run_lifecycle_schedule(make, ds)
    assert eng.insert_stats["deleted_rows"] == 120
    print("sharded-lifecycle-parity ok")
""")


@pytest.mark.slow
def test_sharded_lifecycle_parity_subprocess():
    """The 4-shard lifecycle schedule on 8 virtual CPU devices, regardless
    of the session's real device count."""
    r = subprocess.run([sys.executable, "-c", LIFECYCLE_SHARDED_SCRIPT],
                       capture_output=True, text=True, timeout=420, cwd=".")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sharded-lifecycle-parity ok" in r.stdout


# -- property-based interleavings --------------------------------------------

def _tiny_engine(vectors, metadata, vocab, capacity=None):
    n = vectors.shape[0]
    ds = Dataset(vectors[:n], metadata[:n],
                 [f"f{i}" for i in range(metadata.shape[1])], list(vocab))
    graph = build_alpha_knn(ds.vectors, k=8, r_max=16)
    atlas = AnchorAtlas.build(ds, seed=0)
    index = FiberIndex(ds.vectors, ds.metadata, graph, atlas)
    cfg = walk_config(k=5, beam_width=2).with_knobs(
        {"serve.capacity": capacity, "graph.graph_k": 8})
    return engine_over(index, cfg, vocab_sizes=vocab)


@settings(max_examples=3, deadline=None)
@given(st.lists(st.sampled_from(["insert", "delete", "compact", "maintain",
                                 "search"]),
                min_size=3, max_size=6),
       st.integers(min_value=0, max_value=2**16))
@example(ops=["delete", "delete", "delete"], seed=59)  # once found failing
def test_property_lifecycle_interleavings(ops, seed):
    """Random insert/delete/compact/maintain/search schedules: (a) recall
    parity vs rebuild over live rows at every search point and at the
    end, (b) the engine's live-gid set tracks a host-side model exactly,
    (c) deleted ids are never returned."""
    from repro.data.synth import make_selectivity_queries

    ds = _tiny_ds()
    vocab = tuple(ds.vocab_sizes)
    base_n = 200
    eng = _tiny_engine(ds.vectors[:base_n], ds.metadata[:base_n], vocab,
                       capacity=ds.n)
    # graph_k=8 is thin: relink every compacted row left below graph_k
    # edges. At the default 0.5 the compacted graph stays sparser than a
    # rebuild's and trails it past the bound on some delete schedules.
    # 50 queries per code: at 5, one missed neighbour alone is 0.02.
    eng.cfg = eng.cfg.with_knobs({"maintenance.defer_repair": True,
                                  "maintenance.compact_min_rows": 4,
                                  "maintenance.compact_tombstone_frac": 0.05,
                                  "maintenance.min_degree_frac": 1.0})
    loop = MaintenanceLoop(eng, eng.cfg.maintenance)
    rng = np.random.default_rng(seed)
    labeled = [("sel", q) for code in (0, 1)
               for q in make_selectivity_queries(ds, code, 50)]
    written = base_n
    live = set(range(base_n))

    def check(tag):
        lv, lm, lg = _live_view(eng.state)
        assert set(lg.tolist()) == live, tag
        ids_dyn, _ = eng.search([q for _, q in labeled])
        for row in ids_dyn:
            assert live.issuperset(int(i) for i in np.asarray(row)), \
                f"{tag}: dead or unwritten id returned"
        rec_dyn = _gid_recalls(labeled, ids_dyn, lv, lm, lg, vocab, k=5)
        reb = _tiny_engine(lv, lm, vocab)
        ids_reb, _ = reb.search([q for _, q in labeled])
        ids_reb = [lg[np.asarray(r)] for r in ids_reb]
        rec_reb = _gid_recalls(labeled, ids_reb, lv, lm, lg, vocab, k=5)
        assert rec_dyn["sel"] >= rec_reb["sel"] - 0.02 - 1e-9, (
            tag, rec_dyn, rec_reb)

    for i, op in enumerate(ops):
        if op == "insert" and written + 20 <= ds.n:
            eng.insert_batch(ds.vectors[written:written + 20],
                             ds.metadata[written:written + 20])
            live.update(range(written, written + 20))
            written += 20
        elif op == "delete" and len(live) > 40:
            gone = rng.choice(sorted(live), size=15, replace=False)
            assert eng.delete_batch(gone) == 15
            live.difference_update(int(g) for g in gone)
        elif op == "compact":
            lifecycle.compact_state(eng.state, force=True)
            eng.refresh_device()
            assert eng.state.tombstones == 0
        elif op == "maintain":
            loop.run_until_idle()
            assert eng.state.pending_rows == 0
        elif op == "search":
            check(f"op{i}")
    loop.run_until_idle()
    check("final")


# -- deferred repair: the backlog drain must reproduce the inline result -----

def test_deferred_drain_matches_inline_repair():
    """Two identical engines ingest the same two batches — one inline, one
    deferred-then-drained. Draining the FIFO must reproduce the inline
    adjacency bit-for-bit (patch_adjacency only ever looks at strictly
    earlier rows). Centroids are running means — their refresh sees
    whatever is live at drain time — so search agreement is asserted as
    exact per-query recall, not id-for-id equality."""
    ds = _tiny_ds(seed=9)
    vocab = tuple(ds.vocab_sizes)
    a = _tiny_engine(ds.vectors[:240], ds.metadata[:240], vocab,
                     capacity=ds.n)
    b = _tiny_engine(ds.vectors[:240], ds.metadata[:240], vocab,
                     capacity=ds.n)
    b.cfg = b.cfg.with_knobs({"maintenance.defer_repair": True})
    for lo in (240, 280):
        a.insert_batch(ds.vectors[lo:lo + 40], ds.metadata[lo:lo + 40])
        b.insert_batch(ds.vectors[lo:lo + 40], ds.metadata[lo:lo + 40])
    assert a.state.pending_rows == 0
    assert b.state.pending_rows == 80
    assert b.insert_stats["maintenance_lag"] == 80
    loop = MaintenanceLoop(b, b.cfg.maintenance)
    loop.run_until_idle()
    assert b.state.pending_rows == 0 and loop.repaired_rows == 80
    np.testing.assert_array_equal(a.state.shards[0].adjacency,
                                  b.state.shards[0].adjacency)
    rows = list(range(240, 320, 10))
    queries = [Query(vector=ds.vectors[r],
                     predicate=FilterPredicate.make(
                         {0: [int(ds.metadata[r, 0])]}))
               for r in rows]
    ids_a, _ = a.search(queries)
    ids_b, _ = b.search(queries)
    lv, lm, lg = _live_view(b.state)
    vocab5 = tuple(ds.vocab_sizes)
    for r, x, y, (_, q) in zip(rows, ids_a, ids_b,
                               [("", q) for q in queries]):
        assert r in np.asarray(x).tolist()
        assert r in np.asarray(y).tolist()
        gt = _gid_gt(lv, lm, lg, q, 5, vocab5)
        assert abs(_recall(x, gt) - _recall(y, gt)) <= 0.21  # <= 1 of 5


def test_deferred_rows_findable_before_repair():
    """The hot path stops at slab writes + validity bits + nearest-cluster
    assignment — and that assignment alone must make every fresh row
    findable by its own vector before any graph edge exists."""
    ds = _tiny_ds(seed=4)
    vocab = tuple(ds.vocab_sizes)
    eng = _tiny_engine(ds.vectors[:280], ds.metadata[:280], vocab,
                       capacity=ds.n)
    eng.cfg = eng.cfg.with_knobs({"maintenance.defer_repair": True})
    gids = eng.insert_batch(ds.vectors[280:320], ds.metadata[280:320])
    assert eng.state.pending_rows == 40
    queries = [Query(vector=ds.vectors[r],
                     predicate=FilterPredicate.make(
                         {0: [int(ds.metadata[r, 0])]}))
               for r in range(280, 320)]
    ids, _ = eng.search(queries)
    for g, got in zip(gids, ids):
        assert int(g) in np.asarray(got).tolist()


# -- maintenance loop: scheduling, budgets, priorities -----------------------

def test_maintenance_loop_budget_and_priorities():
    """step() drains the cheapest stale signal first — budgeted backlog
    repair before compaction — and run_until_idle() leaves every
    staleness signal at zero."""
    ds = _tiny_ds(seed=6)
    vocab = tuple(ds.vocab_sizes)
    eng = _tiny_engine(ds.vectors[:260], ds.metadata[:260], vocab,
                       capacity=ds.n)
    eng.cfg = eng.cfg.with_knobs({"maintenance.defer_repair": True,
                                  "maintenance.compact_min_rows": 4,
                                  "maintenance.compact_tombstone_frac": 0.05,
                                  "maintenance.repair_batch_rows": 16})
    loop = MaintenanceLoop(eng, eng.cfg.maintenance)
    assert loop.idle and loop.step() == {"kind": "idle"}
    eng.insert_batch(ds.vectors[260:300], ds.metadata[260:300])
    eng.delete_batch(np.arange(0, 30))
    w = loop.pending_work()
    assert w["repair_backlog_rows"] == 40
    assert w["compactable_shards"] == 1
    out = loop.step(budget_rows=16)  # backlog outranks compaction
    assert out["kind"] == "repair" and out["rows"] == 16
    assert out["remaining"] == 24
    # a published step reports the generation the serve fence checks
    assert out["generation"] == eng.publish_generation
    total = loop.run_until_idle()
    assert loop.idle
    assert loop.repaired_rows == 40
    assert loop.reclaimed_rows == 30
    assert total["steps"] >= 2
    stats = eng.insert_stats
    assert stats["repair_backlog_rows"] == 0
    assert stats["tombstoned_rows"] == 0
    assert stats["maintenance_lag"] == 0
    assert stats["corpus_rows"] == 270


def test_ensure_capacity_compacts_before_growing():
    """An insert past the free tail reclaims tombstoned slots first; only
    a genuinely full slab grows (re-shard to a larger cap, config capacity
    kept in sync). auto_grow=False keeps the old hard error."""
    ds = _tiny_ds(seed=8)
    vocab = tuple(ds.vocab_sizes)
    eng = _tiny_engine(ds.vectors[:300], ds.metadata[:300], vocab,
                       capacity=ds.n)  # free tail: 20
    eng.delete_batch(np.arange(100, 140))
    eng.insert_batch(ds.vectors[300:320], ds.metadata[300:320])  # fits
    stats = eng.insert_stats
    assert stats["slab_growths"] == 0 and stats["compactions"] == 0
    # 30 > free 0, but 40 tombstones are reclaimable: compaction, no growth
    rng = np.random.default_rng(0)
    extra_v = rng.normal(size=(30, ds.vectors.shape[1])).astype(np.float32)
    extra_m = ds.metadata[:30].copy()
    eng.insert_batch(extra_v, extra_m)
    stats = eng.insert_stats
    assert stats["compactions"] == 1 and stats["slab_growths"] == 0
    assert eng.cfg.serve.capacity == 320
    # beyond even the reclaimed room: the slab must grow, not raise
    big_v = rng.normal(size=(40, ds.vectors.shape[1])).astype(np.float32)
    eng.insert_batch(big_v, ds.metadata[:40].copy())
    stats = eng.insert_stats
    assert stats["slab_growths"] == 1
    assert eng.cfg.serve.capacity == eng.state.shards[0].cap > 320
    assert stats["corpus_rows"] == 300 - 40 + 20 + 30 + 40
    # auto_grow off: the PR 5 hard error is still there
    eng.cfg = eng.cfg.with_knobs({"maintenance.auto_grow": False})
    free = eng.state.shards[0].cap - eng.state.shards[0].n_valid
    with pytest.raises(ValueError, match="capacity"):
        eng.insert_batch(
            rng.normal(size=(free + 1, ds.vectors.shape[1]))
            .astype(np.float32), ds.metadata[:free + 1].copy())


# -- service layer: validation, WAL, stats -----------------------------------

def _mk_life_service(ds, n_rows, *, defer=False):
    base = Dataset(ds.vectors[:n_rows], ds.metadata[:n_rows],
                   ds.field_names, list(ds.vocab_sizes))
    cfg = FnsConfig().with_knobs({
        "graph.graph_k": 12, "graph.r_max": 36,
        "walk.k": 10, "walk.max_hops": 80,
        "serve.capacity": ds.n,
        "maintenance.defer_repair": defer,
        "maintenance.compact_min_rows": 8,
        "maintenance.compact_tombstone_frac": 0.05,
        # this service's graph is thin (graph_k=12): relink any compacted
        # row that lost an edge, not just the badly degraded ones
        "maintenance.min_degree_frac": 1.0})
    return RetrievalService.build(base, config=cfg,
                                  params=SearchParams(k=10, max_hops=80))


@pytest.fixture(scope="module")
def full_ds():
    return _full_dataset()


@pytest.fixture(scope="module")
def ds():
    return _corpus()


@pytest.fixture(scope="module")
def labeled(ds):
    return _labeled_queries(ds)


def test_service_ingest_validation_rejects_live_gids(ds):
    """Re-inserting a still-live global id is a loud ValueError naming the
    offending ids — id reuse requires an explicit delete first."""
    svc = _mk_life_service(ds, BASE_N)
    with pytest.raises(ValueError, match=r"still live.*\b7\b|\b7\b.*still live"):
        svc.ingest(ds.vectors[5:10], ds.metadata[5:10],
                   gids=np.arange(5, 10))
    with pytest.raises(ValueError, match="duplicate"):
        svc.ingest(ds.vectors[BASE_N:BASE_N + 2],
                   ds.metadata[BASE_N:BASE_N + 2],
                   gids=np.array([600, 600]))
    with pytest.raises(ValueError, match=r"\b599\b"):
        svc.delete([599])  # never written
    assert svc.delete(np.arange(5, 10)) == 5
    with pytest.raises(ValueError, match=r"\b5\b"):
        svc.delete([5])  # already dead
    # explicit reuse after the delete is the sanctioned path
    svc.ingest(ds.vectors[5:10], ds.metadata[5:10], gids=np.arange(5, 10))
    assert _live_gids(svc.engine().state) == set(
        range(BASE_N))
    # a re-introduced id occurs twice in the slab until compaction (dead
    # old slot + live row): the second delete must resolve to the LIVE
    # occurrence, not report the id missing (regression: locate_gids)
    assert svc.delete([7]) == 1
    svc.ingest(ds.vectors[7:8], ds.metadata[7:8], gids=[7])
    assert svc.delete([7]) == 1
    svc.ingest(ds.vectors[7:8], ds.metadata[7:8], gids=[7])
    assert _live_gids(svc.engine().state) == set(
        range(BASE_N))


def test_service_delete_compact_and_stats(ds, labeled):
    """delete / compact_now on the service: live-set accounting, the
    query_batch maintenance_lag stat, and recall parity over the
    surviving rows."""
    svc = _mk_life_service(ds, BASE_N, defer=True)
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    assert svc.staleness()["repair_backlog_rows"] == CHUNK
    gone = np.arange(0, 480, 12)  # 40 of the base rows
    assert svc.delete(gone) == gone.size
    stl = svc.staleness()
    assert stl["deleted_rows"] == gone.size
    assert stl["tombstoned_rows"] == gone.size
    assert stl["maintenance_lag"] == CHUNK + gone.size
    vecs = np.stack([q.vector for _, q in labeled])
    preds = [q.predicate for _, q in labeled]
    _ids, stats = svc.query_batch(vecs, preds)
    assert stats["maintenance_lag"] == CHUNK + gone.size
    # compact_now drains the shard's backlog before moving rows, so one
    # call clears BOTH signals on a single-shard service
    rep = svc.compact_now()
    assert rep["reclaimed"] == gone.size
    stl = svc.staleness()
    assert stl["tombstoned_rows"] == 0
    assert stl["repair_backlog_rows"] == 0
    assert stl["maintenance_lag"] == 0
    assert stl["corpus_rows"] == BASE_N + CHUNK - gone.size
    # a fresh deferred ingest drains through maintenance_step instead
    svc.ingest(ds.vectors[BASE_N + CHUNK:BASE_N + 2 * CHUNK],
               ds.metadata[BASE_N + CHUNK:BASE_N + 2 * CHUNK])
    out = svc.maintenance_step()
    assert out["kind"] == "repair"
    while svc.maintenance_step()["kind"] != "idle":
        pass
    assert svc.staleness()["repair_backlog_rows"] == 0
    # recall parity over the live rows vs a from-scratch service
    st_live = svc.engine().state
    lv, lm, lg = _live_view(st_live)
    ids = _query(svc, labeled)
    rec = _gid_recalls(labeled, ids, lv, lm, lg, tuple(ds.vocab_sizes))
    ctrl = _mk_life_service(
        Dataset(lv, lm, ds.field_names, list(ds.vocab_sizes)), lv.shape[0])
    ids_c = _query(ctrl, labeled)
    ids_c = [lg[np.asarray(r)] for r in ids_c]
    rec_c = _gid_recalls(labeled, ids_c, lv, lm, lg, tuple(ds.vocab_sizes))
    for label in rec:
        assert rec[label] >= rec_c[label] - 0.02, (label, rec, rec_c)


# -- durability: journal v2 records + format-2 snapshots ---------------------

def test_journal_v2_record_kinds(tmp_path):
    """One journal holding all four record kinds reads back typed and
    ordered; the legacy insert framing is byte-identical to PR 7."""
    from repro.serve.durability import (MAGIC, Journal)

    jp = str(tmp_path / "journal.bin")
    j = Journal(jp)
    vec = np.ones((2, 4), np.float32)
    met = np.zeros((2, 3), np.int32)
    j.append(1, vec, met)
    legacy_len = os.path.getsize(jp)
    raw = open(jp, "rb").read()
    import struct
    assert struct.unpack_from("<I", raw, 0)[0] == MAGIC
    j.append(2, vec, met, gids=np.array([7, 9]))
    j.append_delete(3, np.array([7]))
    j.append_compact(4)
    recs, clean = j.read()
    assert clean == os.path.getsize(jp)
    assert [r.kind for r in recs] == ["insert", "insert", "delete",
                                     "compact"]
    assert [r.seq for r in recs] == [1, 2, 3, 4]
    assert recs[0].gids is None
    np.testing.assert_array_equal(recs[1].gids, [7, 9])
    np.testing.assert_array_equal(recs[2].gids, [7])
    np.testing.assert_array_equal(recs[1].vectors, vec)
    assert recs[2].vectors is None and recs[3].vectors is None
    # a torn tail after the last full record still truncates cleanly
    with open(jp, "ab") as f:
        f.write(b"\x4a")
    recs2, clean2 = j.read()
    assert len(recs2) == 4 and clean2 < os.path.getsize(jp)
    del legacy_len


def test_snapshot_format2_lifecycle_roundtrip(ds, labeled, tmp_path):
    """A snapshot taken mid-lifecycle — tombstones, a deferred-repair
    backlog, a past compaction — restores bit-for-bit: same live set,
    same staleness counters, identical query results."""
    svc = _mk_life_service(ds, BASE_N, defer=True)
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    svc.delete(np.arange(0, 60, 2))
    svc.compact_now()
    svc.ingest(ds.vectors[BASE_N + CHUNK:BASE_N + 2 * CHUNK],
               ds.metadata[BASE_N + CHUNK:BASE_N + 2 * CHUNK])
    svc.delete(np.arange(101, 111))
    svc.enable_durability(str(tmp_path))  # snapshots here
    st0 = svc.staleness()
    assert st0["repair_backlog_rows"] > 0
    assert st0["tombstoned_rows"] == 10
    ids0 = _query(svc, labeled)

    rcv = RetrievalService.recover(str(tmp_path))
    st1 = rcv.staleness()
    for key in st0:  # the lazily-built sequential index is per-process
        if key != "sequential_index_stale_rows":
            assert st1[key] == st0[key], (key, st0, st1)
    assert _live_gids(rcv.engine().state) == \
        _live_gids(svc.engine().state)
    for a, b in zip(ids0, _query(rcv, labeled)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the restored backlog drains to idle, and a journal replay on top of
    # the snapshot applies delete + compact records (WAL round-trip)
    rcv.delete(np.arange(201, 211))
    rcv.compact_now()
    rcv.maintenance_step()
    rcv2 = RetrievalService.recover(str(tmp_path))
    assert rcv2.staleness()["corpus_rows"] == \
        rcv.staleness()["corpus_rows"]
    assert _live_gids(rcv2.engine().state) == \
        _live_gids(rcv.engine().state)


# -- fault injection: SIGKILL at the lifecycle/maintenance points ------------

LIFECYCLE_CRASH_SCRIPT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, "src"); sys.path.insert(0, "tests")
    root, point = sys.argv[1], sys.argv[2]
    import numpy as np
    from test_durability import _corpus
    from test_lifecycle import BASE_N, CHUNK, _mk_life_service
    defer = point.startswith("maintenance.pre")
    ds = _corpus()
    svc = _mk_life_service(ds, BASE_N, defer=defer)
    svc.enable_durability(root)
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    svc.delete(np.arange(100, 120))
    svc.snapshot()
    svc.ingest(ds.vectors[BASE_N + CHUNK:BASE_N + 2 * CHUNK],
               ds.metadata[BASE_N + CHUNK:BASE_N + 2 * CHUNK])
    svc.delete(np.arange(200, 220))
    os.environ["FNS_FAULT"] = point  # read at fire time: SIGKILL self
    if point == "lifecycle.post-tombstone":
        svc.delete(np.arange(300, 320))
    elif point == "maintenance.mid-compact":
        svc.compact_now()
    else:
        svc.maintenance_step()
    print("SURVIVED", flush=True)
    sys.exit(3)
""")

# fault point -> gids the recovered service must serve. Deletes and
# compactions are journaled BEFORE they mutate (same WAL contract as
# ingest), so a kill after the append replays the op; maintenance repair
# is derived state — never journaled, never lost.
_BASE_LIVE = (set(range(BASE_N + 2 * CHUNK))
              - set(range(100, 120)) - set(range(200, 220)))
_LIFECYCLE_SIGKILL_CASES = [
    ("lifecycle.post-tombstone", _BASE_LIVE - set(range(300, 320))),
    ("maintenance.pre-repair", _BASE_LIVE),
    ("maintenance.mid-compact", _BASE_LIVE),
    ("maintenance.pre-publish", _BASE_LIVE),
]


@pytest.mark.slow
@pytest.mark.parametrize("point,expect_live", _LIFECYCLE_SIGKILL_CASES,
                         ids=[c[0] for c in _LIFECYCLE_SIGKILL_CASES])
def test_sigkill_at_lifecycle_points(ds, labeled, point, expect_live):
    """A subprocess SIGKILLs itself at each lifecycle/maintenance fault
    point; recovery must serve exactly the acknowledged live set with
    filtered recall@10 within 2 points of a never-crashed control."""
    root = tempfile.mkdtemp(prefix=f"fns_life_{point.replace('.', '_')}_")
    proc = subprocess.run(
        [sys.executable, "-c", LIFECYCLE_CRASH_SCRIPT, root, point],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == -9, (
        f"expected SIGKILL at {point}, got rc={proc.returncode}\n"
        f"stdout={proc.stdout}\nstderr={proc.stderr}")
    assert "SURVIVED" not in proc.stdout

    svc = RetrievalService.recover(root)
    assert _live_gids(svc.engine().state) == expect_live
    # a second recovery replays to the identical state
    svc2 = RetrievalService.recover(root)
    assert svc2.staleness() == svc.staleness()
    # recovery + a maintenance drain is the steady state queries see
    while svc.maintenance_step()["kind"] != "idle":
        pass
    assert _live_gids(svc.engine().state) == expect_live

    ctrl = _mk_life_service(ds, BASE_N, defer=False)
    ctrl.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
                ds.metadata[BASE_N:BASE_N + CHUNK])
    ctrl.delete(np.arange(100, 120))
    ctrl.ingest(ds.vectors[BASE_N + CHUNK:BASE_N + 2 * CHUNK],
                ds.metadata[BASE_N + CHUNK:BASE_N + 2 * CHUNK])
    ctrl.delete(np.arange(200, 220))
    if point == "lifecycle.post-tombstone":
        ctrl.delete(np.arange(300, 320))
    lv, lm, lg = _live_view(ctrl.engine().state)
    assert set(lg.tolist()) == expect_live
    vocab = tuple(ds.vocab_sizes)
    rec_ctrl = _gid_recalls(labeled, _query(ctrl, labeled), lv, lm, lg,
                            vocab)
    rec_rcv = _gid_recalls(labeled, _query(svc, labeled), lv, lm, lg,
                           vocab)
    for label in rec_ctrl:
        assert rec_rcv[label] >= rec_ctrl[label] - 0.02, (
            label, rec_ctrl, rec_rcv)
    # the recovered service is fully live: delete + compact + re-recover
    svc.delete([0])
    svc.compact_now()
    svc.snapshot()
    svc3 = RetrievalService.recover(root)
    assert len(_live_gids(svc3.engine().state)) == \
        len(expect_live) - 1
