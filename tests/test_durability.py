"""Crash-consistent serving (ISSUE 7 acceptance): a service SIGKILLed at
any named fault point — after slab writes but before the validity flip,
mid-journal-append, mid-snapshot before the atomic rename — must recover
to filtered recall@10 within 2 points of a never-crashed run at
selectivities {0.5, 0.1, 0.02}, with ZERO graph/atlas rebuild on the
recovery path; and a corrupted journal/snapshot byte must be a clean,
loud error, never silently served.

The harness reuses the PR 5 rebuild-parity machinery (brute-force ground
truth per checkpoint, per-selectivity grouped recall) from test_insert.
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
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from test_insert import _grouped_recalls

from repro import faults
from repro.core.search import SearchParams
from repro.core.types import Dataset
from repro.serve.retrieval import RetrievalService

MULTI = len(jax.devices()) >= 4
SELS = (0.5, 0.1, 0.02)
SERVE_PARAMS = SearchParams(k=10, max_hops=80)
GRAPH = dict(graph_k=12, r_max=36)
CHUNK = 40
BASE_N = 480  # + 3 chunks of 40 = the full 600-row corpus


def _corpus():
    from repro.data.synth import make_selectivity_dataset

    return make_selectivity_dataset(SELS, n=600, d=32, n_components=12,
                                    seed=11)


def _labeled_queries(ds):
    from repro.data.synth import make_selectivity_queries

    out = []
    for code, sel in enumerate(SELS):
        for q in make_selectivity_queries(ds, code, 6):
            out.append((f"sel{sel}", q))
    return out


def _mk_service(ds, n_rows, mesh=None):
    base = Dataset(ds.vectors[:n_rows], ds.metadata[:n_rows],
                   ds.field_names, list(ds.vocab_sizes))
    return RetrievalService.build(base, params=SERVE_PARAMS, mesh=mesh,
                                  capacity=ds.n, **GRAPH)


def _query(svc, labeled):
    vecs = np.stack([q.vector for _, q in labeled])
    preds = [q.predicate for _, q in labeled]
    ids, _ = svc.query_batch(vecs, preds)
    return ids


def _recalls(svc, ds, labeled, n_valid):
    return _grouped_recalls(labeled, _query(svc, labeled), ds.vectors,
                            ds.metadata, n_valid, tuple(ds.vocab_sizes))


@pytest.fixture(scope="module")
def ds():
    return _corpus()


@pytest.fixture(scope="module")
def labeled(ds):
    return _labeled_queries(ds)


# -- snapshot / restore ------------------------------------------------------

def test_snapshot_restore_roundtrip_zero_rebuild(ds, labeled, tmp_path,
                                                 monkeypatch):
    """Restore must reproduce the grown service bit-for-bit WITHOUT any
    graph or atlas construction: every build entry point is boobytrapped
    during recovery, so a single kmeans or kNN call fails the test."""
    svc = _mk_service(ds, BASE_N)
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    svc.enable_durability(str(tmp_path))  # snapshots now -> journal empty
    ids0 = _query(svc, labeled)
    st0 = svc.staleness()

    def trap(name):
        def _boom(*a, **k):
            raise AssertionError(f"recovery path called {name}: "
                                 f"snapshot restore must not rebuild")
        return _boom

    import repro.core.atlas as atlas_mod
    import repro.core.batched.insert as insert_mod
    import repro.core.batched.sharded as sharded_mod
    import repro.serve.retrieval as retrieval_mod
    monkeypatch.setattr(retrieval_mod, "build_alpha_knn",
                        trap("build_alpha_knn"))
    monkeypatch.setattr(sharded_mod, "build_shard_graphs",
                        trap("build_shard_graphs"))
    monkeypatch.setattr(atlas_mod, "kmeans", trap("kmeans"))
    monkeypatch.setattr(insert_mod, "kmeans", trap("kmeans"))
    monkeypatch.setattr(atlas_mod.AnchorAtlas, "build",
                        trap("AnchorAtlas.build"))

    svc2 = RetrievalService.recover(str(tmp_path))
    eng2 = svc2.engine()
    d0 = eng2.dispatches
    ids1 = _query(svc2, labeled)
    assert eng2.dispatches - d0 == 1  # one-dispatch contract post-restore
    for a, b in zip(ids0, ids1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st1 = svc2.staleness()
    for key in ("inserted_rows", "corpus_rows", "free_capacity",
                "insert_batches", "reclusters", "reverse_edge_repairs"):
        assert st1[key] == st0[key], (key, st0, st1)
    # the restored service keeps ingesting AND can snapshot again (new
    # inserts MAY legitimately recluster, so the traps come off first)
    monkeypatch.undo()
    svc2.ingest(ds.vectors[BASE_N + CHUNK:BASE_N + 2 * CHUNK],
                ds.metadata[BASE_N + CHUNK:BASE_N + 2 * CHUNK])
    assert svc2.staleness()["inserted_rows"] == 2 * CHUNK


def test_journal_replay_after_restore(ds, labeled, tmp_path):
    """Ingests after the last snapshot live only in the journal; recovery
    must replay them through the normal insert path and reach recall
    parity with the uncrashed service (same rows, same order — the PR 5
    rebuild-parity bound applies transitively)."""
    svc = _mk_service(ds, BASE_N)
    svc.enable_durability(str(tmp_path))
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    svc.snapshot()
    svc.ingest(ds.vectors[BASE_N + CHUNK:BASE_N + 2 * CHUNK],
               ds.metadata[BASE_N + CHUNK:BASE_N + 2 * CHUNK])
    n_valid = BASE_N + 2 * CHUNK
    rec0 = _recalls(svc, ds, labeled, n_valid)

    svc2 = RetrievalService.recover(str(tmp_path))
    assert svc2.staleness()["corpus_rows"] == n_valid
    rec1 = _recalls(svc2, ds, labeled, n_valid)
    for label in rec0:
        assert rec1[label] >= rec0[label] - 0.02, (label, rec0, rec1)
    # replay is idempotent: recovering again changes nothing
    svc3 = RetrievalService.recover(str(tmp_path))
    assert svc3.staleness() == svc2.staleness()
    for a, b in zip(_query(svc2, labeled), _query(svc3, labeled)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restore() (no replay) serves exactly the snapshot rows
    svc4 = RetrievalService.restore(str(tmp_path))
    assert svc4.staleness()["corpus_rows"] == BASE_N + CHUNK
    # ...but still advances sequence numbers past the unreplayed suffix
    assert svc4._next_seq == svc2._next_seq


def test_recover_multi_shard_without_mesh(ds, labeled, tmp_path):
    """A multi-shard snapshot on a 1-device process serves its shards one
    after the other: same per-shard programs, same merge, zero rebuild —
    search results keep the sharded semantics exactly."""
    from repro.core.batched.engine import Engine
    from repro.core.batched.sharded import build_sharded_index
    from repro.core.config import FnsConfig, WalkConfig
    from repro.serve.durability import DurableStore

    sidx = build_sharded_index(ds.vectors[:BASE_N], ds.metadata[:BASE_N], 2,
                               capacity=ds.n, **GRAPH)
    cfg = FnsConfig(walk=WalkConfig(k=10)).with_knobs(
        {"graph.graph_k": GRAPH["graph_k"]})
    eng = Engine(sidx, cfg)
    eng.insert_batch(ds.vectors[BASE_N:BASE_N + CHUNK],
                     ds.metadata[BASE_N:BASE_N + CHUNK])
    qs = [q for _, q in labeled]
    ids0, _ = eng.search(qs)

    store = DurableStore(str(tmp_path))
    store.snapshot(sidx.insert_state)
    state, extra, _ = store.load_latest()
    eng2 = Engine.from_state(state, cfg, vocab_sizes=tuple(ds.vocab_sizes))
    assert eng2.mesh is None and eng2.n_shards == 2
    ids1, _ = eng2.search(qs)
    for a, b in zip(ids0, ids1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and it keeps absorbing inserts
    eng2.insert_batch(ds.vectors[BASE_N + CHUNK:BASE_N + 2 * CHUNK],
                      ds.metadata[BASE_N + CHUNK:BASE_N + 2 * CHUNK])
    assert eng2.insert_stats["inserted_rows"] == 2 * CHUNK


def test_recover_cross_mesh(ds, labeled, tmp_path):
    """4-shard snapshot -> 4-device mesh (reshard-on-load) and 1-shard
    snapshot -> 4-device mesh (empty-slab padding): both serve correctly
    and keep ingesting (multi-device CI job)."""
    if not MULTI:
        pytest.skip("needs >= 4 devices (multi-device CI job)")
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(data=4, model=1)
    svc = _mk_service(ds, BASE_N, mesh=mesh)
    svc.enable_durability(str(tmp_path / "m4"))
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    ids0 = _query(svc, labeled)
    # same-mesh recovery is bit-identical
    svc_m = RetrievalService.recover(str(tmp_path / "m4"), mesh=mesh)
    for a, b in zip(ids0, _query(svc_m, labeled)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # meshless recovery of the same 4-shard snapshot: its shards one after
    # the other, still bit-identical (the mesh==reference parity, applied here)
    svc_r = RetrievalService.recover(str(tmp_path / "m4"))
    for a, b in zip(ids0, _query(svc_r, labeled)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # 1-shard snapshot onto the 4-device mesh: padded empty slabs
    svc1 = _mk_service(ds, BASE_N)
    svc1.enable_durability(str(tmp_path / "m1"))
    svc1.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
                ds.metadata[BASE_N:BASE_N + CHUNK])
    n_valid = BASE_N + CHUNK
    rec0 = _recalls(svc1, ds, labeled, n_valid)
    svc_p = RetrievalService.recover(str(tmp_path / "m1"), mesh=mesh)
    rec1 = _recalls(svc_p, ds, labeled, n_valid)
    for label in rec0:
        assert rec1[label] >= rec0[label] - 0.02, (label, rec0, rec1)
    # the padded shards fill up on later ingests
    gids = svc_p.ingest(ds.vectors[n_valid:n_valid + CHUNK],
                        ds.metadata[n_valid:n_valid + CHUNK])
    assert svc_p.staleness()["corpus_rows"] == n_valid + CHUNK
    assert sorted(int(g) for g in gids) == list(range(n_valid,
                                                      n_valid + CHUNK))


# -- fault injection: in-process crash points --------------------------------

def test_fault_point_post_slab_write(ds, labeled, tmp_path):
    """Crash after the slab write but before the validity flip: the batch
    was journaled first, so recovery replays it — nothing is lost."""
    svc = _mk_service(ds, BASE_N)
    svc.enable_durability(str(tmp_path))
    faults.arm("ingest.post-slab-write")
    try:
        with pytest.raises(faults.InjectedFault):
            svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
                       ds.metadata[BASE_N:BASE_N + CHUNK])
    finally:
        faults.disarm()
    n_valid = BASE_N + CHUNK
    svc2 = RetrievalService.recover(str(tmp_path))
    assert svc2.staleness()["corpus_rows"] == n_valid
    # parity with a never-crashed service over the same rows
    ctrl = _mk_service(ds, BASE_N)
    ctrl.ingest(ds.vectors[BASE_N:n_valid], ds.metadata[BASE_N:n_valid])
    rec_ctrl = _recalls(ctrl, ds, labeled, n_valid)
    rec_rcv = _recalls(svc2, ds, labeled, n_valid)
    for label in rec_ctrl:
        assert rec_rcv[label] >= rec_ctrl[label] - 0.02, (
            label, rec_ctrl, rec_rcv)


def test_fault_point_mid_journal_append(ds, labeled, tmp_path):
    """Crash mid-journal-append: the record is a torn tail — recovery
    drops it (the caller never got an ack), serves the pre-crash state,
    and repairs the journal so the next ingest appends cleanly."""
    svc = _mk_service(ds, BASE_N)
    svc.enable_durability(str(tmp_path))
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    faults.arm("journal.mid-append")
    try:
        with pytest.raises(faults.InjectedFault):
            svc.ingest(ds.vectors[BASE_N + CHUNK:BASE_N + 2 * CHUNK],
                       ds.metadata[BASE_N + CHUNK:BASE_N + 2 * CHUNK])
    finally:
        faults.disarm()
    svc2 = RetrievalService.recover(str(tmp_path))
    assert svc2.staleness()["corpus_rows"] == BASE_N + CHUNK  # torn dropped
    # the repaired journal accepts and replays new appends
    svc2.ingest(ds.vectors[BASE_N + CHUNK:BASE_N + 2 * CHUNK],
                ds.metadata[BASE_N + CHUNK:BASE_N + 2 * CHUNK])
    svc3 = RetrievalService.recover(str(tmp_path))
    assert svc3.staleness()["corpus_rows"] == BASE_N + 2 * CHUNK


def test_fault_point_pre_snapshot_rename(ds, labeled, tmp_path):
    """Crash after the snapshot tmp dir is fully written but before the
    atomic rename: the old snapshot + intact journal still recover the
    full state, and the stale tmp is swept on the next save."""
    svc = _mk_service(ds, BASE_N)
    svc.enable_durability(str(tmp_path))
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    faults.arm("snapshot.pre-rename")
    try:
        with pytest.raises(faults.InjectedFault):
            svc.snapshot()
    finally:
        faults.disarm()
    snap_dir = tmp_path / "snapshots"
    assert any(n.endswith(".tmp") for n in os.listdir(snap_dir))
    n_valid = BASE_N + CHUNK
    svc2 = RetrievalService.recover(str(tmp_path))
    assert svc2.staleness()["corpus_rows"] == n_valid
    svc2.snapshot()  # sweeps the debris, lands the real snapshot
    assert not any(n.endswith(".tmp") for n in os.listdir(snap_dir))
    svc3 = RetrievalService.recover(str(tmp_path))
    assert svc3.staleness()["corpus_rows"] == n_valid


# -- fault injection: real SIGKILL subprocesses ------------------------------

CRASH_SCRIPT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, "src"); sys.path.insert(0, "tests")
    root, point = sys.argv[1], sys.argv[2]
    from test_durability import BASE_N, CHUNK, _corpus, _mk_service
    ds = _corpus()
    svc = _mk_service(ds, BASE_N)
    svc.enable_durability(root)
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    svc.snapshot()
    svc.ingest(ds.vectors[BASE_N + CHUNK:BASE_N + 2 * CHUNK],
               ds.metadata[BASE_N + CHUNK:BASE_N + 2 * CHUNK])
    os.environ["FNS_FAULT"] = point  # read at fire time: SIGKILL self
    if point == "snapshot.pre-rename":
        svc.snapshot()
    else:
        svc.ingest(ds.vectors[BASE_N + 2 * CHUNK:BASE_N + 3 * CHUNK],
                   ds.metadata[BASE_N + 2 * CHUNK:BASE_N + 3 * CHUNK])
    print("SURVIVED", flush=True)
    sys.exit(3)
""")

# fault point -> rows the recovered service must serve. The crashed op's
# batch survives IFF it was fully journaled before the kill: the
# post-slab-write kill happens after the journal fsync (replayed), the
# mid-append kill leaves a torn tail (dropped), and the snapshot kill
# never touches row state at all.
_SIGKILL_CASES = [
    ("ingest.post-slab-write", BASE_N + 3 * CHUNK),
    ("journal.mid-append", BASE_N + 2 * CHUNK),
    ("snapshot.pre-rename", BASE_N + 2 * CHUNK),
]


@pytest.mark.slow
@pytest.mark.parametrize("point,expect_rows", _SIGKILL_CASES,
                         ids=[c[0] for c in _SIGKILL_CASES])
def test_sigkill_recovery_parity(ds, labeled, point, expect_rows):
    """The honest crash test: a subprocess SIGKILLs itself at the fault
    point (no atexit, no flush); this process then recovers from the
    surviving files and must reach filtered recall@10 within 2 points of
    a never-crashed control at selectivities {0.5, 0.1, 0.02}."""
    root = tempfile.mkdtemp(prefix=f"fns_crash_{point.replace('.', '_')}_")
    proc = subprocess.run(
        [sys.executable, "-c", CRASH_SCRIPT, root, point],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == -9, (
        f"expected SIGKILL at {point}, got rc={proc.returncode}\n"
        f"stdout={proc.stdout}\nstderr={proc.stderr}")
    assert "SURVIVED" not in proc.stdout

    svc = RetrievalService.recover(root)
    assert svc.staleness()["corpus_rows"] == expect_rows
    ctrl = _mk_service(ds, BASE_N)
    for lo in range(BASE_N, expect_rows, CHUNK):
        ctrl.ingest(ds.vectors[lo:lo + CHUNK], ds.metadata[lo:lo + CHUNK])
    rec_ctrl = _recalls(ctrl, ds, labeled, expect_rows)
    rec_rcv = _recalls(svc, ds, labeled, expect_rows)
    for label in rec_ctrl:
        assert rec_rcv[label] >= rec_ctrl[label] - 0.02, (
            label, rec_ctrl, rec_rcv)
    # the recovered service is fully live: ingest + snapshot + re-recover
    if expect_rows < len(ds.vectors):
        svc.ingest(ds.vectors[expect_rows:expect_rows + CHUNK],
                   ds.metadata[expect_rows:expect_rows + CHUNK])
        svc.snapshot()
        svc2 = RetrievalService.recover(root)
        assert svc2.staleness()["corpus_rows"] == expect_rows + CHUNK


# -- corruption detection ----------------------------------------------------

def test_journal_corruption_detected(ds, tmp_path):
    """A flipped byte in a COMPLETE journal record is corruption, not a
    torn tail: recovery must refuse loudly, never silently skip."""
    from repro.serve.durability import JournalCorruption

    svc = _mk_service(ds, BASE_N)
    svc.enable_durability(str(tmp_path))
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    jp = tmp_path / "journal.bin"
    raw = bytearray(jp.read_bytes())
    raw[len(raw) // 2] ^= 0xFF  # payload byte of the (only) record
    jp.write_bytes(bytes(raw))
    with pytest.raises(JournalCorruption, match="CRC32"):
        RetrievalService.recover(str(tmp_path))
    # a corrupted header is equally loud (and cannot masquerade as torn)
    raw2 = bytearray(jp.read_bytes())
    raw2[len(raw) // 2] ^= 0xFF  # undo payload flip
    raw2[4] ^= 0x01              # flip a seq byte in the header
    jp.write_bytes(bytes(raw2))
    with pytest.raises(JournalCorruption, match="header"):
        RetrievalService.recover(str(tmp_path))


def test_snapshot_corruption_falls_back(ds, tmp_path):
    """A corrupted newest snapshot falls back to the previous readable
    one; with every snapshot corrupted the error is clean."""
    from repro.checkpoint.ckpt import CheckpointCorruption

    svc = _mk_service(ds, BASE_N)
    svc.enable_durability(str(tmp_path))          # snapshot step 0
    svc.ingest(ds.vectors[BASE_N:BASE_N + CHUNK],
               ds.metadata[BASE_N:BASE_N + CHUNK])
    svc.snapshot()                                # snapshot step 1
    steps = sorted(os.listdir(tmp_path / "snapshots"))
    assert len(steps) == 2

    def corrupt(step_name):
        f = tmp_path / "snapshots" / step_name / "arrays.npz"
        raw = bytearray(f.read_bytes())
        sig = np.ascontiguousarray(
            ds.vectors[:8], np.float32).tobytes()[:16]
        at = raw.find(sig)
        assert at >= 0
        raw[at + 5] ^= 0xFF
        f.write_bytes(bytes(raw))

    corrupt(steps[-1])
    svc2 = RetrievalService.recover(str(tmp_path))
    # fell back to step 0; its journal was truncated by the later
    # snapshot, so only the base rows survive — but NOTHING corrupt served
    assert svc2.staleness()["corpus_rows"] == BASE_N
    corrupt(steps[0])
    with pytest.raises(CheckpointCorruption, match="no readable"):
        RetrievalService.recover(str(tmp_path))


def test_torn_record_boundary_cases(tmp_path):
    """Journal framing unit cases: prefix truncations at every region are
    torn tails (dropped), complete-byte corruption always raises."""
    from repro.serve.durability import Journal, JournalCorruption

    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((6, 8)).astype(np.float32)
    meta = rng.integers(0, 9, (6, 2)).astype(np.int32)
    jp = str(tmp_path / "j.bin")
    j = Journal(jp)
    j.append(1, vecs, meta)
    j.append(2, vecs * 2, meta + 1)
    recs, clean = j.read()
    assert [r[0] for r in recs] == [1, 2]
    np.testing.assert_allclose(recs[1][1], vecs * 2)
    full = open(jp, "rb").read()
    assert clean == len(full)
    rec_len = len(full) // 2
    # truncation anywhere inside the second record -> torn tail, 1 record
    for cut in (3, 20, rec_len - 1):
        with open(jp, "wb") as f:
            f.write(full[:rec_len + cut])
        recs, clean = j.read()
        assert [r[0] for r in recs] == [1] and clean == rec_len
        assert j.repair() == cut
        assert os.path.getsize(jp) == rec_len
        with open(jp, "wb") as f:
            f.write(full)
    # empty + missing files are fine
    open(jp, "wb").close()
    assert j.read() == ([], 0)
    assert Journal(str(tmp_path / "nope.bin")).read() == ([], 0)
    # seq can't be trusted if the header CRC fails
    bad = bytearray(full)
    bad[9] ^= 0xFF
    with open(jp, "wb") as f:
        f.write(bytes(bad))
    with pytest.raises(JournalCorruption):
        j.read()


# -- ingest validation (satellite) -------------------------------------------

def test_ingest_validation_clean_errors(ds, tmp_path):
    """Bad ingest inputs fail up front with clean messages — and BEFORE
    the journal write, so an invalid batch can never poison recovery."""
    svc = _mk_service(ds, BASE_N)
    svc.enable_durability(str(tmp_path))
    good_v, good_m = ds.vectors[BASE_N:BASE_N + 4], ds.metadata[
        BASE_N:BASE_N + 4]
    with pytest.raises(ValueError, match="must be 2-D"):
        svc.ingest(np.zeros((2, 3, 4)), good_m[:2])
    with pytest.raises(ValueError, match="one metadata row per vector"):
        svc.ingest(good_v, good_m[:3])
    with pytest.raises(ValueError, match="fields"):
        svc.ingest(good_v, good_m[:, :-1])
    with pytest.raises(ValueError, match="serves dim"):
        svc.ingest(good_v[:, :-2], good_m)
    with pytest.raises(ValueError, match="declared vocab domain"):
        bad = good_m.copy()
        bad[0, 0] = 10 ** 6
        svc.ingest(good_v, bad)
    # none of the rejects reached the journal or the slabs
    assert os.path.getsize(tmp_path / "journal.bin") == 0
    assert svc.staleness()["inserted_rows"] == 0
    svc.ingest(good_v, good_m)  # the valid batch still lands
    assert svc.staleness()["inserted_rows"] == 4


# -- hypothesis: crash-point x schedule interleavings ------------------------

def _small_fixture():
    from repro.data.synth import (make_selectivity_dataset,
                                  make_selectivity_queries)

    sds = make_selectivity_dataset((0.5, 0.1), n=260, d=16,
                                   n_components=6, seed=3)
    return sds, [("q", q) for q in make_selectivity_queries(sds, 0, 4)]


_SMALL_DS, _SMALL_QS = _small_fixture()


@settings(max_examples=6, deadline=None)
@given(st.lists(st.sampled_from(["ingest", "snapshot", "query"]),
                min_size=2, max_size=5),
       st.sampled_from([p for p in faults.POINTS
                        if p.split(".")[0] in ("ingest", "journal",
                                               "snapshot")] + [None]))
def test_recovery_interleavings(ops, crash):
    """Any schedule of (ingest | snapshot | query) followed by a crash at
    any ingest-path fault point must recover to exactly the acknowledged
    state (the lifecycle/maintenance points are exercised by
    test_lifecycle.py, where the triggering ops exist):
    replay is idempotent (a second recovery is bit-identical) and
    staleness counters survive."""
    ds = _SMALL_DS
    labeled = _SMALL_QS
    root = tempfile.mkdtemp(prefix="fns_hyp_")
    svc = RetrievalService.build(
        Dataset(ds.vectors[:200], ds.metadata[:200], ds.field_names,
                list(ds.vocab_sizes)),
        params=SearchParams(k=5, max_hops=40), capacity=ds.n,
        graph_k=8, r_max=24)
    svc.enable_durability(root)
    written = 200
    acked = 200
    for op in ops:
        if op == "ingest" and written + 10 <= ds.n:
            svc.ingest(ds.vectors[written:written + 10],
                       ds.metadata[written:written + 10])
            written += 10
            acked = written
        elif op == "snapshot":
            svc.snapshot()
        elif op == "query":
            _query(svc, labeled)
    if crash is not None:
        faults.arm(crash)
        try:
            with pytest.raises(faults.InjectedFault):
                if crash == "snapshot.pre-rename":
                    svc.snapshot()
                elif written + 10 <= ds.n:
                    svc.ingest(ds.vectors[written:written + 10],
                               ds.metadata[written:written + 10])
                    acked = written + 10  # unreachable: fault fires first
                else:
                    raise faults.InjectedFault(crash)  # corpus exhausted
        finally:
            faults.disarm()
        if crash == "ingest.post-slab-write" and written + 10 <= ds.n:
            acked = written + 10  # journaled before the slab write: kept
    rcv1 = RetrievalService.recover(root)
    assert rcv1.staleness()["corpus_rows"] == acked
    assert rcv1.staleness()["inserted_rows"] == acked - 200
    rcv2 = RetrievalService.recover(root)  # idempotent replay
    assert rcv2.staleness() == rcv1.staleness()
    for a, b in zip(_query(rcv1, labeled), _query(rcv2, labeled)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
