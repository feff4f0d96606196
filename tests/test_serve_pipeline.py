"""Serving-path pipeline + bugfix regressions (ISSUE 10 satellites):
admission-queue batch forming, bucket/lane rounding, dispatch/collect
overlap, the unit-basis bucket pads, selective stat slicing on both
engine routes, and the publish-generation fence."""
import time

import numpy as np
import pytest

from repro import faults
from repro.core.config import FnsConfig, ServeConfig
from repro.core.search import SearchParams
from repro.core.types import Dataset, FilterPredicate, normalize
from repro.serve.pipeline import AdmissionQueue, ServePipeline
from repro.serve.retrieval import RetrievalService


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _corpus(seed=7, n=400, d=16, fields=4, vocab=5):
    rng = np.random.default_rng(seed)
    vecs = normalize(rng.standard_normal((n, d)))
    meta = rng.integers(0, vocab, (n, fields)).astype(np.int32)
    return rng, Dataset(vecs, meta, [f"f{i}" for i in range(fields)],
                        [vocab] * fields)


_PIPE_KNOBS = {"walk.k": 5, "walk.max_hops": 40, "graph.graph_k": 8,
               "graph.r_max": 24, "serve.queue_max_batch": 4,
               "serve.queue_budget_ms": 0.0}


@pytest.fixture(scope="module")
def pipe_svc():
    _, ds = _corpus()
    svc = RetrievalService.build(
        ds, config=FnsConfig().with_knobs(_PIPE_KNOBS))
    return ds, svc


# -- admission queue / batch former ------------------------------------------

def test_admission_queue_size_and_deadline_triggers():
    """poll() cuts a batch when the bucket fills OR the oldest ticket's
    wait crosses queue_budget_ms — and not a moment before (fake clock)."""
    clk = FakeClock()
    scfg = ServeConfig(queue_max_batch=8, queue_budget_ms=5.0)
    q = AdmissionQueue(scfg, clock=clk)
    for i in range(3):
        q.admit(np.zeros(4, np.float32), FilterPredicate.make({}))
    assert q.poll() is None                      # 3 < 8, wait 0ms
    clk.t += 0.004
    assert q.poll() is None                      # 4ms < 5ms budget
    clk.t += 0.002
    batch = q.poll()                             # 6ms: deadline trips
    assert batch is not None and len(batch) == 3 and len(q) == 0
    for _ in range(10):
        q.admit(np.zeros(4, np.float32), FilterPredicate.make({}))
    batch = q.poll()                             # full bucket, no waiting
    assert len(batch) == 8 and len(q) == 2
    assert q.poll() is None                      # remainder: not due yet
    assert len(q.poll(force=True)) == 2          # drain


def test_bucket_target_rounds_to_lane_multiple():
    """Bucket targets follow query_batch's pow2 rule, rounded UP to a
    multiple of the query-axis size (the 2D-mesh divisibility rule)."""
    scfg = ServeConfig(min_bucket=4)
    assert AdmissionQueue(scfg, q_lanes=1).bucket_target(5) == 8
    assert AdmissionQueue(scfg, q_lanes=4).bucket_target(5) == 8
    assert AdmissionQueue(scfg, q_lanes=3).bucket_target(3) == 6
    assert AdmissionQueue(scfg, q_lanes=8).bucket_target(2) == 8
    assert AdmissionQueue(scfg, q_lanes=4).bucket_target(1) == 4


# -- the double-buffered pipeline --------------------------------------------

def test_pipeline_results_match_query_batch(pipe_svc):
    """Pump-until-drained through the async dispatch/collect path must
    reproduce the synchronous query_batch results exactly, across more
    tickets than one bucket (so >1 batch is in flight)."""
    ds, svc = pipe_svc
    rng = np.random.default_rng(1)
    qs = rng.standard_normal((10, 16)).astype(np.float32)
    preds = [FilterPredicate.make({0: [i % 5]}) for i in range(10)]
    pipe = ServePipeline(svc)
    tickets = [pipe.submit(v, p) for v, p in zip(qs, preds)]
    while not all(t.done for t in tickets):
        if pipe.pump() == 0 and len(pipe.queue) == 0:
            pipe.drain()
    assert pipe.batches >= 2
    ref_ids, _ = svc.query_batch(qs, list(preds))
    for t, ref in zip(tickets, ref_ids):
        assert t.error is None and t.done
        np.testing.assert_array_equal(np.asarray(t.ids), np.asarray(ref))
        assert t.sojourn_ms is not None and t.sojourn_ms >= 0.0


def test_pipeline_overlap_with_injected_latency(pipe_svc):
    """Batch N+1's staging (forming + predicate compile + fenced pack +
    dispatch) happens BEFORE batch N's host sync — with latency injected
    into the pre-dispatch window, batch 0's collect timestamp must land
    after batch 1's (delayed) dispatch, proving N+1 staged while N was in
    flight rather than after its sync."""
    _, svc = pipe_svc
    rng = np.random.default_rng(2)
    pipe = ServePipeline(svc)
    delay = 0.05
    faults.arm("serve.pre-dispatch", lambda: time.sleep(delay))
    try:
        for i in range(8):                       # 2 buckets of 4
            pipe.submit(rng.standard_normal(16).astype(np.float32),
                        FilterPredicate.make({0: [i % 5]}))
        pipe.pump()                              # stage batch 0
        pipe.pump()                              # stage batch 1, sync 0
        pipe.drain()
    finally:
        faults.disarm("serve.pre-dispatch")
    d_t = {no: t for e, no, t in pipe.events if e == "dispatch"}
    c_t = {no: t for e, no, t in pipe.events if e == "collect"}
    assert pipe.batches == 2
    assert d_t[1] < c_t[0], (d_t, c_t)           # staging precedes the sync
    # the sync really waited out batch 1's injected staging latency
    assert c_t[0] - d_t[0] >= delay


def test_pipeline_isolates_bad_ticket(pipe_svc):
    """A ticket whose predicate blows MAX_DISJUNCTS gets its own error +
    empty result; batch-mates answer normally (per-ticket isolation)."""
    from repro.core.predicate import And, In, Or

    ds, svc = pipe_svc
    rng = np.random.default_rng(3)
    bad = And(*[Or(In(f, [0]), In(f, [1])) for f in range(4)])
    preds = [FilterPredicate.make({0: [1]}), bad,
             FilterPredicate.make({1: [2]})]
    pipe = ServePipeline(svc)
    tickets = [pipe.submit(rng.standard_normal(16).astype(np.float32), p)
               for p in preds]
    pipe.pump(force=True)
    pipe.drain()
    assert "max_disjuncts" in tickets[1].error
    assert np.asarray(tickets[1].ids).size == 0
    for t, col in ((tickets[0], 0), (tickets[2], 1)):
        assert t.error is None
        row = np.asarray(t.ids)
        assert row.size > 0
        assert (ds.metadata[row, col] == (1 if col == 0 else 2)).all()


# -- satellite bugfix regressions --------------------------------------------

def test_bucket_pads_are_unit_basis_not_zero(pipe_svc):
    """The bucket-pad dummies must carry a unit-norm vector — a zero
    vector has zero norm, so any cosine normalization of the padded batch
    would turn the pad lane into NaNs — and padding must not perturb the
    real queries' results."""
    ds, svc = pipe_svc
    rng = np.random.default_rng(4)
    eng = svc.engine()
    seen = {}
    orig = eng.search

    def spy(queries, **kw):
        seen["queries"] = queries
        return orig(queries, **kw)

    eng.search = spy
    try:
        vec = rng.standard_normal((1, 16))
        pred = [FilterPredicate.make({0: [2]})]
        ids_b, _ = svc.query_batch(vec, pred)               # pads to 4
    finally:
        eng.search = orig
    padded = seen["queries"]
    assert len(padded) == 4
    for dummy in padded[1:]:
        norm = float(np.linalg.norm(dummy.vector))
        assert norm == pytest.approx(1.0), norm
        # the NaN-propagation regression: normalizing the pad vector
        # must stay finite (zeros wouldn't under x / ||x||)
        assert np.isfinite(
            dummy.vector / np.linalg.norm(dummy.vector)).all()
        # never(): matches no corpus row, so the pad lane stays inert
        assert not dummy.predicate.mask(ds.metadata).any()
    ids_u, _ = svc.query_batch(vec, pred, bucket=False)
    np.testing.assert_array_equal(np.asarray(ids_b[0]),
                                  np.asarray(ids_u[0]))


def test_stats_slice_only_per_query_axes_batched_route(pipe_svc):
    """query_batch must slice only stats with a per-query leading axis:
    per-query walks/hops come back at (q_real,), while the scalar publish
    generation passes through unmangled (the old blanket v[:q_real]
    TypeErrors on it)."""
    _, svc = pipe_svc
    rng = np.random.default_rng(5)
    ids, stats = svc.query_batch(
        rng.standard_normal((3, 16)),
        [FilterPredicate.make({0: [i]}) for i in range(3)])
    assert stats["walks"].shape == (3,)
    assert stats["hops"].shape == (3,)
    assert isinstance(stats["generation"], int)
    assert stats["generation"] == svc.engine().publish_generation


def test_stats_slice_only_per_query_axes_sharded_reference_route():
    """Same contract through the shard-at-a-time program: an engine over
    a 2-shard index without a mesh, attached to the service."""
    from repro.core.batched.engine import Engine
    from repro.core.batched.sharded import build_sharded_index

    _, ds = _corpus(seed=8)
    cfg = FnsConfig().with_knobs(_PIPE_KNOBS)
    sidx = build_sharded_index(ds.vectors, ds.metadata, 2, config=cfg)
    eng = Engine(sidx, cfg)
    svc = RetrievalService(None, SearchParams(k=5, max_hops=40),
                           config=cfg, _ds=ds, _engine=eng)
    rng = np.random.default_rng(9)
    d0 = eng.dispatches
    ids, stats = svc.query_batch(
        rng.standard_normal((3, 16)),
        [FilterPredicate.make({0: [i]}) for i in range(3)])
    assert eng.dispatches - d0 == eng.n_shards  # one per shard
    assert len(ids) == 3
    assert stats["walks"].shape == (3,)
    assert stats["hops"].shape == (3,)
    assert isinstance(stats["generation"], int)


def test_publish_generation_fence_interleaved_delete():
    """A publish landing between predicate pack and dispatch (scripted via
    the serve.pre-dispatch fault hook) must NOT serve stale arrays: the
    fence re-packs, the retry counter ticks, and the just-deleted document
    is absent from the results of the very dispatch it raced."""
    rng, ds = _corpus(seed=10)
    svc = RetrievalService.build(
        ds, config=FnsConfig().with_knobs(
            {**_PIPE_KNOBS, "serve.capacity": 450}))
    vec = rng.standard_normal((1, 16))
    pred = [FilterPredicate.make({0: [3]})]
    ids0, _ = svc.query_batch(vec, pred)
    target = int(np.asarray(ids0[0])[0])
    eng = svc.engine()
    gen0 = eng.publish_generation

    def publish_mid_window():
        faults.disarm("serve.pre-dispatch")  # fire once, not on re-pack
        svc.delete([target])

    faults.arm("serve.pre-dispatch", publish_mid_window)
    try:
        ids1, stats1 = svc.query_batch(vec, pred)
    finally:
        faults.disarm()
    assert eng.fence_retries >= 1
    assert target not in np.asarray(ids1[0]).tolist()
    assert stats1["generation"] == eng.publish_generation > gen0


def test_maintenance_step_reports_publish_generation():
    """MaintenanceLoop.step() reports the generation its publish produced
    — the number an operator correlates with dispatch-fence retries."""
    rng, ds = _corpus(seed=11)
    svc = RetrievalService.build(
        ds, config=FnsConfig().with_knobs(
            {**_PIPE_KNOBS, "serve.capacity": 480,
             "maintenance.defer_repair": True}))
    vecs = normalize(rng.standard_normal((8, 16)))
    meta = rng.integers(0, 5, (8, 4)).astype(np.int32)
    svc.ingest(vecs, meta)
    eng = svc.engine()
    out = svc.maintenance_step()
    assert out["kind"] == "repair"
    assert out["generation"] == eng.publish_generation
    assert svc.maintenance_step()["kind"] == "idle"
