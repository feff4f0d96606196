"""The search path's own measurement: host spans (``fns.*``) on the
profiler's clock, device scopes in the compiled program's op metadata, and
the program's loop counters (``rounds``, ``iters``, ``slots``)."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.batched import scopes
from repro.core.config import FnsConfig
from repro.core.types import Dataset, FilterPredicate, normalize
from repro.serve.retrieval import RetrievalService

SPANS = ("fns.form", "fns.pack", "fns.dispatch", "fns.fetch", "fns.unpack")
SCOPES = ("filter_eval", "anchor_select", "walk_hop")


@pytest.fixture(scope="module")
def service():
    rng = np.random.default_rng(5)
    n, d = 600, 16
    vecs = normalize(rng.standard_normal((n, d)))
    meta = rng.integers(0, 5, (n, 3)).astype(np.int32)
    ds = Dataset(vecs, meta, ["a", "b", "c"], [5] * 3)
    cfg = FnsConfig().with_knobs({"walk.k": 5, "walk.max_hops": 40,
                                  "graph.graph_k": 8, "graph.r_max": 24})
    svc = RetrievalService.build(ds, config=cfg)
    queries = rng.standard_normal((6, d)).astype(np.float32)
    preds = [FilterPredicate.make({0: [i % 5], 1: [(i + 1) % 5, i % 5]})
             for i in range(6)]
    svc.query_batch(queries, preds)     # compile outside the trace
    return svc, queries, preds


def _events(trace_dir):
    """Every host event of the one trace under ``trace_dir``:
    (name, start ns, end ns, stats)."""
    path = next(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                out.append((ev.name, s, s + int(ev.duration_ns),
                            dict(ev.stats)))
    return out


def test_spans_of_a_batch_share_its_number_and_counters(service, tmp_path):
    svc, queries, preds = service
    eng = svc.engine()
    d0 = eng.dispatches
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("caller.query_batch"):
            ids_q, st_q = svc.query_batch(queries, preds)
        with TraceAnnotation("caller.pipeline"):
            ticket = svc.dispatch_batch(queries, preds)
            ids_p, st_p = svc.collect_batch(ticket)
    finally:
        jax.profiler.stop_trace()
    # one dispatch a batch, as without the spans
    assert eng.dispatches - d0 == 2
    for a, b in zip(ids_q, ids_p):
        np.testing.assert_array_equal(a, b)
    events = _events(tmp_path)
    callers = {n: (s, e) for n, s, e, _ in events if n.startswith("caller.")}
    spans = [ev for ev in events if ev[0].startswith("fns.")]
    batches = sorted({int(st["batch"]) for _, _, _, st in spans})
    assert len(batches) == 2 and batches[1] == batches[0] + 1
    for batch, caller, stats in zip(batches, ("caller.query_batch",
                                              "caller.pipeline"),
                                    (st_q, st_p)):
        mine = [ev for ev in spans if int(ev[3]["batch"]) == batch]
        assert sorted(n for n, _, _, _ in mine) == sorted(SPANS)
        lo, hi = callers[caller]
        assert all(lo <= s and e <= hi for _, s, e, _ in mine)
        by = {n: (s, e, st) for n, s, e, st in mine}
        # in the order a batch crosses them
        order = [by[n][0] for n in SPANS]
        assert order == sorted(order)
        form, unpack = by["fns.form"][2], by["fns.unpack"][2]
        assert (int(form["queries"]), int(form["lanes"])) == (6, 8)
        assert int(by["fns.pack"][2]["retries"]) == 0
        assert (int(unpack["rounds"]), int(unpack["iters"])) == (
            stats["rounds"], stats["iters"])
        assert stats["rounds"] >= 1 and stats["iters"] >= stats["hops"].max()
        # the lanes the walk computed, and the hops of the batch's queries
        assert (int(unpack["slots"]), int(unpack["hops"])) == (
            stats["slots"], int(stats["hops"].sum()))
        assert stats["hops"].sum() <= stats["slots"] <= 8 * stats["iters"]


def test_stats_carry_the_counters_without_a_trace(service):
    svc, queries, preds = service
    ids, stats = svc.query_batch(queries, preds)
    assert len(ids) == 6 and {"rounds", "iters", "slots"} <= set(stats)
    assert isinstance(stats["rounds"], int)
    assert isinstance(stats["slots"], int)


def _op_names(hlo: str) -> list[tuple[str, str]]:
    """(instruction, op_name metadata) of every instruction in HLO text."""
    return re.findall(r"%(\S+) = .*?op_name=\"([^\"]*)\"", hlo)


def test_search_program_carries_the_device_scopes(service):
    svc, queries, preds = service
    eng = svc.engine()
    from repro.core.batched.engine import pack_query_batch
    from repro.core.types import Query

    qs = [Query(vector=v, predicate=p)
          for v, p in zip(normalize(queries), preds)]
    q_vecs, fields, allowed, bounds = pack_query_batch(
        qs, v_cap=eng.datlas.v_cap, vocab_sizes=eng.vocab_sizes)
    hlo = eng._search.lower(eng.datlas, eng.vectors, eng.adjacency,
                            eng.metadata, q_vecs, fields, allowed,
                            valid_bm=eng._valid_bm,
                            bounds=bounds).compile().as_text()
    assert hlo.startswith("HloModule jit_search_batch")
    names = _op_names(hlo)
    for scope in SCOPES:
        assert any(f"/{scope}/" in op for _, op in names), scope
    # the walk's hop runs inside the inner while loop's body, and fusions
    # there carry the scope
    hop_fusions = [inst for inst, op in names if "fusion" in inst and
                   re.search(r"while/body/.*while/body/walk_hop/", op)]
    assert hop_fusions
    # the program's record of the executable gives each op that scope
    record = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(scopes, "OP_SCOPES", record)
        scopes.record(hlo)
    ops = record["jit_search_batch"]
    assert set(ops.values()) == set(SCOPES) | {"other"}
    assert {ops[inst] for inst in hop_fusions} == {"walk_hop"}
    assert SCOPES == scopes.SCOPES


def test_a_new_executable_records_its_scopes(monkeypatch):
    """``dispatch_program`` records the scopes of each executable the call
    compiled, once; two executables that disagree on an op leave it
    unknown."""
    monkeypatch.setattr(scopes, "OP_SCOPES", {})
    texts = []
    record = scopes.record
    monkeypatch.setattr(scopes, "record",
                        lambda text: texts.append(text) or record(text))

    @jax.jit
    def scoped(x):
        with jax.named_scope("walk_hop"):
            return jnp.tanh(x) * 2.0

    for shape in ((8, 128), (8, 128), (16, 128)):
        scopes.dispatch_program(scoped, 0, jnp.ones(shape))
    assert len(texts) == 2
    ops = scopes.OP_SCOPES["jit_scoped"]
    assert "walk_hop" in ops.values()
    for path in ("jit(f)/x", "jit(f)/walk_hop/x"):
        scopes.record("HloModule jit_scoped, x\n  %a.1 = f32[] add(), "
                      f'metadata={{op_name="{path}"}}\n')
    assert ops["a.1"] is None


def test_a_fusion_without_metadata_takes_its_computations_scope():
    """XLA leaves some fusions (a scatter into the visited bitmap, on the
    TPU) without metadata: their scope is the one scope of the computation
    they call, and unknown where that computation has none or several."""
    hlo = """HloModule jit_search_batch, entry_computation_layout={()->()}

%fused_computation.29 (param_0: u32[8]) -> u32[8] {
  %param_0 = u32[8]{0} parameter(0)
  %transpose.1 = u32[8]{0} transpose(%param_0), metadata={op_name="jit(f)/while/body/walk_hop/select_n"}
  ROOT %scatter.5 = u32[8]{0} scatter(%param_0, %transpose.1)
}

%fused_computation.30 (param_0: u32[8]) -> u32[8] {
  ROOT %param_0.1 = u32[8]{0} parameter(0)
}

ENTRY %main.3 (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  %fusion.370 = u32[8]{0} fusion(%p), kind=kCustom, calls=%fused_computation.29
  %fusion.371 = u32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.30
  ROOT %fusion.356 = u32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.29, metadata={op_name="jit(f)/anchor_select/gather"}
}
"""
    got = scopes.op_scopes(hlo)
    assert got["fusion.370"] == "walk_hop"
    assert got["fusion.356"] == "anchor_select"
    assert "fusion.371" not in got and "p" not in got


def test_a_fusion_carries_its_roots_scope():
    """XLA names a fusion's op after its root instruction, so a fusion
    inside a scoped ``while_loop`` body reads that scope."""
    def f(x):
        def body(c):
            i, y = c
            with jax.named_scope("walk_hop"):
                y = jnp.tanh(y * 2.0) + 1.0
            return i + 1, y
        with jax.named_scope("filter_eval"):
            x = jnp.sin(x) * 3.0
        return jax.lax.while_loop(lambda c: c[0] < 3, body, (0, x))[1]

    hlo = jax.jit(f).lower(jnp.ones((8, 128))).compile().as_text()
    fused = [op for inst, op in _op_names(hlo) if "fusion" in inst]
    assert any(op.startswith("jit(f)/while/body/walk_hop/") for op in fused)
    assert any(op.startswith("jit(f)/filter_eval/") for op in fused)
