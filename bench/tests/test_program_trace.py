"""The program's spans, device scopes and counters read from a trace: on
hand-made TPU-shaped planes, and in a whole traced run on the CPU."""
from __future__ import annotations

import contextlib
import io
import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import minitree  # noqa: E402
import program_trace as PT  # noqa: E402
import run  # noqa: E402

NEW = ("pack_ms.batch", "unpack_ms.batch", "walk_hop_ms", "anchor_round_ms")


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=n, events=evs)
                          for n, evs in lines])


def _planes(scoped: bool = True, spans: bool = True):
    """One TPU plane running one search program in each of two batches,
    and the host's benchmark and program spans.

    Batch 0, program [100, 400): a filter_eval fusion scoped by its
    ``tf_op`` stat, an anchor_select fusion scoped by the op_name in its
    long name, a while op (unscoped) holding two walk_hop fusions, and an
    op with no metadata at all. Batch 1, program [600, 700): one walk_hop
    fusion, one anchor_select fusion. Unscoped, every op carries its bare
    HLO instruction alone, as on a TPU."""
    def op(name, start, dur, path=None, in_name=False):
        long = f"%{name} = f32[8] fusion(...)"
        if not scoped or path is None:
            return _ev(long, start, dur)
        if in_name:
            return _ev(f'{long}, metadata={{op_name="{path}"}}', start, dur)
        return _ev(long, start, dur, tf_op=path)

    ops = [
        op("fusion.1", 100, 20, "jit(search_batch)/filter_eval/and"),
        op("fusion.2", 120, 30, "jit(search_batch)/while/body/"
           "anchor_select/top_k", in_name=True),
        op("while.3", 150, 200, "jit(search_batch)/while/body/while"),
        op("fusion.4", 160, 80, "jit(search_batch)/while/body/while/body/"
           "walk_hop/add:add"),
        op("fusion.5", 250, 60, "jit(search_batch)/while/body/while/body/"
           "walk_hop/select_n", in_name=True),
        op("copy.6", 360, 40),
        op("fusion.4", 600, 50, "jit(search_batch)/while/body/while/body/"
           "walk_hop/add"),
        op("fusion.2", 650, 50, "jit(search_batch)/while/body/"
           "anchor_select/top_k"),
    ]
    host = [_ev("bench.window", 0, 1000),
            _ev("bench.query_batch", 10, 420),
            _ev("bench.query_batch", 500, 250)]
    if spans:
        host += [
            _ev("fns.form", 20, 30, batch=0, queries=6, lanes=8),
            _ev("fns.pack", 50, 40, batch=0, retries=0),
            _ev("fns.dispatch", 90, 20, batch=0),
            _ev("fns.fetch", 110, 300, batch=0),
            _ev("fns.unpack", 410, 15, batch=0, rounds=2, iters=30),
            _ev("fns.form", 510, 40, batch=1, queries=6, lanes=8),
            _ev("fns.pack", 550, 20, batch=1, retries=1),
            _ev("fns.dispatch", 570, 10, batch=1),
            _ev("fns.fetch", 580, 130, batch=1),
            # the unpack overlaps device work for 0 ns, then 35 ns idle
            _ev("fns.unpack", 710, 35, batch=1, rounds=1, iters=10),
            # a span of a batch that ends after the window: not counted
            _ev("fns.unpack", 990, 20, batch=2, rounds=9, iters=99)]
    return [_plane("/device:TPU:0", [("XLA Modules", [
                       _ev("jit_search_batch(71)", 100, 300),
                       _ev("jit_search_batch(71)", 600, 100)]),
                       ("XLA Ops", ops)]),
            _plane("/host:CPU", [("python", host)])]


# what the program records of the same compiled program (scopes.py)
RECORDED = {"jit_search_batch": {
    "fusion.1": "filter_eval", "fusion.2": "anchor_select",
    "while.3": "other", "fusion.4": "walk_hop", "fusion.5": "walk_hop"},
    "jit_other": {"copy.6": "walk_hop"}}


def test_scope_from_a_tf_op_stat_and_from_a_long_name():
    assert PT.scope_of({"tf_op": "jit(f)/while/body/walk_hop/add:add"},
                       "%fusion.1 = f32[8] fusion()") == "walk_hop"
    assert PT.scope_of({}, '%fusion.2 = f32[8] fusion(), metadata={op_name='
                       '"jit(f)/anchor_select/top_k" source_file="x"}') \
        == "anchor_select"
    # the stat wins over the name; a path with no known scope is other
    assert PT.scope_of({"tf_op": "jit(f)/while/body/lt"},
                       'op_name="jit(f)/walk_hop/x"') == "other"
    assert PT.scope_of({}, "%copy.6 = f32[8] copy()") is None
    # the innermost known scope of a nested path
    assert PT.scope_of({"tf_op": "jit(f)/anchor_select/walk_hop/x"},
                       "") == "walk_hop"


def test_scope_from_the_programs_record_by_module_and_name():
    # the trace's own metadata wins; copy.6 is recorded for another module
    for planes, recorded in ((_planes(), RECORDED),
                             (_planes(scoped=False), RECORDED),
                             (_planes(), {})):
        prog = PT.read_planes(planes, recorded)
        assert [o.scope for o in prog.ops] == [
            "filter_eval", "anchor_select", "other", "walk_hop", "walk_hop",
            None, "walk_hop", "anchor_select"]
    assert PT._module_of([(0, 10, "jit_a(1)"), (20, 30, "jit_b(2)")],
                         25) == "jit_b"
    assert PT._module_of([(0, 10, "jit_a(1)")], 15) == ""


def test_device_time_by_scope_is_own_time_in_the_window():
    prog = PT.read_planes(_planes(), {})
    own = prog.scope_seconds()
    # the while op's own time is what its body ops leave: 200 - 80 - 60
    assert own["walk_hop"] == pytest.approx((80 + 60 + 50) / 1e9)
    assert own["anchor_select"] == pytest.approx((30 + 50) / 1e9)
    assert own["filter_eval"] == pytest.approx(20 / 1e9)
    assert own["other"] == pytest.approx(60 / 1e9)
    assert own[None] == pytest.approx(40 / 1e9)
    bd = prog.breakdown()
    assert bd["device_s_by_scope"]["walk_hop"]["top"][0] == [
        "fusion.4", pytest.approx(130 / 1e9)]


def test_counters_and_host_time_inside_nested_program_spans():
    prog = PT.read_planes(_planes(), {})
    # batch 2's unpack ends after the window
    assert sorted(prog.batches()) == [0, 1]
    assert prog.counter("iters") == 40 and prog.counter("rounds") == 3
    # device busy: [100, 400) and [600, 700). Batch 0's form and pack
    # [20, 90) are idle; batch 1's [510, 570) too. Batch 0's unpack
    # [410, 425) is idle, batch 1's [710, 745) too.
    assert prog.host_ms(("fns.form", "fns.pack")) == pytest.approx(
        (70 + 60) / 2 / 1e6)
    assert prog.host_ms(("fns.unpack",)) == pytest.approx(
        (15 + 35) / 2 / 1e6)
    # idle time goes to the innermost span around it, program spans
    # inside the benchmark's own: pack [50, 90) and [550, 570); unpack
    # [410, 425), [710, 745) and batch 2's [990, 1000)
    idle = prog.breakdown()["idle_s_by_span"]
    assert idle["fns.pack"] == pytest.approx(60 / 1e9)
    assert idle["fns.unpack"] == pytest.approx(60 / 1e9)
    assert idle["bench.query_batch"] == pytest.approx(30 / 1e9)
    assert idle["other"] == pytest.approx((10 + 70 + 240) / 1e9)
    assert sum(idle.values()) == pytest.approx(
        prog.trace.window_s - prog.trace.busy_s)


def _read_all(planes, monkeypatch, recorded=None) -> dict:
    prog = PT.read_planes(planes, recorded or {})
    monkeypatch.setattr(PT, "trace_of", lambda _: prog)
    return {name: run.load_reader(BENCH / "metrics" / f"{name}.py")({})
            for name in NEW}


def test_each_new_reader(monkeypatch):
    for planes, recorded in ((_planes(), None),
                             (_planes(scoped=False), RECORDED)):
        got = _read_all(planes, monkeypatch, recorded)
        assert got["pack_ms.batch"] == pytest.approx(65 / 1e6)
        assert got["unpack_ms.batch"] == pytest.approx(25 / 1e6)
        assert got["walk_hop_ms"] == pytest.approx(190 / 1e6 / 40)
        assert got["anchor_round_ms"] == pytest.approx(80 / 1e6 / 3)


def test_readers_are_silent_without_spans_or_scopes(monkeypatch):
    got = _read_all(_planes(scoped=False), monkeypatch)
    assert got["walk_hop_ms"] is None and got["anchor_round_ms"] is None
    assert got["pack_ms.batch"] is not None
    got = _read_all(_planes(spans=False), monkeypatch)
    assert all(v is None for v in got.values()), got
    monkeypatch.setattr(PT, "trace_of", lambda _: None)
    for name in NEW:
        assert run.load_reader(BENCH / "metrics" / f"{name}.py")({}) is None


def test_a_reader_finds_the_trace_of_its_own_tree(tmp_path):
    assert PT.newest_trace(tmp_path) is None
    reader = tmp_path / "metrics" / "x.py"
    assert PT.trace_of(str(reader)) is None
    old = tmp_path / ".cache/a/trace/plugins/profile/1/h.xplane.pb"
    new = tmp_path / ".cache/b/trace/plugins/profile/2/h.xplane.pb"
    for f in (old, new):
        f.parent.mkdir(parents=True)
        f.write_bytes(b"")
    import os
    os.utime(old, (1, 1))
    assert PT.newest_trace(tmp_path) == new


def test_traced_run_on_the_cpu_prints_what_the_cpu_can_give(tmp_path,
                                                             monkeypatch):
    """A whole ``--trace 1`` run of the cell on a shrunk copy of the tree,
    through the hand tool: the program's spans are read from that copy's
    own trace and its ops' scopes from the program's record. The CPU has
    no peaks, so the roofline readers stay silent there."""
    from repro.core.batched import scopes

    # this run's programs alone: other tests compile other shapes
    monkeypatch.setattr(scopes, "OP_SCOPES", {})
    tree = minitree.make(tmp_path)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = PT.main(["--workload", "hm.batch.selective", "--seed",
                      str(2**31 + 21), "--seconds", "2", "--trace", "1"],
                     require_tpu=False, root=tree)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    cpu_gives = ("host_ms.batch", "idle_share.batch", "lane_occupancy") + NEW
    assert set(m) == set(cpu_gives), m
    assert all(m[name] > 0 for name in cpu_gives), m
    assert m["pack_ms.batch"] + m["unpack_ms.batch"] <= m["host_ms.batch"]
    report = json.loads(next(ln for ln in err.getvalue().splitlines()
                             if ln.startswith("program: "))[9:])
    assert report["batches"] >= 1 and report["iters"] >= report["rounds"]
    assert report["pack_ms"] == pytest.approx(m["pack_ms.batch"])
    assert set(report["device_s_by_scope"]) >= set(PT.SCOPES)
    assert set(report["idle_s_by_span"]) <= {
        "fns.form", "fns.pack", "fns.dispatch", "fns.fetch", "fns.unpack",
        "bench.query_batch", "other"}
    # the harness removed the trace once the readers had read it, and the
    # hand tool left the reduction as it was
    assert PT.newest_trace(tree / "bench") is None
    assert PT.trace_reduce.reduce_dir.__module__ == "trace_reduce"
