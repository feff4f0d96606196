"""The trace reduction on a small trace recorded on the CPU and on
hand-made TPU-shaped planes."""
from __future__ import annotations

import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import trace_reduce as T  # noqa: E402

CPU_TRACE = BENCH / "testdata" / "cpu_trace"


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def _tpu_planes():
    """One TPU plane: two search-program runs (each holding a filter_eval
    kernel and a while loop with nested ops) and a small other program;
    and the host's bench spans."""
    ops = [
        # run 1: [100, 400)
        _ev("%filter_eval_batch.1 = s32[8,4] custom-call(...)", 100, 20),
        _ev("%while.3 = (s32[]) while(...)", 130, 260),
        _ev("%fusion.7 = f32[8,16] fusion(%filter_eval_batch.1)", 140, 100),
        _ev("%fusion.8 = f32[8] fusion(...)", 250, 100),
        # another program: [450, 460)
        _ev("%greater.1 = pred[] compare(...)", 450, 10),
        # run 2: [600, 800), overlapping ops counted once in busy time
        _ev("%filter_eval_batch.1 = s32[8,4] custom-call(...)", 600, 50),
        _ev("%fusion.7 = f32[8,16] fusion(...)", 640, 160),
    ]
    modules = [_ev("jit__unknown(123)", 100, 300),
               _ev("jit_greater(9)", 450, 10),
               _ev("jit__unknown(123)", 600, 200)]
    host = [_ev("bench.window", 0, 1000),
            _ev("bench.query_batch", 90, 380),
            _ev("bench.query_batch", 590, 220),
            _ev("PjitFunction(search_batch)", 60, 5)]
    return [
        _plane("/device:TPU:0", [_line("XLA Modules", modules),
                                 _line("XLA Ops", ops),
                                 _line("Async XLA Ops", [
                                     _ev("%copy-start.1 = ...", 0, 900)])]),
        _plane("/host:CPU", [_line("python", host)]),
    ]


def test_busy_share_is_the_union_of_device_intervals():
    r = T.reduce_planes(_tpu_planes())
    assert r.window == (0, 1000)
    # [100, 120) and [130, 390) from run 1's ops, [450, 460), [600, 800):
    # the async copy line is not device work
    assert r.busy_s == pytest.approx((20 + 260 + 10 + 200) / 1e9)
    assert r.window_s == pytest.approx(1000 / 1e9)


def _metric(name):
    return run.load_reader(BENCH / "metrics" / f"{name}.py")


def _renamed_kernel(planes):
    """The same planes with the filter_eval kernel under another name, as
    a program that fused or replaced it would show."""
    for ln in planes[0].lines:
        for ev in ln.events:
            ev.name = ev.name.replace("filter_eval_batch", "custom-call")
    return planes


def test_search_program_is_the_longest_run_in_each_batch_span():
    r = T.reduce_planes(_tpu_planes())
    runs = r.main_runs("bench.query_batch")
    assert [(m.start, m.end) for m in runs] == [(100, 400), (600, 800)]
    assert r.run_seconds(runs) == pytest.approx(500 / 1e9)
    assert r.op_seconds(r.is_filter_eval) == pytest.approx(70 / 1e9)
    # an op that only names the kernel among its operands is not the kernel
    assert not r.is_filter_eval(T.Op("fusion.7", 0, 1))
    # without the kernel's name the same runs are found
    r2 = T.reduce_planes(_renamed_kernel(_tpu_planes()))
    assert r2.main_runs("bench.query_batch") == runs
    assert r2.op_seconds(r2.is_filter_eval) == 0


def test_host_time_of_a_batch_is_its_span_less_device_busy_time():
    counters = {"hops": [np.array([3, 5]), np.array([4, 0])],
                "mean_degree": 2.0, "d": 8, "batches": 2, "n": 64,
                "fields": 2, "lanes": 8}
    peak = {"hbm_bytes_per_s": 1e12}
    for planes in (_tpu_planes(), _renamed_kernel(_tpu_planes())):
        ctx = {"trace": T.reduce_planes(planes), "counters": counters,
               "peak": peak}
        # span [90, 470) holds 290 ns of device work, [590, 810) 200 ns
        assert _metric("host_ms.batch")(ctx) == pytest.approx(
            ((380 - 290) + (220 - 200)) / 2 / 1e6)
    need = 12 * 2.0 * (4 * 8 + 4)
    ctx["trace"] = T.reduce_planes(_tpu_planes())
    assert _metric("walk_roofline")(ctx) == pytest.approx(
        100 * need / 1e12 / ((500 - 70) / 1e9))
    # a program whose kernel is renamed: the walk's time is the whole run
    ctx["trace"] = T.reduce_planes(_renamed_kernel(_tpu_planes()))
    assert _metric("walk_roofline")(ctx) == pytest.approx(
        100 * need / 1e12 / (500 / 1e9))
    assert _metric("filter_eval_roofline")(ctx) is None


def test_own_time_of_nested_ops_and_the_breakdown():
    r = T.reduce_planes(_tpu_planes())
    own = r.self_seconds()
    # while.3 spans [130, 390) and holds fusion.7 [140, 240) and fusion.8
    # [250, 350)
    assert own["while.3"] == pytest.approx(60 / 1e9)
    assert own["fusion.7"] == pytest.approx((100 + 160) / 1e9)
    bd = r.breakdown()
    assert bd["device_ops"][0][0] == "fusion.7"
    assert len(bd["device_ops"]) <= 10


def test_idle_gaps_go_to_the_host_span_around_them():
    r = T.reduce_planes(_tpu_planes())
    gaps = dict((n, s) for n, s in r.breakdown()["idle_gaps"])
    # by each gap's midpoint: [120, 130) and [390, 450) lie in the first
    # batch span [90, 470); [0, 100), [460, 600) and [800, 1000) in none
    assert gaps["bench.query_batch"] == pytest.approx((10 + 60) / 1e9)
    assert gaps["other"] == pytest.approx((100 + 140 + 200) / 1e9)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)


def test_recorded_cpu_trace():
    r = T.reduce_dir(CPU_TRACE)
    assert [n for n, _, _ in r.host_spans].count("bench.query_batch") == 3
    lo, hi = r.window
    assert hi > lo and r.window == r.spans("bench.window")[0]
    # three runs of one program, each a dot, a tanh and two reductions
    assert len(r.modules) == 3
    assert {o.name for o in r.ops} >= {"dot_general.1", "wrapped_tanh"}
    assert 0 < r.busy_s < r.window_s
    assert r.breakdown()["device_ops"][0][0] == "dot_general.1"
    gaps = r.idle_gaps()
    assert sum(s for _, s in gaps) == pytest.approx(r.window_s - r.busy_s)
    # one program run in each batch span, found without its name
    runs = r.main_runs("bench.query_batch")
    assert len(runs) == 3 and len({m.name for m in runs}) == 1
    assert r.op_seconds(r.is_filter_eval) == 0


def test_no_trace_file_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        T.find_trace(tmp_path)
