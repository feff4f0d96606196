"""``slot_occupancy``: the walk's hops over the lane slots it computed, read
from the ``fns.unpack`` spans of a trace, on hand-made TPU-shaped planes
and in a whole traced run on the CPU."""
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

READER = BENCH / "metrics" / "slot_occupancy.py"
CHIP = {"hbm_bytes_per_s": 819e9}


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _planes(counted: bool = True):
    """A TPU plane running one search program in each of three batches,
    and the host's ``fns.unpack`` spans: batches 0 and 1 end inside the
    window, batch 2 after it. Without ``counted`` the spans carry only
    ``rounds`` and ``iters``, as a program whose walk does not narrow."""
    def unpack(start, batch, hops, slots):
        extra = dict(hops=hops, slots=slots) if counted else {}
        return _ev("fns.unpack", start, 10, batch=batch, rounds=2, iters=30,
                   **extra)

    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Modules", events=[
            _ev("jit_search_batch(7)", s, 90) for s in (100, 300, 900)]),
        types.SimpleNamespace(name="XLA Ops", events=[
            _ev("%fusion.4 = f32[8] fusion(...)", s, 80)
            for s in (105, 305, 905)])])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="python", events=[
            _ev("bench.window", 0, 1000),
            unpack(200, 0, hops=300, slots=400),
            unpack(400, 1, hops=150, slots=600),
            unpack(995, 2, hops=1, slots=1000)])])
    return [device, host]


def _read(monkeypatch, planes, peak=CHIP):
    prog = PT.read_planes(planes, {}) if planes is not None else None
    monkeypatch.setattr(PT, "trace_of", lambda _: prog)
    return run.load_reader(READER)({"peak": peak})


def test_reads_the_hops_over_the_slots_of_the_windows_batches(monkeypatch):
    assert _read(monkeypatch, _planes()) == pytest.approx(
        (300 + 150) / (400 + 600))


@pytest.mark.parametrize("why", ["no_slot_counter", "no_trace", "no_chip"])
def test_silent_where_there_is_nothing_to_read(monkeypatch, why):
    planes = {"no_slot_counter": _planes(counted=False),
              "no_trace": None}.get(why, _planes())
    peak = {} if why == "no_chip" else CHIP
    assert _read(monkeypatch, planes, peak) is None


def test_a_traced_cpu_run_counts_the_slots_of_its_narrowing_walk(
        tmp_path, monkeypatch):
    """A whole ``--trace 1`` run of the cell on a shrunk copy of the tree
    (Q=32 lanes: widths 32, 16 and 8), with the CPU named among the peaks
    so that the reader reads: at most one hop a slot, and fewer hops than
    the full width would have computed."""
    tree = minitree.make(tmp_path)
    import jax

    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"][jax.devices()[0].device_kind] = CHIP
    (tree / "bench" / "peaks.json").write_text(json.dumps(peaks))
    monkeypatch.setattr(run, "BENCH", tree / "bench")
    counted = {}
    trace_of = PT.trace_of

    def spy(reader):
        prog = trace_of(reader)
        counted.update(hops=prog.counter("hops"),
                       slots=prog.counter("slots"),
                       iters=prog.counter("iters"))
        return prog

    monkeypatch.setattr(PT, "trace_of", spy)
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = run.main(["--workload", "hm.batch.selective", "--seed",
                       str(2**31 + 22), "--seconds", "2", "--trace", "1"],
                      require_tpu=False, root=tree)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    got = line["metrics"]["slot_occupancy"]
    assert got["unit"] == "fraction"
    batch = minitree.SHRINK["traffic/batch_selective.json"]["batch"]
    assert 0 < counted["hops"] <= counted["slots"] < batch * counted["iters"]
    assert got["value"] == pytest.approx(counted["hops"] / counted["slots"])
