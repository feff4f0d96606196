"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes, their lines, and events with
a start and a duration in nanoseconds on one clock.

* Device work: on a TPU, the events of each ``/device:TPU:<i>`` plane's
  ``XLA Ops`` line (one per operation run, named by the whole HLO
  instruction, ``%name = ...``, nested: a ``while`` op holds its body's
  ops) and ``XLA Modules`` line (one per program run). A CPU trace has no
  device plane: there the operations are the host events that carry an
  ``hlo_op`` stat, and a program run spans its module's operations.
* The program a call runs is found by where and how long it runs, not by
  its name or the kernels in it: the longest program run that starts
  inside each of the call's host spans (``main_runs``). A
  ``query_batch`` call runs one search program and a few small ones that
  copy results to the host.
* The window: the benchmark's own ``bench.window`` span. Busy time is the
  union of the operations' intervals inside the window, averaged over the
  devices; idle is the rest of the window.
* Host spans: the benchmark's ``bench.*`` annotations around the calls into
  the program that a traffic generator makes (``bench.query_batch``). Each
  idle gap of the device is attributed to the span that covers its
  midpoint, else to ``other``.
"""
from __future__ import annotations

import bisect
import dataclasses
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Op:
    name: str
    start: int          # ns
    end: int            # ns
    module: str = ""
    device: int = 0


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


@dataclasses.dataclass
class Reduced:
    """A trace reduced to device operations, program runs and host spans
    over one window (all times in ns on the trace's clock)."""

    ops: list[Op]
    modules: list[Op]
    host_spans: list[tuple[str, int, int]]
    window: tuple[int, int]
    devices: int = 1

    @staticmethod
    def is_filter_eval(op: Op) -> bool:
        """The Pallas kernel of ``kernels/filter_eval.py``, which XLA
        names after the kernel's function."""
        return op.name.startswith("filter_eval")

    def main_runs(self, span: str) -> list[Op]:
        """For each host span named ``span`` and each device, the longest
        program run that starts inside the span."""
        out = []
        for lo, hi in self.spans(span):
            inside = [m for m in self.modules if lo <= m.start < hi]
            for d in sorted({m.device for m in inside}):
                out.append(max((m for m in inside if m.device == d),
                               key=lambda m: m.end - m.start))
        return out

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy_intervals(self, device: int, lo: int | None = None,
                        hi: int | None = None):
        return _union(_clip([(o.start, o.end) for o in self.ops
                             if o.device == device],
                            self.window[0] if lo is None else lo,
                            self.window[1] if hi is None else hi))

    def busy_seconds(self, lo: int | None = None,
                     hi: int | None = None) -> float:
        """Seconds in [lo, hi) (default: the window) in which an operation
        ran, averaged over the devices."""
        total = sum(_length(self._busy_intervals(d, lo, hi))
                    for d in range(self.devices))
        return total / self.devices / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in the window in which an operation ran, averaged over
        the devices."""
        return self.busy_seconds()

    def op_seconds(self, match) -> float:
        """Device seconds of the operations ``match(op)`` selects, inside
        the window, summed over devices."""
        return _length_sum([(o.start, o.end) for o in self.ops if match(o)],
                           self.window) / 1e9

    def run_seconds(self, runs: list[Op]) -> float:
        """Device seconds of the program runs ``runs`` inside the window,
        summed over devices."""
        return _length_sum([(m.start, m.end) for m in runs],
                           self.window) / 1e9

    def spans(self, name: str) -> list[tuple[int, int]]:
        return [(s, e) for n, s, e in self.host_spans if n == name]

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Each gap of device 0 inside the window with the host span it
        fell in: (span name, seconds). The benchmark's spans come from one
        thread and do not overlap, so the span that covers a gap's
        midpoint is the last one to start before it."""
        busy = self._busy_intervals(0)
        lo, hi = self.window
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        inner = sorted((s, e, n) for n, s, e in self.host_spans
                       if n != WINDOW_SPAN)
        starts = [s for s, _, _ in inner]
        out = []
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) // 2
            i = bisect.bisect_right(starts, mid) - 1
            name = inner[i][2] if i >= 0 and inner[i][1] > mid else "other"
            out.append((name, (e - s) / 1e9))
        return out

    def self_seconds(self) -> dict[str, float]:
        """Each operation's own device time inside the window (its length
        less that of the operations nested in it), summed by name."""
        out: dict[str, float] = {}
        for d in range(self.devices):
            evs = sorted(((max(o.start, self.window[0]),
                           min(o.end, self.window[1]), o.name)
                          for o in self.ops if o.device == d),
                         key=lambda t: (t[0], -t[1]))
            stack: list[list] = []      # [end, name, own ns]

            def close(item):
                out[item[1]] = out.get(item[1], 0.0) + item[2] / 1e9

            for s, e, name in evs:
                if e <= s:
                    continue
                while stack and stack[-1][0] <= s:
                    close(stack.pop())
                if stack:
                    stack[-1][2] -= min(e, stack[-1][0]) - s
                stack.append([e, name, e - s])
            while stack:
                close(stack.pop())
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (own time, summed by
        name), and the idle time by what the host was doing, largest
        first."""
        ops = self.self_seconds()
        gaps: dict[str, float] = {}
        for name, sec in self.idle_gaps():
            gaps[name] = gaps.get(name, 0.0) + sec
        return {"device_ops": sorted(([k, v] for k, v in ops.items()),
                                     key=lambda kv: -kv[1])[:top],
                "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                                    key=lambda kv: -kv[1])[:top]}


def _length_sum(intervals, window) -> int:
    """Summed length of ``intervals`` clipped to ``window``, overlaps
    counted once per interval (durations, not a union)."""
    lo, hi = window
    return sum(min(e, hi) - max(s, lo) for s, e in intervals
               if e > lo and s < hi)


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def reduce_planes(planes) -> Reduced:
    """Reduce planes shaped as ``ProfileData``'s (``name``, ``lines`` of
    ``name``/``events``, events with ``name``, ``start_ns``,
    ``duration_ns``, ``stats``)."""
    ops, modules, spans = [], [], []
    planes = list(planes)
    devices = sorted(p.name for p in planes
                     if p.name.startswith("/device:") and any(
                         ln.name == "XLA Ops" for ln in p.lines))
    for plane in planes:
        dev = devices.index(plane.name) if plane.name in devices else None
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if dev is not None:
                    if line.name == "XLA Ops":
                        ops.append(Op(op_name(ev.name), s, e, device=dev))
                    elif line.name == "XLA Modules":
                        modules.append(Op(ev.name, s, e, device=dev))
                    continue
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, s, e))
                elif not devices and e > s:
                    st = _stats(ev)
                    if "hlo_op" in st:
                        ops.append(Op(str(st["hlo_op"]), s, e,
                                      module=str(st.get("hlo_module", ""))))
    if not devices:
        modules = _cpu_runs(ops)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        window = windows[0]
    elif ops:
        window = (min(o.start for o in ops), max(o.end for o in ops))
    else:
        window = (0, 0)
    return Reduced(ops, modules, spans, window, max(1, len(devices)))


CPU_RUN_GAP_NS = 100_000


def _cpu_runs(ops: list[Op]) -> list[Op]:
    """A CPU trace's program runs: consecutive operations of one module
    less than ``CPU_RUN_GAP_NS`` apart (the host thunks of one run follow
    each other within microseconds)."""
    runs: list[Op] = []
    for o in sorted(ops, key=lambda o: o.start):
        last = runs[-1] if runs else None
        if (last and last.name == o.module
                and o.start <= last.end + CPU_RUN_GAP_NS):
            last.end = max(last.end, o.end)
        else:
            runs.append(Op(o.module, o.start, o.end))
    return runs


def find_trace(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir: Path) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(find_trace(trace_dir)))
                         .planes)
