"""The program's own measurement in a traced run: its host spans and the
device scope of each operation.

The search path annotates itself (``src/repro``): host spans ``fns.form``,
``fns.pack``, ``fns.dispatch``, ``fns.fetch`` and ``fns.unpack``, each with
the service's ``batch`` number (``fns.unpack`` also with the program's
``rounds`` and ``iters``), and device scopes ``filter_eval``,
``anchor_select`` and ``walk_hop`` in the compiled program's op metadata.
This module reads them from the same ``.xplane.pb`` as ``trace_reduce``:

* A reader passes its own bench directory (``trace_of``); the trace is the
  newest file under ``<bench>/.cache/*/trace/``, which the harness removes
  only after the readers have run.
* An operation's scope comes from its ``tf_op`` stat, else from the
  ``op_name="..."`` inside its long HLO name (the innermost of the known
  scopes in that path, or ``other``), else from the program's own record
  of its compiled programs (``recorded_scopes``), looked up by the op's
  module and name: a TPU trace carries neither of the first two. Else
  it is unknown (None).
* Device time of a scope is the operations' own time (less the time of the
  operations nested in them: a ``while`` holds its body's ops) inside the
  benchmark's ``bench.window`` span, summed over devices.

A program without the spans or the scopes (an older one) reads as None in
every reader here, and nothing raises.

    python3 bench/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> --trace 1

runs the cell as ``run.py`` does and prints, on standard error, device
time by scope with each scope's top operations and idle time by the
innermost host span around it.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import json
import os
import re
import sys
from pathlib import Path

import trace_reduce

SPAN_PREFIX = "fns."
SCOPES = ("filter_eval", "anchor_select", "walk_hop")
OTHER = "other"
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass
class Span:
    name: str
    start: int          # ns
    end: int            # ns
    args: dict


@dataclasses.dataclass
class ScopedOp:
    name: str
    start: int          # ns
    end: int            # ns
    scope: str | None
    device: int = 0


def recorded_scopes() -> dict:
    """The program's own record of its compiled search programs' op
    scopes (``repro.core.batched.scopes.OP_SCOPES``: HLO module name ->
    op name -> scope), filled in this process as each program compiled;
    empty for a program that keeps none."""
    try:
        from repro.core.batched.scopes import OP_SCOPES
    except ImportError:
        return {}
    return OP_SCOPES


def scope_of(stats: dict, long_name: str,
             recorded: str | None = None) -> str | None:
    """The device scope of one operation: from its ``tf_op`` stat, else
    from the ``op_name`` metadata in its long HLO name (the innermost
    known scope in that path, ``other`` for a path with none), else the
    program's ``recorded`` scope for the op, which may be None."""
    path = stats.get("tf_op")
    if not path:
        m = _OP_NAME.search(long_name)
        path = m.group(1) if m else None
    if not path:
        return recorded
    for part in reversed(str(path).split("/")):
        if part in SCOPES:
            return part
    return OTHER


def _module_of(runs: list[tuple[int, int, str]], start: int) -> str:
    """The module (``jit_x`` of an ``XLA Modules`` event ``jit_x(123)``)
    whose run holds ``start``; runs sorted by start."""
    i = bisect.bisect_right(runs, (start, float("inf"), "")) - 1
    if i >= 0 and start < runs[i][1]:
        return runs[i][2].split("(", 1)[0]
    return ""


@dataclasses.dataclass
class Program:
    """The program's host spans and scoped device operations, over the
    window and busy time of ``trace_reduce``'s reduction."""

    spans: list[Span]
    ops: list[ScopedOp]
    trace: trace_reduce.Reduced

    def batches(self) -> dict[int, dict[str, list[Span]]]:
        """Each batch number whose ``fns.unpack`` ends inside the window,
        with its program spans by name."""
        lo, hi = self.trace.window
        out: dict[int, dict[str, list[Span]]] = {}
        for s in self.spans:
            if "batch" in s.args:
                out.setdefault(int(s.args["batch"]), {}).setdefault(
                    s.name, []).append(s)
        return {b: sp for b, sp in out.items()
                if any(lo <= u.end <= hi for u in sp.get("fns.unpack", []))}

    def host_ms(self, names: tuple[str, ...]) -> float | None:
        """Per batch of the window, the time inside the union of its spans
        named ``names`` in which no device operation ran; the mean, in ms.
        None if no batch has such a span."""
        lo, hi = self.trace.window
        per = []
        for spans in self.batches().values():
            ivs = trace_reduce._union(trace_reduce._clip(
                [(s.start, s.end) for n in names for s in spans.get(n, [])],
                lo, hi))
            if ivs:
                per.append(sum((e - s) / 1e9 - self.trace.busy_seconds(s, e)
                               for s, e in ivs))
        return 1e3 * sum(per) / len(per) if per else None

    def counter(self, arg: str) -> int:
        """Sum of the ``fns.unpack`` argument ``arg`` over the window's
        batches."""
        return sum(int(s.args.get(arg, 0)) for spans in
                   self.batches().values()
                   for s in spans.get("fns.unpack", []))

    def own_seconds(self) -> dict[tuple[str | None, str], float]:
        """Each operation's own device time inside the window (its length
        less that of the operations nested in it), summed by (scope,
        name) over devices."""
        lo, hi = self.trace.window
        out: dict[tuple[str | None, str], float] = {}
        for d in sorted({o.device for o in self.ops}):
            evs = sorted(((max(o.start, lo), min(o.end, hi), o)
                          for o in self.ops if o.device == d),
                         key=lambda t: (t[0], -t[1]))
            stack: list[list] = []          # [end, op, own ns]

            def close(item):
                key = (item[1].scope, item[1].name)
                out[key] = out.get(key, 0.0) + item[2] / 1e9

            for s, e, op in evs:
                if e <= s:
                    continue
                while stack and stack[-1][0] <= s:
                    close(stack.pop())
                if stack:
                    stack[-1][2] -= min(e, stack[-1][0]) - s
                stack.append([e, op, e - s])
            while stack:
                close(stack.pop())
        return out

    def scope_seconds(self) -> dict[str | None, float]:
        """Own device seconds in the window by scope."""
        out: dict[str | None, float] = {}
        for (scope, _), sec in self.own_seconds().items():
            out[scope] = out.get(scope, 0.0) + sec
        return out

    def per_unit_ms(self, scope: str, arg: str) -> float | None:
        """Device ms of ``scope`` in the window over the window's sum of
        the ``fns.unpack`` argument ``arg``; None where no op has that
        scope or no batch that counter."""
        units = self.counter(arg)
        seconds = self.scope_seconds().get(scope, 0.0)
        if units <= 0 or seconds <= 0:
            return None
        return 1e3 * seconds / units

    def idle_by_span(self) -> dict[str, float]:
        """Idle seconds of device 0 in the window by the innermost host
        span (``fns.*`` or ``bench.*``) around each idle stretch, ``other``
        outside every span."""
        spans = [(s.name, s.start, s.end) for s in self.spans] + [
            sp for sp in self.trace.host_spans
            if sp[0] != trace_reduce.WINDOW_SPAN]
        lo, hi = self.trace.window
        busy = self.trace._busy_intervals(0)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        out: dict[str, float] = {}
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            near = [sp for sp in spans if sp[1] < e and sp[2] > s]
            cuts = sorted({s, e} | {x for sp in near for x in sp[1:]
                                    if s < x < e})
            for a, b in zip(cuts, cuts[1:]):
                around = [sp for sp in near if sp[1] <= a and b <= sp[2]]
                name = (min(around, key=lambda sp: sp[2] - sp[1])[0]
                        if around else "other")
                out[name] = out.get(name, 0.0) + (b - a) / 1e9
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def breakdown(self, top: int = 5) -> dict:
        """Device seconds by scope with each scope's top operations, and
        idle seconds by host span (``idle_by_span``)."""
        by: dict[str, list] = {}
        for (scope, name), sec in self.own_seconds().items():
            by.setdefault(str(scope), []).append([name, sec])
        scopes = {s: {"seconds": sum(v for _, v in ops),
                      "top": sorted(ops, key=lambda kv: -kv[1])[:top]}
                  for s, ops in by.items()}
        return {"device_s_by_scope": scopes,
                "idle_s_by_span": self.idle_by_span()}


def read_planes(planes, recorded: dict | None = None) -> Program:
    """The program's spans and scoped operations from planes shaped as
    ``ProfileData``'s (see ``trace_reduce.reduce_planes``). ``recorded``
    (default: ``recorded_scopes()``) gives an op's scope by its module
    and name where the trace does not."""
    recorded = recorded_scopes() if recorded is None else recorded
    planes = list(planes)
    reduced = trace_reduce.reduce_planes(planes)
    devices = sorted(p.name for p in planes
                     if p.name.startswith("/device:") and any(
                         ln.name == "XLA Ops" for ln in p.lines))

    def lookup(module: str, op: str) -> str | None:
        return recorded.get(module, {}).get(op)

    spans, ops = [], []
    for plane in planes:
        if plane.name in devices:
            d = devices.index(plane.name)
            lines = {ln.name: ln.events for ln in plane.lines}
            runs = sorted((int(ev.start_ns),
                           int(ev.start_ns) + int(ev.duration_ns), ev.name)
                          for ev in lines.get("XLA Modules", []))
            for ev in lines["XLA Ops"]:
                s = int(ev.start_ns)
                name = trace_reduce.op_name(ev.name)
                ops.append(ScopedOp(
                    name, s, s + int(ev.duration_ns),
                    scope_of(dict(ev.stats), ev.name,
                             lookup(_module_of(runs, s), name)), d))
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Span(ev.name, s, e, dict(ev.stats)))
                elif not devices and e > s:
                    st = dict(ev.stats)
                    if "hlo_op" in st:
                        name = str(st["hlo_op"])
                        ops.append(ScopedOp(name, s, e, scope_of(
                            st, ev.name, lookup(str(st.get("hlo_module")),
                                                name))))
    return Program(spans, ops, reduced)


def newest_trace(bench: Path) -> Path | None:
    """The newest ``.xplane.pb`` of any cell's traced run under
    ``<bench>/.cache``, or None."""
    files = list(Path(bench).glob(
        ".cache/*/trace/plugins/profile/*/*.xplane.pb"))
    return max(files, key=lambda f: f.stat().st_mtime) if files else None


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> Program:
    del mtime   # part of the cache key only
    from jax.profiler import ProfileData

    return read_planes(ProfileData.from_file(path).planes)


def load(path: Path) -> Program:
    """The program's record in one trace file, read once per file."""
    return _load(str(path), Path(path).stat().st_mtime)


def trace_of(reader_file: str) -> Program | None:
    """For a reader at ``<bench>/metrics/<name>.py``: the program's record
    in its bench directory's newest trace, or None where there is none."""
    path = newest_trace(Path(reader_file).resolve().parents[1])
    return load(path) if path is not None else None


def main(argv=None, **kw) -> int:
    """A hand tool: one run of a cell as ``run.py`` makes it (the same
    arguments, with ``--trace 1``; ``kw`` go to ``run.main``), which also
    prints the program's record of the traced window to standard error
    (``program: {...}``) before the harness removes the trace."""
    import run

    reduce_dir = trace_reduce.reduce_dir

    def reduce_and_report(trace_dir):
        prog = load(trace_reduce.find_trace(trace_dir))
        out = prog.breakdown()
        out.update(batches=len(prog.batches()),
                   rounds=prog.counter("rounds"),
                   iters=prog.counter("iters"),
                   pack_ms=prog.host_ms(("fns.form", "fns.pack")),
                   unpack_ms=prog.host_ms(("fns.unpack",)))
        print("program: " + json.dumps(out), file=sys.stderr, flush=True)
        return reduce_dir(trace_dir)

    trace_reduce.reduce_dir = reduce_and_report
    try:
        return run.main(argv, **kw)
    finally:
        trace_reduce.reduce_dir = reduce_dir


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
