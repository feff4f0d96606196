#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix (and the generator module the mix names), per-check limits
and per-layer metric readers are files under ``bench/`` found by the
names there. A run makes the corpus on the device
from the seed, builds the index, warms every program shape the window
uses (all of that is ``setup_s``), drives the traffic for ``--seconds``,
reads the peak device memory, frees the program's state, and compares
every answer of the window with the plain reference (``reference.py``).

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, with ``device.busy_s``/``window_s`` and a
``breakdown``. The last line of standard output is one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the last key of that object.

It runs on a TPU only: with no TPU, or fewer chips than the cell asks
for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import plugin  # noqa: E402


def workdir(root: Path, workload: str) -> Path:
    """The run-time files of one cell (the trace): a fixed path
    inside the checkout, never committed."""
    path = root / "bench" / ".cache" / workload
    path.mkdir(parents=True, exist_ok=True)
    return path


class NoChip(RuntimeError):
    """The run found no TPU, or fewer chips than the cell asks for."""


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by name: the entry of
    ``BENCHMARK.json``, its configuration, traffic mix and check limits,
    and the end-to-end and per-layer metrics it reports."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    bench = root / "bench"

    def reports(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    traffic = json.loads(
        (bench / "traffic" / f"{cell['traffic']}.json").read_text())
    return {
        "cell": cell,
        "config": json.loads((root / config["file"]).read_text()),
        "traffic": traffic,
        "generator": bench / "traffic" / f"{traffic['generator']}.py",
        "limits": json.loads(
            (bench / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if reports(m)],
        "per_layer": [m for m in spec["per_layer"] if reports(m)],
        "readers": {m["name"]: bench / "metrics" / f"{m['name']}.py"
                    for m in spec["per_layer"] if reports(m)},
    }


def load_reader(path: Path):
    """The ``read(ctx)`` function of one per-layer metric's reader."""
    return plugin.load(path).read


def make_loop(spec: dict, seed: int, seconds: float, root: Path):
    """The cell's traffic generator, loaded by the name its traffic file
    gives, set to drive ``seconds`` of the mix from ``seed``."""
    return plugin.load(spec["generator"]).Loop(
        spec["config"], spec["traffic"], seed, seconds,
        workdir(root, spec["cell"]["name"]))


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {info['platform']!r}); "
                     f"this benchmark reports chip numbers only")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{info['count']}")
    return info


class CompileCounter:
    """Counts the programs JAX lowers (each a new compile or a load from
    the persistent cache): none should happen inside the window."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0

        def on_duration(event: str, *args, **kw) -> None:
            if event == self.EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def check_lines(checks: dict, limits: dict) -> dict:
    """Each number compared beside its limit; missing limits fail."""
    out = {}
    for name, value in checks.items():
        limit = limits.get(name)
        ok = limit is not None and value <= limit
        out[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    return out


def main(argv=None, *, require_tpu: bool = True, root: Path = ROOT,
         break_program=None) -> int:
    """One run of one cell. The keywords are for tests on the CPU:
    ``require_tpu=False`` skips the look for a chip (and the persistent
    compile cache), ``root`` reads another copy of the benchmark's files,
    and ``break_program(loop)`` may plant a fault in the program after
    the warm-up, under the timed path."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    spec = load_cell(args.workload, root)
    sys.path.insert(0, str(root / "src"))
    try:
        dev = device_info(int(spec["cell"]["chips"]), require_tpu)
        peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
        if dev["kind"] not in peaks and require_tpu:
            raise NoChip(f"device kind {dev['kind']!r} is not in "
                         f"bench/peaks.json")
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    peak = peaks.get(dev["kind"], {})

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    import reference
    import trace_reduce

    if require_tpu:
        # a CPU rehearsal (a test) leaves the persistent cache alone: the
        # setting is process-wide and would reach the tests that follow
        enable_compile_cache()
    compiles = CompileCounter()
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, float(spec["traffic"]["trace_seconds"]))
    loop = make_loop(spec, args.seed, seconds, root)
    loop.setup()
    loop.warm()
    if break_program is not None:
        break_program(loop)
    setup_s = time.perf_counter() - T_START
    phases = " ".join(f"{k}={v:.3f}" for k, v in loop.phases.items())
    print(f"setup_s: {setup_s:.3f} ({phases}; programs lowered in "
          f"set-up: {compiles.count})", flush=True)

    trace_dir = workdir(root, args.workload) / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    before = compiles.count
    with jax.profiler.TraceAnnotation("bench.window"):
        loop.window(annotate=bool(args.trace))
    if args.trace:
        jax.profiler.stop_trace()
    in_window = compiles.count - before
    print(f"window: {loop.describe()} compiles_in_window: {in_window}",
          flush=True)
    loop.finish()

    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    counters = loop.counters()
    loop.close()
    gc.collect()
    cmp = loop.comparison()
    judged = reference.judge(cmp["vectors"], cmp["queries"], cmp["answers"],
                             cmp["allowed"], loop.k)
    print(f"short_answers: {judged['short_answers']} of "
          f"{len(cmp['answers'])}", flush=True)
    numbers = {k: judged[k] for k in reference.CHECKS}
    numbers.update(cmp["checks"], compiles_in_window=in_window)
    checks = check_lines(numbers, spec["limits"])
    correct = all(c["ok"] for c in checks.values())

    metrics = {}
    result = {"correct": correct, "attempted": cmp["attempted"],
              "failed": cmp["failed"]}
    if args.trace:
        reduced = trace_reduce.reduce_dir(trace_dir)
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        ctx = {"trace": reduced, "counters": counters, "peak": peak,
               "config": spec["config"], "traffic": spec["traffic"]}
        for m in spec["per_layer"]:
            value = load_reader(spec["readers"][m["name"]])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = reduced.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = loop.end_to_end(judged)
        e2e["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result.update(metrics=metrics, device=dev)
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']} limit {c['limit']} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
