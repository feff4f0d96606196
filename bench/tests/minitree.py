"""A copy of the benchmark's files at sizes a CPU test run can hold, and
whole runs of a cell on it, sound or with a fault planted."""
from __future__ import annotations

import contextlib
import io
import json
import shutil
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# per cell file: what a test run changes, every other number as committed
SHRINK = {
    "configs": {"n": 3072},
    "traffic/batch_selective.json": {"batch": 32, "batches": 6, "pool": 8,
                                     "predicates": {"shape":
                                                    "two_field_conjunction",
                                                    "selectivity":
                                                    [0.02, 0.05]}},
}


def make(dst: Path) -> Path:
    """``BENCHMARK.json`` and ``bench/`` under ``dst``, shrunk."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    for f in (dst / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg.update(SHRINK["configs"])
        f.write_text(json.dumps(cfg))
    for name, change in SHRINK.items():
        if name.startswith("traffic/"):
            f = dst / "bench" / name
            f.write_text(json.dumps({**json.loads(f.read_text()), **change}))
    return dst


# -- faults planted under the timed path, and a whole run on the CPU ------

def _half_left_out(answers):
    return [a if i < len(answers) // 2 else a[:0]
            for i, a in enumerate(answers)]


def _moved(answers):
    return [(np.asarray(a) + 1) % SHRINK["configs"]["n"] for a in answers]


def _reversed(answers):
    return [np.asarray(a)[::-1] for a in answers]


def _truncated(answers):
    return [np.asarray(a)[:10] for a in answers]


FAULTS = {"half_left_out": _half_left_out, "answer_moved": _moved,
          "order_turned": _reversed, "answer_truncated": _truncated}


def plant(fault):
    """A ``break_program`` hook: wrap the service call that produces the
    window's answers so that ``fault`` alters them."""
    def hook(loop):
        svc = loop.svc
        inner = svc.query_batch

        def broken(*args, **kw):
            ids, stats = inner(*args, **kw)
            return fault(ids), stats

        svc.query_batch = broken
    return hook


def run_cell(tree: Path, cell: str, hook=None) -> dict:
    """One whole run of ``cell`` on the CPU, its look for a chip skipped;
    the result line."""
    import run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", "22", "--seconds", "2",
                       "--trace", "0"], require_tpu=False, root=tree,
                      break_program=hook)
    if rc != 0:
        raise RuntimeError(f"run exited {rc}")
    return json.loads(out.getvalue().strip().splitlines()[-1])


def limits(tree: Path, cell: str) -> dict:
    return json.loads((tree / "bench" / "limits" / f"{cell}.json")
                      .read_text())
