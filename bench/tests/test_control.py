"""``correct`` must come out false for the control and for each fault of
the timed path a one-chip cell can have, on the CPU at a small size, in
each cell of ``BENCHMARK.json``.

The control is the reference in the program's place one precision below
the configuration's (cosine scores of bfloat16 operands); the faults are
planted under a whole run of the harness, with its look for a chip
skipped: half of each batch's answers left out, and an answer altered
where it is produced (its ids moved to other rows, its order turned
round, or cut to its first ten ids)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import control  # noqa: E402
import minitree  # noqa: E402

CELLS = [w["name"] for w in json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return minitree.make(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tree, cell):
    out = control.readings(cell, seed=21, seconds=2.0, root=tree)
    limit = minitree.limits(tree, cell)["order_gap"]
    assert out["queries"] > 0
    assert out["program"]["order_gap"] <= limit
    assert out["program"]["wrong_ids"] == 0
    assert out["control"]["order_gap"] > limit, out


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tree, cell):
    """The same run with nothing planted: the faults below are what
    turns it."""
    result = minitree.run_cell(tree, cell)
    assert result["correct"] is True, result["checks"]


@pytest.mark.parametrize("fault", sorted(minitree.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_in_the_timed_path_is_not_correct(tree, cell, fault):
    result = minitree.run_cell(tree, cell,
                               minitree.plant(minitree.FAULTS[fault]))
    assert result["correct"] is False, result["checks"]
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"
