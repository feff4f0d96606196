"""The harness finds everything a cell needs by name, a cell is added by
adding files and entries alone, and a run without a TPU reports nothing."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import loadgen  # noqa: E402
import minitree  # noqa: E402
import run  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files_by_name(cell):
    spec = run.load_cell(cell)
    assert spec["config"]["name"] == spec["cell"]["config"]
    assert spec["traffic"]["name"] == spec["cell"]["traffic"]
    assert spec["generator"].name == f"{spec['traffic']['generator']}.py"
    assert callable(run.plugin.load(spec["generator"]).Loop)
    assert callable(loadgen.shape(spec["traffic"]["predicates"]["shape"])
                    .draw)
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"], "every cell reports a per-layer metric"
    for m in spec["per_layer"]:
        assert m["moves"] in names, (m["name"], m["moves"])
        assert callable(run.load_reader(spec["readers"][m["name"]]))
    for name in ("wrong_ids", "empty_answers", "order_gap",
                 "short_answer_share", "compiles_in_window"):
        assert name in spec["limits"]


def test_every_configuration_is_used_and_lists_its_cuts():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["name"] == c["name"]


def _digest(tree: Path) -> dict:
    return {str(p.relative_to(tree)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(tree.rglob("*")) if p.is_file()}


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = _digest(tmp_path / "bench")
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "hm-catalogue.json").read_text())
    cfg.update(name="hm-half", n=52550)
    (b / "configs" / "hm-half.json").write_text(json.dumps(cfg))
    traffic = json.loads((b / "traffic" / "batch_selective.json")
                         .read_text())
    traffic.update(name="batch_mixed",
                   predicates={"shape": "two_field_conjunction",
                               "selectivity": [0.005, 0.5]})
    (b / "traffic" / "batch_mixed.json").write_text(json.dumps(traffic))
    (b / "limits" / "hm-half.batch.mixed.json").write_text(
        (b / "limits" / "hm.batch.selective.json").read_text())
    (b / "metrics" / "batches.batch.py").write_text(
        "def read(ctx):\n    return ctx['counters']['batches']\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "hm-half", "source": "x",
                            "file": "bench/configs/hm-half.json",
                            "reduced": ["n"], "why": "x"})
    spec["workloads"].append({"name": "hm-half.batch.mixed",
                              "config": "hm-half", "traffic": "batch_mixed",
                              "chips": 1, "why": "x"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "hm.batch.selective" in m["workloads"]:
            m["workloads"].append("hm-half.batch.mixed")
    spec["per_layer"].append({"name": "batches.batch", "unit": "batches",
                              "better": "higher", "source": "program_counter",
                              "layer": "retrieval host path", "moves": "qps",
                              "workloads": ["hm-half.batch.mixed"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    new = run.load_cell("hm-half.batch.mixed", tmp_path)
    assert new["config"]["n"] == 52550
    assert new["traffic"]["predicates"]["selectivity"] == [0.005, 0.5]
    assert [m["name"] for m in new["per_layer"]] == ["batches.batch"]
    read = run.load_reader(new["readers"]["batches.batch"])
    assert read({"counters": {"batches": 7}}) == 7
    assert {m["name"] for m in new["end_to_end"]} == {
        "qps", "recall_at_25", "setup_s"}
    # the cells already there resolve as before, and no file was edited
    assert run.load_cell("hm.batch.selective", tmp_path)["per_layer"] == \
        run.load_cell("hm.batch.selective")["per_layer"]
    after = _digest(tmp_path / "bench")
    assert all(after[k] == v for k, v in before.items())


# a new traffic mix with a generator and a predicate shape of its own
NEW_GENERATOR = '''"""Each batch of the closed loop sent twice in a row."""
from pathlib import Path

import plugin

Base = plugin.load(Path(__file__).with_name("closed_batch.py")).Loop


class Loop(Base):
    def _batch(self, b):
        return super()._batch(b // 2)
'''

NEW_SHAPE = '''"""One field's most frequent values up to the target share."""
import numpy as np


def draw(corpus, target, rng):
    meta = corpus.metadata[:corpus.n]
    f = int(rng.integers(meta.shape[1]))
    counts = np.bincount(meta[meta[:, f] >= 0, f],
                         minlength=corpus.vocab_sizes[f])
    vals, acc = [], 0
    for v in np.argsort(-counts, kind="stable"):
        if not vals or acc + counts[v] <= target * corpus.n:
            vals.append(int(v))
            acc += int(counts[v])
    return {"any": [[{"f": f, "in": sorted(vals)}]]}
'''


def test_a_new_generator_and_shape_run_with_no_file_edited(tmp_path):
    """A mix whose generator and predicate shape are new files runs end to
    end, on the CPU at a test size, and is judged like any other."""
    tree = minitree.make(tmp_path)
    before = _digest(tree / "bench")
    b = tree / "bench"
    (b / "traffic" / "closed_repeat.py").write_text(NEW_GENERATOR)
    (b / "shapes" / "one_field.py").write_text(NEW_SHAPE)
    traffic = json.loads((b / "traffic" / "batch_selective.json")
                         .read_text())
    traffic.update(name="repeat_one_field", generator="closed_repeat",
                   predicates={"shape": "one_field",
                               "selectivity": [0.05, 0.3]})
    (b / "traffic" / "repeat_one_field.json").write_text(json.dumps(traffic))
    cell = "hm.batch.repeat"
    (b / "limits" / f"{cell}.json").write_text(
        (b / "limits" / "hm.batch.selective.json").read_text())
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": cell, "config": "hm-catalogue",
                              "traffic": "repeat_one_field", "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "hm.batch.selective" in m.get("workloads", []):
            m["workloads"].append(cell)
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))

    result = minitree.run_cell(tree, cell)
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"qps", "recall_at_25", "setup_s"}
    assert result["metrics"]["recall_at_25"]["value"] > 0.5
    after = _digest(tree / "bench")
    assert all(after.get(k) == v for k, v in before.items())


def _bench_run(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_with_a_device_error():
    proc = _bench_run(ROOT)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_with_only_the_benchmark_files_reports_nothing(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = _bench_run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        run.load_cell("no.such.cell")
