"""The ``arxiv384.batch.window`` cell on the CPU at small sizes: its corpus
and predicate shape on the committed schema, a whole sound run through the
harness, and a fault in the program's tag test that ``correct`` catches."""
from __future__ import annotations

import contextlib
import io
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import loadgen  # noqa: E402
import minitree  # noqa: E402
import plugin  # noqa: E402
import run  # noqa: E402

CELL = "arxiv384.batch.window"
CONFIG = json.loads((BENCH / "configs" / "arxiv-titles-384.json")
                    .read_text())
TRAFFIC = json.loads((BENCH / "traffic" / "batch_window.json").read_text())
corpora = plugin.load(BENCH / "corpora" / "labels_and_date.py")
generator = plugin.load(BENCH / "traffic" / "closed_batch_window.py")
SHAPE = loadgen.shape(TRAFFIC["predicates"]["shape"])
# what a test run changes in the cell's traffic, every other number as
# committed (minitree shrinks the configuration's n)
SMALL = {"batch": 32, "batches": 6, "pool": 64}


def _corpus(n: int, seed: int):
    return corpora.make_corpus(dict(CONFIG, n=n), seed)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tree = minitree.make(tmp_path_factory.mktemp("bench"))
    f = tree / "bench" / "traffic" / "batch_window.json"
    f.write_text(json.dumps({**json.loads(f.read_text()), **SMALL}))
    return tree


def test_corpus_is_deterministic_and_keeps_the_schema():
    big = 2**31 + 12345
    a, b = _corpus(600, big), _corpus(600, big)
    c = _corpus(600, big + 2**32)
    np.testing.assert_array_equal(np.asarray(a.vectors),
                                  np.asarray(b.vectors))
    np.testing.assert_array_equal(a.metadata, b.metadata)
    assert not np.array_equal(a.metadata, c.metadata)
    meta = CONFIG["metadata"]
    v, days = meta["labels"]["n_labels"], meta["update_date"]["days"]
    words = corpora.label_words(v)
    assert a.tag_fields == c.tag_fields == ((0, v),)
    assert a.vocab_sizes == [v] + [0] * (words - 1) + [days]
    assert a.metadata.shape == (600, words + 1)
    labels = corpora.label_matrix(a)
    per_row = labels.sum(axis=1)
    assert per_row.min() >= 1
    assert per_row.max() <= meta["labels"]["max_per_row"]
    assert 1.4 < per_row.mean() < 1.9
    assert (0 <= a.metadata[:, -1]).all() and (a.metadata[:, -1] < days).all()
    norms = np.linalg.norm(np.asarray(a.vectors), axis=1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-5)


def test_predicates_land_in_the_selectivity_range():
    """The shape, on the committed schema, draws label-set-and-window
    predicates inside the traffic's range, whose label sets span several
    words of the tag-set field; the program's predicate selects the rows
    the reference's numpy evaluation does."""
    c = _corpus(8192, 11)
    lo, hi = TRAFFIC["predicates"]["selectivity"]
    rng = np.random.default_rng(2)
    sels, words = [], set()
    for t in np.geomspace(lo, hi, TRAFFIC["pool"] // 4):
        desc = SHAPE.draw(c, float(t), rng)
        passes = generator.mask(desc, c.metadata)
        sels.append(passes.mean())
        has = desc["any"][0][0]["has"]
        assert 1 <= len(has) <= 8
        words.update(lab >> 5 for lab in has)
        np.testing.assert_array_equal(
            generator.to_program(desc).mask(c.metadata, c.vocab_sizes),
            passes)
    assert min(sels) >= 0.8 * lo and max(sels) <= 1.25 * hi
    assert len(words) >= 3


def test_counters_count_the_tag_words_as_fields():
    cfg = dict(CONFIG, n=1024)
    loop = generator.Loop(cfg, dict(TRAFFIC, **SMALL), seed=5, seconds=1,
                          workdir=None)
    loop.setup()
    out = loop.counters()
    words = corpora.label_words(CONFIG["metadata"]["labels"]["n_labels"])
    assert out["fields"] == words + 1 == loop.corpus.metadata.shape[1]
    assert out["n"] == 1024 and out["d"] == CONFIG["d"]
    assert loop.svc.engine().tag_fields == loop.corpus.tag_fields


def test_sound_run_is_correct(tree):
    result = minitree.run_cell(tree, CELL)
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"qps", "recall_at_25", "setup_s"}
    assert result["metrics"]["recall_at_25"]["value"] > 0.8


def _first_word_only(loop):
    """Plant the fault: the program's tag test reads only a row's first
    label word, whatever word of the clause it tests. The search program
    is compiled again and warmed before the window."""
    from repro.kernels import ref

    def tag_ok(metadata, field, words):
        import jax.numpy as jnp

        row = metadata[:, jnp.maximum(field, 0)].T.astype(jnp.uint32)
        ok = jnp.zeros(row.shape, bool)
        for w in range(words.shape[1]):
            ok = ok | ((row & words[:, w][:, None]) != 0)
        return ok

    ref.tag_ok = tag_ok
    loop.svc.engine()._init_programs()
    loop.warm()


def test_tag_test_reading_one_word_is_not_correct(tree, monkeypatch):
    from repro.kernels import ref

    monkeypatch.setattr(ref, "tag_ok", ref.tag_ok)
    result = minitree.run_cell(tree, CELL, _first_word_only)
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert result["correct"] is False, checks
    assert checks["wrong_ids"] > 0 or checks["empty_answers"] > 0, checks
    assert checks["compiles_in_window"] == 0


def test_traced_run_reads_the_filter_passes(tmp_path, monkeypatch):
    """A ``--trace 1`` run of the cell on the CPU: ``filter_pass_us``
    reads the ``filter_eval`` scope's time over the batches'
    ``clause_passes``."""
    from repro.core.batched import scopes

    monkeypatch.setattr(scopes, "OP_SCOPES", {})
    tree = minitree.make(tmp_path)
    f = tree / "bench" / "traffic" / "batch_window.json"
    f.write_text(json.dumps({**json.loads(f.read_text()), **SMALL}))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", str(2**31 + 21),
                       "--seconds", "2", "--trace", "1"],
                      require_tpu=False, root=tree)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["filter_pass_us"] > 0, m
    assert {"host_ms.batch", "idle_share.batch", "lane_occupancy",
            "pack_ms.batch", "unpack_ms.batch", "walk_hop_ms",
            "anchor_round_ms"} <= set(m), m
