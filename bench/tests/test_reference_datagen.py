"""The reference, the corpus generator and the traffic's predicates, on
the CPU at small sizes."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import datagen  # noqa: E402
import loadgen  # noqa: E402
import predicates  # noqa: E402
import reference  # noqa: E402

CONFIGS = {p.stem: json.loads(p.read_text())
           for p in (BENCH / "configs").glob("*.json")}
TRAFFIC = {p.stem: json.loads(p.read_text())
           for p in (BENCH / "traffic").glob("*.json")}
CELLS = json.loads((BENCH.parent / "BENCHMARK.json").read_text())["workloads"]
CONJUNCTION = loadgen.shape("two_field_conjunction")


def _small(name: str, n: int) -> dict:
    cfg = dict(CONFIGS[name])
    cfg["n"] = n
    return cfg


def _numpy_topk(vectors, queries, allowed, k):
    """Filtered top-k by float64 cosine distance, one query at a time."""
    out = []
    for q, ok in zip(queries.astype(np.float64), allowed):
        rows = np.nonzero(ok)[0]
        d = 1.0 - vectors[rows].astype(np.float64) @ q
        out.append(rows[np.argsort(d, kind="stable")[:k]])
    return out


def test_reference_equals_numpy_brute_force():
    corpus = datagen.make_corpus(_small("hm-catalogue", 1500), seed=3)
    vectors = np.asarray(corpus.vectors)
    rng = np.random.default_rng(0)
    allowed = rng.random((300, 1500)) < rng.uniform(0.002, 0.2, (300, 1))
    queries = datagen.queries_near(corpus, rng.integers(1500, size=300),
                                   seed=4, noise=0.15)
    got = reference.exact_answers(corpus.vectors, queries,
                                  lambda lo, hi: allowed[lo:hi], k=25)
    want = _numpy_topk(vectors, queries, allowed, 25)
    assert sum(len(w) < 25 for w in want) > 0     # some lists run short
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    # the exact answers judge as correct, with full recall
    out = reference.judge(corpus.vectors, queries, got,
                          lambda lo, hi: allowed[lo:hi], k=25)
    assert out == {"wrong_ids": 0, "empty_answers": 0, "order_gap": 0.0,
                   "short_answers": 0, "short_answer_share": 0.0,
                   "recall": 1.0}


def test_judge_counts_each_kind_of_wrong_answer():
    corpus = datagen.make_corpus(_small("hm-catalogue", 800), seed=5)
    rng = np.random.default_rng(1)
    allowed = rng.random((4, 800)) < 0.3
    queries = datagen.queries_near(corpus, rng.integers(800, size=4),
                                   seed=6, noise=0.15)
    exact = reference.exact_answers(corpus.vectors, queries,
                                    lambda lo, hi: allowed[lo:hi], k=10)
    failing = int(np.nonzero(~allowed[0])[0][0])
    answers = [np.concatenate([[failing], exact[0][1:]]),   # wrong id
               exact[1][:5],                                # short
               exact[2][::-1],                              # out of order
               None]                                        # never came
    out = reference.judge(corpus.vectors, queries, answers,
                          lambda lo, hi: allowed[lo:hi], k=10)
    assert out["wrong_ids"] == 1
    assert out["empty_answers"] == 1
    assert out["short_answers"] == 2
    assert out["short_answer_share"] == 0.5
    assert out["order_gap"] > 1e-3
    # an empty answer is right only where no row passes
    none_pass = np.zeros((1, 800), bool)
    out = reference.judge(corpus.vectors, queries[:1], [np.zeros(0, int)],
                          lambda lo, hi: none_pass, k=10)
    assert out["empty_answers"] == 0 and out["recall"] == 1.0


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_corpus_is_deterministic_per_seed(name):
    big = 2**31 + 12345                     # beyond 32 bits' sign
    a = datagen.make_corpus(_small(name, 600), seed=big)
    b = datagen.make_corpus(_small(name, 600), seed=big)
    c = datagen.make_corpus(_small(name, 600), seed=big + 2**32)
    np.testing.assert_array_equal(np.asarray(a.vectors), np.asarray(b.vectors))
    np.testing.assert_array_equal(a.metadata, b.metadata)
    assert not np.array_equal(np.asarray(a.vectors), np.asarray(c.vectors))
    # one schema for every seed: the configuration's
    assert a.vocab_sizes == c.vocab_sizes == \
        CONFIGS[name]["metadata"]["vocab_sizes"]
    norms = np.linalg.norm(np.asarray(a.vectors), axis=1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-5)
    assert a.metadata.shape == (600, len(a.vocab_sizes))
    assert (a.metadata < np.asarray(a.vocab_sizes)).all()


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_batch_predicates_land_in_the_selectivity_range(cell):
    """Every cell's predicate shape, on its configuration's data, draws
    predicates inside its traffic's selectivity range."""
    tr = TRAFFIC[cell["traffic"]]
    c = datagen.make_corpus(_small(cell["config"], 8192), seed=11)
    shape = loadgen.shape(tr["predicates"]["shape"])
    lo, hi = tr["predicates"]["selectivity"]
    rng = np.random.default_rng(2)
    sels = []
    for t in np.geomspace(lo, hi, tr["pool"]):
        desc = shape.draw(c, float(t), rng)
        predicates.to_program(desc)          # the program takes it
        sels.append(predicates.mask(desc, c.metadata).mean())
    assert min(sels) >= 0.8 * lo and max(sels) <= 1.25 * hi


def test_program_predicate_matches_the_description():
    """The program's own predicate selects the rows the reference's numpy
    evaluation does (the same description, two evaluations)."""
    c = datagen.make_corpus(_small("hm-catalogue", 3000), seed=12)
    descs = [
        {"any": [[{"f": 0, "in": [0, 3]}, {"f": 1, "in": [1]}]]},
        {"any": [[{"f": 7, "in": [5, 6, 190]}]]},
        {"any": [[{"f": 4, "in": [2]}, {"f": 9, "in": [0, 1, 2]},
                  {"f": 23, "in": [7]}]]},
    ]
    for d in descs:
        prog = predicates.to_program(d)
        np.testing.assert_array_equal(
            prog.mask(c.metadata, c.vocab_sizes),
            predicates.mask(d, c.metadata))
    with pytest.raises(ValueError):         # no program form drawn yet
        predicates.to_program({"any": [[{"f": 0, "lo": 1, "hi": 2}]]})


def test_walk_navigates_the_benchmark_data():
    """On the benchmark's own data at catalogue width, the program's walk
    finds the filtered top-25 at least twice as well as a visit of as many
    rows drawn at random."""
    from repro.core.types import Dataset
    from repro.serve.retrieval import RetrievalService

    cfg = _small("hm-catalogue", 4096)
    cfg["index"] = dict(cfg["index"], **{"graph.graph_k": 32,
                                         "graph.r_max": 64})
    c = datagen.make_corpus(cfg, seed=13)
    ds = Dataset(np.asarray(c.vectors), c.metadata, c.field_names,
                 c.vocab_sizes)
    svc = RetrievalService.build(
        ds, config=loadgen.program_config(cfg, {}))
    rng = np.random.default_rng(3)
    desc = CONJUNCTION.draw(c, 0.05, rng)
    passes = predicates.mask(desc, c.metadata)
    src = rng.choice(np.nonzero(passes)[0], size=32)
    queries = datagen.queries_near(c, src, seed=14,
                                   noise=cfg["query_noise"])
    ids, stats = svc.query_batch(queries,
                                 [predicates.to_program(desc)] * 32)
    out = reference.judge(c.vectors, queries, ids,
                          lambda lo, hi: np.tile(passes, (hi - lo, 1)), 25)
    visit = np.mean(np.minimum(
        1.0, stats["hops"] * svc.index.graph.degrees.mean() / ds.n))
    assert out["wrong_ids"] == 0
    assert out["recall"] >= 0.8, (out, visit)
    assert out["recall"] >= 2 * visit, (out, visit)
