"""``closed_batch``'s closed loop on a ``labels_and_date`` configuration:
each query's predicate is a label set AND a date window.

It differs from ``closed_batch`` only where the data does: the corpus
(``bench/corpora/labels_and_date.py``, whose labels are a tag-set field),
the predicate descriptions (a ``{"f", "has": [labels]}`` clause beside
``predicates.py``'s value-set and interval clauses), their program form
(``HasAny`` AND ``Range``) and numpy masks, and ``counters()``, whose
``fields`` counts every int32 column the predicate sweep reads, label
words included.
"""
from __future__ import annotations

import time
from pathlib import Path

import jax
import numpy as np

import datagen
import loadgen
import plugin

BENCH = Path(__file__).resolve().parents[1]
Base = plugin.load(Path(__file__).with_name("closed_batch.py")).Loop
corpora = plugin.load(BENCH / "corpora" / "labels_and_date.py")


def mask(desc: dict, metadata: np.ndarray) -> np.ndarray:
    """Rows of ``metadata`` that pass ``desc``, by numpy alone."""
    out = np.zeros(metadata.shape[0], bool)
    for conj in desc["any"]:
        m = np.ones(metadata.shape[0], bool)
        for c in conj:
            if "has" in c:
                m &= corpora.has_any(metadata, c["f"], c["has"])
                continue
            col = metadata[:, c["f"]]
            if "in" in c:
                m &= np.isin(col, np.asarray(c["in"], np.int64))
            else:
                m &= (col >= c["lo"]) & (col <= c["hi"])
        out |= m
    return out


def to_program(desc: dict):
    """The program's predicate for a description: an ``Or`` of ``And``s of
    ``HasAny``, ``In`` and ``Range`` leaves."""
    from repro.core.predicate import And, HasAny, In, Or, Range

    def leaf(c):
        if "has" in c:
            return HasAny(c["f"], c["has"])
        if "in" in c:
            return In(c["f"], c["in"])
        return Range(c["f"], c["lo"], c["hi"])

    conjs = [And(*map(leaf, conj)) for conj in desc["any"]]
    return conjs[0] if len(conjs) == 1 else Or(*conjs)


class Loop(Base):
    """Back-to-back ``query_batch`` calls of label-and-window queries."""

    def setup(self) -> None:
        from repro.core.types import Dataset
        from repro.serve.retrieval import RetrievalService

        cfg, tr = self.cfg, self.traffic
        t = time.perf_counter()
        self.corpus = c = corpora.make_corpus(cfg, self.seed)
        ds = Dataset(np.asarray(c.vectors[:c.n]), c.metadata[:c.n],
                     c.field_names, c.vocab_sizes, tag_fields=c.tag_fields)
        self.phases["corpus_s"] = time.perf_counter() - t
        rng = np.random.default_rng([self.seed, 1])
        shape = loadgen.shape(tr["predicates"]["shape"], BENCH)
        lo, hi = tr["predicates"]["selectivity"]
        targets = np.geomspace(lo, hi, int(tr["pool"]))
        self.descs = [shape.draw(c, float(x), rng) for x in targets]
        self.masks = np.stack([mask(d, ds.metadata) for d in self.descs])
        q, nb = int(tr["batch"]), int(tr["batches"])
        self.pred_of = rng.permutation(np.resize(np.arange(len(targets)),
                                                 q * nb))
        src = np.empty(q * nb, np.int64)
        for p, m in enumerate(self.masks):
            at = np.nonzero(self.pred_of == p)[0]
            members = np.nonzero(m)[0]
            src[at] = members[rng.integers(members.size, size=at.size)]
        self.vectors = datagen.queries_near(c, src, self.seed,
                                            float(cfg["query_noise"]))
        progs = [to_program(d) for d in self.descs]
        self.progs = [progs[p] for p in self.pred_of]
        self.phases["traffic_s"] = time.perf_counter() - t - sum(
            self.phases.values())
        t = time.perf_counter()
        self.svc = RetrievalService.build(
            ds, config=loadgen.program_config(cfg, tr))
        jax.block_until_ready(self.svc.engine().vectors)
        self.phases["build_s"] = time.perf_counter() - t

    def counters(self) -> dict:
        out = super().counters()
        out["fields"] = int(self.corpus.metadata.shape[1])
        return out
