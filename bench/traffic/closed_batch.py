"""A closed loop: one client sends back-to-back
``RetrievalService.query_batch`` calls of ``batch`` queries.

The traffic file gives ``batch``, ``batches`` (the plan's length; the
window cycles through it), ``pool`` (distinct predicates built per seed),
and ``predicates``: the ``shape`` (``bench/shapes/<shape>.py``) and the
``selectivity`` range. The pool's target selectivities are log-spaced over
that range, so every seed draws the same multiset of targets, each query
takes one in the seed's own order, and its vector lies near a random row
that passes its predicate.
"""
from __future__ import annotations

import time
from pathlib import Path

import jax
import numpy as np

import datagen
import loadgen
import predicates

BENCH = Path(__file__).resolve().parents[1]


class Loop:
    """Back-to-back ``query_batch`` calls from one client."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 workdir: Path):
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds = seed, seconds
        self.k = int(cfg["index"]["walk.k"])
        self.batches: list[dict] = []
        self.phases: dict[str, float] = {}

    def setup(self) -> None:
        from repro.serve.retrieval import RetrievalService

        cfg, tr = self.cfg, self.traffic
        t = time.perf_counter()
        self.corpus = datagen.make_corpus(cfg, self.seed)
        ds = loadgen.dataset(self.corpus)
        self.phases["corpus_s"] = time.perf_counter() - t
        rng = np.random.default_rng([self.seed, 1])
        shape = loadgen.shape(tr["predicates"]["shape"], BENCH)
        lo, hi = tr["predicates"]["selectivity"]
        targets = np.geomspace(lo, hi, int(tr["pool"]))
        self.descs = [shape.draw(self.corpus, float(x), rng) for x in targets]
        self.masks = np.stack([predicates.mask(d, ds.metadata)
                               for d in self.descs])
        q, nb = int(tr["batch"]), int(tr["batches"])
        self.pred_of = rng.permutation(np.resize(np.arange(len(targets)),
                                                 q * nb))
        src = np.empty(q * nb, np.int64)
        for p, m in enumerate(self.masks):
            at = np.nonzero(self.pred_of == p)[0]
            members = np.nonzero(m)[0]
            src[at] = members[rng.integers(members.size, size=at.size)]
        self.vectors = datagen.queries_near(self.corpus, src, self.seed,
                                            float(cfg["query_noise"]))
        progs = [predicates.to_program(d) for d in self.descs]
        self.progs = [progs[p] for p in self.pred_of]
        self.phases["traffic_s"] = time.perf_counter() - t - sum(
            self.phases.values())
        t = time.perf_counter()
        self.svc = RetrievalService.build(
            ds, config=loadgen.program_config(cfg, tr))
        jax.block_until_ready(self.svc.engine().vectors)
        self.phases["build_s"] = time.perf_counter() - t

    def _batch(self, b: int):
        q = int(self.traffic["batch"])
        lo = (b % int(self.traffic["batches"])) * q
        return lo, self.vectors[lo:lo + q], self.progs[lo:lo + q]

    def warm(self) -> None:
        t = time.perf_counter()
        for b in range(2):
            _, v, p = self._batch(b)
            self.svc.query_batch(v, p)
        self.phases["warm_s"] = time.perf_counter() - t

    def window(self, annotate: bool) -> None:
        t0 = time.perf_counter()
        b = 0
        while True:
            lo, v, p = self._batch(b)
            t = time.perf_counter()
            with loadgen.annotate("bench.query_batch", annotate):
                ids, stats = self.svc.query_batch(v, p)
            self.batches.append({"lo": lo, "ids": ids,
                                 "hops": np.asarray(stats["hops"]),
                                 "seconds": time.perf_counter() - t})
            b += 1
            if time.perf_counter() - t0 >= self.seconds:
                break
        self.window_s = time.perf_counter() - t0

    def finish(self) -> None:
        pass

    def close(self) -> None:
        self.svc = None

    def describe(self) -> str:
        """The window's batches: each one's largest hop count and seconds."""
        return (f"batches={len(self.batches)} max_hops="
                f"{[int(b['hops'].max()) for b in self.batches]} seconds="
                f"{[round(b['seconds'], 3) for b in self.batches]}")

    def comparison(self) -> dict:
        """What the reference compares: every query the window answered,
        its answer, and the rows it could rightly return."""
        q = int(self.traffic["batch"])
        at = np.concatenate([b["lo"] + np.arange(q) for b in self.batches])
        masks, pred_of = self.masks, self.pred_of[at]
        return {"vectors": self.corpus.vectors[:self.corpus.n],
                "queries": self.vectors[at],
                "answers": [i for b in self.batches for i in b["ids"]],
                "allowed": lambda lo, hi: masks[pred_of[lo:hi]],
                "checks": {}, "attempted": at.size, "failed": 0}

    def end_to_end(self, judged: dict) -> dict:
        n_q = sum(len(b["ids"]) for b in self.batches)
        return {"qps": n_q / self.window_s,
                "recall_at_25": judged["recall"]}

    def counters(self) -> dict:
        q = int(self.traffic["batch"])
        lanes = max(self.svc.config.serve.min_bucket,
                    1 << (q - 1).bit_length())
        return {"batches": len(self.batches), "batch": q, "lanes": lanes,
                "hops": [b["hops"] for b in self.batches],
                "mean_degree": float(self.svc.index.graph.degrees.mean()),
                "n": self.corpus.n, "d": self.corpus.d,
                "fields": len(self.corpus.vocab_sizes)}
