"""A label set AND a date window: ``labels`` has any of S, S holding 1-8
labels taken most popular first from a random start among the 24 most
popular, until they cover about sqrt(target) of the rows; then a window of
whole days over the rows that carry one of S, at a random place, wide
enough that the conjunction passes about ``target`` of the rows. It fits
a ``labels_and_date`` configuration (``bench/corpora/labels_and_date.py``).

A description's clauses (``predicates.py``'s form, plus one kind):
``{"f": 0, "has": [labels]}`` (the row carries any of them) and
``{"f": date column, "lo": a, "hi": b}`` (inclusive days).
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

import plugin

MAX_LABELS = 8
START = 24
corpora = plugin.load(Path(__file__).resolve().parents[1] / "corpora"
                      / "labels_and_date.py")


def _popular(corpus) -> tuple[np.ndarray, np.ndarray]:
    """The (n, n_labels) label matrix and the labels by falling count,
    computed once per corpus."""
    cached = getattr(corpus, "_popular", None)
    if cached is None:
        labels = corpora.label_matrix(corpus)
        order = np.argsort(-labels.sum(axis=0), kind="stable")
        cached = corpus._popular = (labels, order)
    return cached


def _fill(labels: np.ndarray, order, goal: float):
    """Labels in ``order`` whose union covers as close to ``goal`` rows as
    it can from below (the first one whatever it covers), at most
    MAX_LABELS of them; and the rows they cover."""
    chosen, covered = [], np.zeros(labels.shape[0], bool)
    for lab in order:
        more = covered | labels[:, lab]
        if chosen and more.sum() > goal:
            continue
        chosen.append(int(lab))
        covered = more
        if len(chosen) == MAX_LABELS or covered.sum() >= goal:
            break
    return chosen, covered


def draw(corpus, target: float, rng: np.random.Generator) -> dict:
    labels, order = _popular(corpus)
    n = corpus.n
    want = max(int(round(target * n)), 1)
    chosen, covered = _fill(labels, order[int(rng.integers(START)):],
                            np.sqrt(target) * n)
    if covered.sum() < want:        # the less popular labels fall short
        chosen, covered = _fill(labels, order, np.sqrt(target) * n)
    date = corpus.metadata.shape[1] - 1
    days = np.sort(corpus.metadata[:n, date][covered])
    want = min(want, days.size)
    start = int(rng.integers(days.size - want + 1))
    return {"any": [[{"f": 0, "has": sorted(chosen)},
                     {"f": date, "lo": int(days[start]),
                      "hi": int(days[start + want - 1])}]]}
