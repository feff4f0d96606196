"""A two-field conjunction of value sets: field a takes its most frequent
values up to ~sqrt(target) of the rows, field b then values inside that
set up to ``target``; of 16 random field pairs, the one whose selectivity
lands closest to ``target``. It fits a configuration with categorical
fields (``metadata.kind`` ``categorical``)."""
from __future__ import annotations

import numpy as np

PAIRS = 16


def _fill(counts: np.ndarray, goal: float) -> list[int]:
    """Values, most frequent first, whose counts sum as close to ``goal``
    as they can from below; the least-overshooting value when none fits."""
    vals, acc = [], 0
    for v in np.argsort(-counts, kind="stable"):
        c = int(counts[v])
        if c and acc + c <= goal:
            vals.append(int(v))
            acc += c
    if not vals:
        pos = np.nonzero(counts)[0]
        vals = [int(pos[np.argmin(counts[pos])])]
    return vals


def draw(corpus, target: float, rng: np.random.Generator) -> dict:
    metadata = corpus.metadata[:corpus.n]
    vocab = corpus.vocab_sizes
    n, n_fields = metadata.shape
    best = None
    for _ in range(PAIRS):
        fa, fb = (int(f) for f in rng.choice(n_fields, 2, replace=False))
        a, b = metadata[:, fa], metadata[:, fb]
        va, vb = vocab[fa], vocab[fb]
        vals_a = _fill(np.bincount(a[a >= 0], minlength=va),
                       np.sqrt(target) * n)
        both = (a >= 0) & (b >= 0)
        joint = np.bincount(a[both] * vb + b[both],
                            minlength=va * vb).reshape(va, vb)
        counts_b = joint[vals_a].sum(0)
        vals_b = _fill(counts_b, target * n)
        acc = int(counts_b[vals_b].sum())
        err = abs(np.log(max(acc, 1) / (target * n)))
        if best is None or err < best[0]:
            best = (err, [{"f": fa, "in": sorted(vals_a)},
                          {"f": fb, "in": sorted(vals_b)}])
    return {"any": [best[1]]}
