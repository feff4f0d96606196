"""The plain reference: brute-force filtered top-k, and the numbers that
decide ``correct``.

For every answered query the reference scores all rows that were live when
its batch was dispatched with a full-f32 (``precision=HIGHEST``) cosine on
the device, masks them with its own numpy evaluation of the query's
predicate description (``predicates.mask``), and takes the exact top-k.
Nothing here imports the program or reads what it made: the rows come
from ``datagen``, the masks from the descriptions.

Numbers compared (each with its limit, in ``checks``):

* ``wrong_ids``: returned ids that fail their predicate, were not live at
  the batch's generation, or repeat within one answer. Exact: limit 0.
* ``empty_answers``: queries with a passing row that got no id, or no
  answer at all. Exact: limit 0 (the walk seeds from passing rows, so
  an answer is never empty while a row passes).
* ``order_gap``: the largest step by which the reference's distance falls
  along an answer's returned order. A program that ranks by full-f32 scores
  reads rounding (about 1e-7); one that ranks by bf16 scores reads ~1e-4.
* ``short_answer_share``: the share of queries whose answer holds fewer
  than ``min(k, rows passing)`` ids. The walk is approximate and may stop
  short now and then; an answer cut short where it is produced shows on
  every query.

``recall`` (the mean recall@k against the exact top-k) is an end-to-end
metric, not a check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 256
CHECKS = ("wrong_ids", "empty_answers", "order_gap",
          "short_answer_share")


@functools.partial(jax.jit, static_argnames=("k", "low_precision"))
def _topk(vectors, q, allowed, *, k, low_precision=False):
    """Exact filtered top-k by cosine distance: (B, k) ids and distances,
    -1 / inf past the rows that pass. ``low_precision`` ranks by scores of
    bf16 operands: the control, not the reference."""
    if low_precision:
        s = jnp.einsum("qd,nd->qn", q.astype(jnp.bfloat16),
                       vectors.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    else:
        s = jnp.einsum("qd,nd->qn", q, vectors, precision=HIGHEST)
    s = jnp.where(allowed, s, -jnp.inf)
    v, i = jax.lax.top_k(s, k)
    ok = jnp.isfinite(v)
    return jnp.where(ok, i, -1), jnp.where(ok, 1.0 - v, jnp.inf)


@jax.jit
def _dist(vectors, q, ids):
    """Full-f32 cosine distance of each (query, returned id); ids -1 give
    inf."""
    rows = vectors[jnp.maximum(ids, 0)]
    d = 1.0 - jnp.einsum("qkd,qd->qk", rows, q, precision=HIGHEST)
    return jnp.where(ids >= 0, d, jnp.inf)


def _pad_ids(answers, k: int) -> np.ndarray:
    out = np.full((len(answers), k), -1, np.int64)
    for j, a in enumerate(answers):
        a = np.asarray(a, np.int64)[:k]
        out[j, :a.size] = a
    return out


def _blocks(n: int):
    for lo in range(0, n, BLOCK):
        yield lo, min(lo + BLOCK, n)


def _full(x: np.ndarray, fill=0) -> jax.Array:
    """``x`` padded to BLOCK rows, so that every block runs one program."""
    pad = [(0, BLOCK - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.asarray(np.pad(x, pad, constant_values=fill))


def exact_answers(vectors, queries: np.ndarray, allowed_block, k: int,
                  low_precision: bool = False) -> list:
    """The exact filtered top-k of every query as a list of id arrays.
    ``allowed_block(lo, hi)`` gives the (hi - lo, rows) mask of the rows
    queries ``lo:hi`` may return. With ``low_precision`` these are the
    control's answers."""
    out = []
    for lo, hi in _blocks(len(queries)):
        ids, _ = _topk(vectors, _full(queries[lo:hi]),
                       _full(allowed_block(lo, hi), False), k=k,
                       low_precision=low_precision)
        out.extend(i[i >= 0] for i in np.asarray(ids)[:hi - lo])
    return out


def judge(vectors, queries: np.ndarray, answers: list, allowed_block,
          k: int) -> dict:
    """Compare ``answers`` (one id array, or None where none came, per
    query) with the reference. ``allowed_block(lo, hi)`` gives the mask of
    the rows queries ``lo:hi`` could rightly return: passing the predicate
    and live at the query's generation. Returns the checks' numbers and
    the recall."""
    wrong = short = empty = 0
    gap = 0.0
    recalls = []
    for lo, hi in _blocks(len(queries)):
        allowed = allowed_block(lo, hi)
        q = _full(queries[lo:hi])
        exact, _ = _topk(vectors, q, _full(allowed, False), k=k)
        exact = np.asarray(exact)
        ids = _pad_ids([a if a is not None else [] for a in answers[lo:hi]],
                       k)
        d = np.asarray(_dist(vectors, q, _full(ids, -1).astype(jnp.int32)))
        for j in range(hi - lo):
            a = answers[lo + j]
            want = min(k, int(allowed[j].sum()))
            if a is None or len(a) == 0:
                empty += want > 0
                short += want > 0
                recalls.append(0.0 if want else 1.0)
                continue
            a = np.asarray(a, np.int64)
            inside = (a >= 0) & (a < allowed.shape[1])
            ok = np.zeros(a.size, bool)
            ok[inside] = allowed[j, a[inside]]
            wrong += int((~ok).sum()) + (a.size - np.unique(a).size)
            short += int(a.size < want)
            if a.size > 1:
                steps = d[j, :a.size - 1] - d[j, 1:a.size]
                gap = max(gap, float(np.max(steps)))
            truth = exact[j][exact[j] >= 0]
            recalls.append(len(np.intersect1d(a, truth)) / want
                           if want else 1.0)
    return {"wrong_ids": wrong, "empty_answers": empty, "order_gap": gap,
            "short_answers": short,
            "short_answer_share": short / len(queries) if len(queries)
            else 0.0,
            "recall": float(np.mean(recalls)) if recalls else 0.0}
