"""Pure-jnp oracles for the kernels in this package, and the jnp ops the
search path runs on every platform.

Semantics contract:
* fiber_expand_walk (the walk's expansion op; no kernel — the same XLA
  gather + full-f32 dot on every platform): sims[q, r] = q_vec[q] .
  X[ids[q, r]], TWO outputs per (q, r) — sims masked only by id validity
  (the walk's traversal distances) and sims additionally masked by the
  filter bit (the result-queue candidates).
* filter_eval: packed uint32 bitmap of conjunctive predicate over int codes;
  code -1 (unpopulated) fails any clause on that field.
* filter_eval_batch: filter_eval for Q queries at once, consuming the
  pack_predicates clause tables (fields (Q, C) i32; allowed (Q, C, Wv)
  uint32 value bitmaps) -> (Q, ceil(n/32)) uint32. Disjunctive (Q, D, C)
  pack_dnf tables OR the per-disjunct conjunctive bitmaps (dead-disjunct
  padding, marked with field sentinel -2, contributes nothing). A clause
  on a tag-set field (its first column in ``tag_cols``) passes a row whose
  label words share a bit with the clause's bitmap words (``tag_ok``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = jnp.float32(-jnp.inf)


def bitmap_get(bitmap: jax.Array, idx: jax.Array) -> jax.Array:
    """bitmap: (..., n_words) uint32; idx: (...,) int32 -> bool."""
    word = jnp.take_along_axis(
        bitmap, (idx >> 5).astype(jnp.int32)[..., None] if idx.ndim == bitmap.ndim - 1
        else (idx >> 5).astype(jnp.int32), axis=-1)
    if word.ndim > idx.ndim:
        word = word[..., 0]
    return ((word >> (idx & 31).astype(jnp.uint32)) & 1).astype(bool)


def fiber_expand_walk(q_vecs: jax.Array, corpus: jax.Array, ids: jax.Array,
                      bitmap: jax.Array):
    """q_vecs (Q, d); corpus (n, d); ids (Q, R) i32 (-1 pad);
    bitmap (Q, n_words) uint32. Returns (sims, sims_pass), both (Q, R) f32:
    ``sims`` is -inf only for padded ids, ``sims_pass`` additionally -inf
    where the id's filter bit is 0."""
    safe = jnp.maximum(ids, 0)
    rows = corpus[safe].astype(jnp.float32)            # (Q, R, d)
    sims = jnp.einsum("qrd,qd->qr", rows, q_vecs.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    words = jnp.take_along_axis(bitmap, (safe >> 5).astype(jnp.int32), axis=1)
    bits = ((words >> (safe & 31).astype(jnp.uint32)) & 1).astype(bool)
    valid = ids >= 0
    return jnp.where(valid, sims, NEG), jnp.where(valid & bits, sims, NEG)


def filter_eval(metadata: jax.Array, fields: jax.Array, allowed: jax.Array):
    """metadata (n, F) i32; fields (C,) i32 (-1 = inactive clause);
    allowed (C, V_cap) uint8 (1 = value allowed). Returns (ceil(n/32),)
    uint32 packed bitmap (row-major bit i -> point i)."""
    n = metadata.shape[0]
    v_cap = allowed.shape[1]
    ok = jnp.ones((n,), bool)
    for c in range(fields.shape[0]):
        f = fields[c]
        active = f >= 0
        vals = metadata[:, jnp.maximum(f, 0)]
        in_range = (vals >= 0) & (vals < v_cap)
        hit = allowed[c, jnp.clip(vals, 0, v_cap - 1)] > 0
        clause_ok = in_range & hit
        ok = jnp.where(active, ok & clause_ok, ok)
    pad = (-n) % 32
    okp = jnp.pad(ok, (0, pad))
    bits = okp.reshape(-1, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (bits * weights).sum(axis=1).astype(jnp.uint32)


def tag_ok(metadata: jax.Array, field: jax.Array, words: jax.Array):
    """The tag-set clause oracle: metadata (n, F) i32; field (Q,) i32, the
    first column of a tag-set field; words (Q, Wv) uint32, the clause's
    label bitmap. (Q, n) bool: the row's label word w (column field + w)
    shares a bit with clause word w, for some w. Clause words past the
    field's own words are zero (labels lie below its V), so the columns
    they would read are never tested."""
    n_cols = metadata.shape[1]
    ok = jnp.zeros((field.shape[0], metadata.shape[0]), bool)
    for w in range(words.shape[1]):
        col = jnp.minimum(jnp.maximum(field, 0) + w, n_cols - 1)
        row = metadata[:, col].T.astype(jnp.uint32)             # (Q, n)
        ok = ok | ((row & words[:, w][:, None]) != 0)
    return ok


def _conj_ok(metadata: jax.Array, fields: jax.Array, allowed: jax.Array,
             bounds: jax.Array | None = None, tag_cols: tuple = ()):
    """(Q, n) bool conjunction for one clause-table slice: fields (Q, C)
    i32, allowed (Q, C, Wv) uint32 value bitmaps, optional bounds (Q, C, 2)
    i32 interval rows (a clause with lo <= hi is the two-comparison
    interval test; its bitmap row is zero). A clause whose field is one of
    ``tag_cols`` is the tag test (``tag_ok``)."""
    n = metadata.shape[0]
    q_n, n_clauses = fields.shape
    v_cap = allowed.shape[-1] * 32
    ok = jnp.ones((q_n, n), bool)
    for c in range(n_clauses):
        f = fields[:, c]                                        # (Q,)
        vals = metadata[:, jnp.maximum(f, 0)].T                 # (Q, n)
        safe = jnp.clip(vals, 0, v_cap - 1)
        words = jnp.take_along_axis(allowed[:, c, :],
                                    (safe >> 5).astype(jnp.int32), axis=1)
        bit = ((words >> (safe & 31).astype(jnp.uint32)) & 1).astype(bool)
        clause_ok = bit & (vals >= 0) & (vals < v_cap)
        if bounds is not None:
            lo = bounds[:, c, 0][:, None]                       # (Q, 1)
            hi = bounds[:, c, 1][:, None]
            iv_ok = (vals >= 0) & (vals >= lo) & (vals <= hi)
            clause_ok = jnp.where(lo <= hi, iv_ok, clause_ok)
        if tag_cols:
            is_tag = jnp.isin(f, jnp.asarray(tag_cols, jnp.int32))
            clause_ok = jnp.where(is_tag[:, None],
                                  tag_ok(metadata, f, allowed[:, c, :]),
                                  clause_ok)
        ok = jnp.where((f >= 0)[:, None], ok & clause_ok, ok)
    return ok


def filter_eval_batch(metadata: jax.Array, fields: jax.Array,
                      allowed: jax.Array, n_disj: jax.Array | None = None,
                      bounds: jax.Array | None = None,
                      tag_cols: tuple = ()):
    """metadata (n, F) i32; fields (Q, C) i32 (-1 = inactive clause);
    allowed (Q, C, ceil(v_cap/32)) uint32 value bitmaps (the
    ``pack_predicates`` clause-table format). Returns (Q, ceil(n/32))
    uint32 packed pass bitmaps; pad bits beyond n are 0.

    Disjunctive form (the ``pack_dnf`` tables): fields (Q, D, C) i32
    (-2 = dead-disjunct padding), allowed (Q, D, C, Wv), n_disj (Q,) i32
    live-disjunct counts (derived from the sentinel when omitted); the
    bitmap is the union over live disjuncts of conjunctive bitmaps.
    Optional bounds (Q, D, C, 2) i32 marks interval clauses (lo <= hi).
    ``tag_cols`` names the tag-set fields by their first column."""
    n = metadata.shape[0]
    q_n = fields.shape[0]
    if fields.ndim == 3:
        D = fields.shape[1]
        if n_disj is None:
            from repro.kernels.filter_eval import table_n_disj
            n_disj = table_n_disj(fields)
        ok = jnp.zeros((q_n, n), bool)
        for d in range(D):
            ok_d = _conj_ok(metadata, fields[:, d, :], allowed[:, d, :, :],
                            None if bounds is None else bounds[:, d, :, :],
                            tag_cols)
            ok = ok | (ok_d & (d < n_disj)[:, None])
    else:
        ok = _conj_ok(metadata, fields, allowed, tag_cols=tag_cols)
    pad = (-n) % 32
    okp = jnp.pad(ok, ((0, 0), (0, pad)))
    bits = okp.reshape(q_n, -1, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (bits * weights).sum(axis=-1).astype(jnp.uint32)
