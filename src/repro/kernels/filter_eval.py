"""Pallas TPU kernel: predicate evaluation -> packed bitmap.

Turns (metadata codes x predicate) into the per-query filter bitmap consumed
by the batched engine. The paper's per-node O(|S|) dict lookup becomes a
corpus-sweep VPU pass (DESIGN.md §3). The layout is chosen for the TPU's
(8, 128) vector tiles:

* the metadata is swept as a (F, 32, W) code table whose element
  ``[f, b, w]`` is field f of corpus row ``32*w + b``, so one corpus tile is
  a lane-dense (32, tw) block per field, a clause picks its field by a
  dynamic index on the untiled leading dim, and the 32 rows of one output
  word sit in one lane: packing is a shift and a sum over sublanes;
* the clause tables (field ids, value-bitmap words, interval bounds,
  live-disjunct counts) are per-query scalars, read from SMEM one query
  tile at a time; value-set membership walks the clause's bitmap words,
  testing ``code >> 5 == w`` and bit ``code & 31`` of word w, so nothing is
  gathered and no vocabulary-wide table is ever expanded.

Conjunctive (Q, C) tables are the D = 1 case of the disjunctive (Q, D, C)
form (DESIGN.md §8): the kernel ORs the per-disjunct conjunctions, skipping
the dead-disjunct tail by the per-query live count. ``bounds`` adds the
interval test (``lo <= hi`` marks an interval clause) to the same program.

``tag_cols`` (static: the schema's tag-set fields, ``core.tags``) adds the
tag test: a clause on a tag-set field passes a row whose label words share
a bit with the clause's bitmap words, ``(row word w & clause word w) != 0``
for some w, reading word w from column ``field + w``. With no tag-set
field the program is the value-set/interval one alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# disjunct-table sentinel (shared with the packers in core.device_atlas):
# a fields entry of -1 is an inactive clause inside a live disjunct
# (conjunction over nothing = pass), DEAD_DISJUNCT marks the padding tail
# of dead disjuncts (contributes False to the union). Live disjuncts pack
# densely from 0, so the per-query count is recoverable from the table.
DEAD_DISJUNCT = -2

# rows per packed output word, and the TPU lane width a multi-tile sweep's
# words-per-tile must be a multiple of
_WORD = 32
_LANES = 128
# XLA tiles a long 1D s32 array by 1024 elements; an SMEM block of such an
# array must cover whole tiles
_SMEM_TILE = 1024
# queries per grid step: the output block's sublane height
_Q_TILE = 8


def table_n_disj(fields: jax.Array) -> jax.Array:
    """(Q, D, C) fields table -> (Q,) i32 live-disjunct counts (jittable)."""
    return jnp.sum(fields[:, :, 0] > DEAD_DISJUNCT, axis=1).astype(jnp.int32)


def _tile_words(tn: int, n_words: int, lane_aligned: bool) -> int:
    """Output words per corpus tile. ``tn`` is static under jit, so a bad
    knob fails at trace time with its name instead of a Mosaic error: one
    tile covering the whole corpus may have any width (its block equals the
    array); for Mosaic (``lane_aligned``) a sweep over several tiles needs
    lane-aligned tiles, while the interpreter takes any 32-row multiple, so
    CPU tests can sweep many small tiles."""
    if tn <= 0 or tn % _WORD != 0:
        raise ValueError(
            f"KernelConfig.filter_tile (tn) must be a positive multiple of "
            f"32 for the bitmap pack; got {tn}")
    tw = tn // _WORD
    if tw >= n_words:
        return n_words
    if lane_aligned and tw % _LANES != 0:
        raise ValueError(
            f"KernelConfig.filter_tile (tn) must be a multiple of "
            f"{_WORD * _LANES} rows when the corpus ({32 * n_words} rows) "
            f"spans several tiles; got {tn}")
    return tw


def _kernel(fields_ref, words_ref, bounds_ref, ndisj_ref, meta_ref, out_ref,
            ok_ref, conj_ref, hit_ref, *, qt: int, n_disjuncts: int,
            n_clauses: int, n_vwords: int, has_bounds: bool,
            tag_cols: tuple[int, ...] = ()):
    """One (corpus tile, query tile) step. Scalar tables are flattened per
    query: fields[q*D*C + d*C + c], words[(q*D*C + d*C + c)*Wv + w],
    bounds[(q*D*C + d*C + c)*2 + {0, 1}], ndisj[q]; ``q`` is the query's
    row within this step's SMEM block. Dead disjuncts, inactive clauses and
    all-zero bitmap words are skipped by scalar branches, so a clause costs
    one vector pass per value word it actually uses. The pass state lives
    in int32 VMEM scratch (Mosaic selects no bool vectors). A clause whose
    field is one of ``tag_cols`` takes the tag test instead, one vector
    pass per non-zero clause word as well."""
    n_cols, tw = meta_ref.shape[0], meta_ref.shape[2]
    dc = n_disjuncts * n_clauses
    shift = jax.lax.broadcasted_iota(jnp.int32, (_WORD, tw), 0)

    def clause(slot):
        col = meta_ref[jnp.maximum(fields_ref[slot], 0)]      # (32, tw)
        hi5 = jax.lax.shift_right_arithmetic(col, 5)
        lo5 = col & 31
        hit_ref[...] = jnp.zeros_like(col)

        def word_step(w, carry):
            word = words_ref[slot * n_vwords + w]

            @pl.when(word != 0)
            def _():
                bit = jax.lax.shift_right_logical(
                    jnp.full(col.shape, word, jnp.int32), lo5) & 1
                hit_ref[...] |= jnp.where(hi5 == w, bit, 0)
            return carry

        jax.lax.fori_loop(0, n_vwords, word_step, 0)
        if has_bounds:
            lo = bounds_ref[2 * slot]
            hi = bounds_ref[2 * slot + 1]

            @pl.when(lo <= hi)
            def _():
                iv = (col >= 0) & (col >= lo) & (col <= hi)
                hit_ref[...] = iv.astype(jnp.int32)
        conj_ref[...] &= hit_ref[...]

    def tag_clause(slot):
        field = fields_ref[slot]
        hit_ref[...] = jnp.zeros((_WORD, tw), jnp.int32)

        def word_step(w, carry):
            word = words_ref[slot * n_vwords + w]

            @pl.when(word != 0)
            def _():
                row = meta_ref[jnp.minimum(field + w, n_cols - 1)]
                hit_ref[...] |= ((row & word) != 0).astype(jnp.int32)
            return carry

        jax.lax.fori_loop(0, n_vwords, word_step, 0)
        conj_ref[...] &= hit_ref[...]

    def any_clause(slot):
        field = fields_ref[slot]
        if not tag_cols:
            pl.when(field >= 0)(lambda: clause(slot))
            return
        is_tag = functools.reduce(jnp.logical_or,
                                  [field == c for c in tag_cols])
        pl.when((field >= 0) & is_tag)(lambda: tag_clause(slot))
        pl.when((field >= 0) & ~is_tag)(lambda: clause(slot))

    def query(j, carry):
        ok_ref[...] = jnp.zeros_like(ok_ref)
        for dd in range(n_disjuncts):

            @pl.when(dd < ndisj_ref[j])
            def _():
                conj_ref[...] = jnp.ones_like(conj_ref)
                for c in range(n_clauses):
                    any_clause(j * dc + dd * n_clauses + c)
                ok_ref[...] |= conj_ref[...]
        out_ref[pl.ds(j, 1), :] = jnp.sum(ok_ref[...] << shift, axis=0,
                                          keepdims=True)     # (1, tw)
        return carry

    jax.lax.fori_loop(0, qt, query, 0)


@functools.partial(jax.jit, static_argnames=("tn", "interpret", "tag_cols"))
def filter_eval_batch(metadata, fields, allowed, n_disj=None, bounds=None, *,
                      tn: int, interpret: bool = True,
                      tag_cols: tuple[int, ...] = ()):
    """Batched corpus sweep: metadata (n, F) i32; fields (Q, C) i32 (-1
    inactive); allowed (Q, C, ceil(v_cap/32)) uint32 value bitmaps (the
    ``pack_predicates`` clause-table format) -> (Q, ceil(n/32)) uint32.

    Disjunctive form (``pack_dnf`` tables): fields (Q, D, C) i32 (-2 = dead
    disjunct) with allowed (Q, D, C, ceil(v_cap/32)) and n_disj (Q,) i32
    live-disjunct counts (derived from the sentinel when omitted); the
    per-query bitmap is the union over live disjuncts of their conjunctive
    bitmaps, still one corpus sweep.

    Interval form: ``bounds`` (Q, D, C, 2) i32 rides along the disjunctive
    tables; a clause row with ``lo <= hi`` is evaluated as the inclusive
    interval test instead of bitmap membership (its bitmap row is zero).

    Tag-set fields: a clause whose field is in ``tag_cols`` (the first
    metadata column of each tag-set field) passes a row whose label words
    share a bit with the clause's bitmap words.

    The grid is (corpus tiles, query tiles) with queries on the fast axis,
    so a corpus tile's code block stays resident while the query tiles'
    few-KB scalar tables stream through SMEM. Pad bits beyond n are forced
    to 0 so the output matches ``ref.filter_eval_batch`` bit-exactly even
    for unconstrained predicates."""
    n, n_fields = metadata.shape
    q_n = fields.shape[0]
    if fields.ndim == 2:
        fields, allowed = fields[:, None], allowed[:, None]
        n_disj = jnp.ones((q_n,), jnp.int32)
    elif n_disj is None:
        n_disj = table_n_disj(fields)
    d_n, c_n, wv = allowed.shape[1], allowed.shape[2], allowed.shape[3]
    n_words = (n + _WORD - 1) // _WORD
    tw = _tile_words(tn, n_words, lane_aligned=not interpret)
    w_pad = -(-n_words // tw) * tw
    qt = min(_Q_TILE, q_n)
    q_pad = -(-q_n // qt) * qt
    # padded rows get code -1 -> fail all active clauses -> bit 0
    meta_p = jnp.pad(metadata, ((0, _WORD * w_pad - n), (0, 0)),
                     constant_values=-1)
    codes = meta_p.T.reshape(n_fields, w_pad, _WORD).transpose(0, 2, 1)
    n_qt = q_pad // qt

    def smem_table(tab, fill=0):
        """(Q, per_q) scalars -> flat SMEM table of one tile-aligned block
        per query tile, and that block's spec."""
        per_tile = qt * tab.shape[1]
        blk = -(-per_tile // _SMEM_TILE) * _SMEM_TILE
        tab = jnp.pad(tab, ((0, q_pad - q_n), (0, 0)), constant_values=fill)
        tab = jnp.pad(tab.reshape(n_qt, per_tile),
                      ((0, 0), (0, blk - per_tile))).reshape(-1)
        return tab, pl.BlockSpec((blk,), lambda i, q: (q,),
                                 memory_space=pltpu.SMEM)

    has_bounds = bounds is not None
    tables = [
        smem_table(fields.reshape(q_n, -1), fill=-1),
        smem_table(jax.lax.bitcast_convert_type(allowed, jnp.int32)
                   .reshape(q_n, -1)),
        smem_table(bounds.astype(jnp.int32).reshape(q_n, -1) if has_bounds
                   else jnp.zeros((q_n, 2), jnp.int32)),
        # padded queries get no live disjunct -> all-zero rows
        smem_table(n_disj.astype(jnp.int32).reshape(q_n, 1)),
    ]

    out = pl.pallas_call(
        functools.partial(_kernel, qt=qt, n_disjuncts=d_n, n_clauses=c_n,
                          n_vwords=wv, has_bounds=has_bounds,
                          tag_cols=tuple(tag_cols)),
        grid=(w_pad // tw, n_qt),
        in_specs=[spec for _, spec in tables] + [
            pl.BlockSpec((n_fields, _WORD, tw), lambda i, q: (0, 0, i))],
        out_specs=pl.BlockSpec((qt, tw), lambda i, q: (q, i)),
        out_shape=jax.ShapeDtypeStruct((q_pad, w_pad), jnp.int32),
        scratch_shapes=[pltpu.VMEM((_WORD, tw), jnp.int32)] * 3,
        interpret=interpret,
    )(*[tab for tab, _ in tables], codes)
    out = jax.lax.bitcast_convert_type(out[:q_n, :n_words], jnp.uint32)
    tail = n - _WORD * (n_words - 1)
    if tail < _WORD:  # zero pad bits: an unconstrained predicate passes pad rows
        out = out.at[:, n_words - 1].set(out[:, n_words - 1]
                                         & jnp.uint32((1 << tail) - 1))
    return out


def filter_eval(metadata, fields, allowed, *, tn: int,
                interpret: bool = True):
    """Single-query form: metadata (n, F) i32; fields (C,) i32 (-1
    inactive); allowed (C, V_cap) uint8 dense table -> (ceil(n/32),)
    uint32 — the Q = 1 case of ``filter_eval_batch``."""
    c_n, v_cap = allowed.shape
    dense = jnp.pad(allowed > 0, ((0, 0), (0, (-v_cap) % _WORD)))
    words = jnp.sum(dense.reshape(c_n, -1, _WORD).astype(jnp.uint32)
                    << jnp.arange(_WORD, dtype=jnp.uint32), axis=-1,
                    dtype=jnp.uint32)
    return filter_eval_batch(metadata, fields[None], words[None], tn=tn,
                             interpret=interpret)[0]
