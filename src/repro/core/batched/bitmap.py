"""Packed uint32 bitmaps for the lockstep walk state (DESIGN.md §3).

The batched walk used to carry three dense ``(Q, n)`` bool masks (visited,
in-results, filter-pass) — ~256 MB of mask state for a 256-query batch over
a million-point corpus. Packing each mask into ``(Q, ceil(n/32)) uint32``
words cuts that memory and its per-hop scatter/gather traffic 8×, and is
the same layout ``filter_eval`` already emits and the Pallas kernels probe:
bit ``i`` of word ``w`` holds entry ``32*w + i``.

A node may own two bits. At a ``stride`` of s (1 or 2) bits per node, node
i's slot is bits ``s*(i % (32/s))`` .. ``+ s-1`` of word ``i // (32/s)``.
The walk's state map (``state_map``) uses stride 2: bit ``VISITED`` of a
slot is the visited bit, bit ``PASS`` the filter bit, so one word gather
per neighbour reads both.

All helpers are jittable fixed-shape ops. ``set_bits`` is a scatter-OR
built from scatter-add: it dedupes indices within a row and drops
already-set bits first, so ``add == or`` exactly (property-tested against
bool-mask oracles in ``tests/test_bitmap.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_ONE = jnp.uint32(1)
# the bits of a node's slot in the walk's state map (stride STATE_STRIDE)
VISITED, PASS = 0, 1
STATE_STRIDE = 2


def n_words(n: int, stride: int = 1) -> int:
    """Words needed to hold ``n`` slots of ``stride`` bits."""
    return -(-n * stride // 32)


def _slot_log2(stride: int) -> int:
    """log2 of the slots a word holds at ``stride`` (1 or 2 bits a node)."""
    return {1: 5, 2: 4}[stride]


def pack_bits(mask: jax.Array) -> jax.Array:
    """``(..., n) bool -> (..., ceil(n/32)) uint32``; bit i of word w is
    entry 32*w + i. Pad bits (beyond n) are 0."""
    *lead, n = mask.shape
    pad = (-n) % 32
    m = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, pad)])
    m = m.reshape(*lead, -1, 32).astype(jnp.uint32)
    return (m * (_ONE << jnp.arange(32, dtype=jnp.uint32))).sum(-1)


def unpack_bits(bm: jax.Array, n: int) -> jax.Array:
    """``(..., W) uint32 -> (..., n) bool`` (inverse of ``pack_bits``)."""
    bits = (bm[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & _ONE
    flat = bits.reshape(*bm.shape[:-1], bm.shape[-1] * 32)
    return flat[..., :n].astype(bool)


def gather_words(bm: jax.Array, idx: jax.Array, stride: int = 1):
    """The word that holds each node's slot: ``bm (Q, W) uint32``,
    ``idx (Q, m) int32`` -> ``(Q, m) uint32`` (one gather; a negative
    index reads node 0's word)."""
    safe = jnp.maximum(idx, 0)
    return jnp.take_along_axis(bm, safe >> _slot_log2(stride), axis=1)


def bit_of(words: jax.Array, idx: jax.Array, stride: int = 1,
           bit: int = 0) -> jax.Array:
    """Bit ``bit`` of each node's slot, read from the words
    ``gather_words`` gave for ``idx`` -> ``(Q, m) bool``. Negative indices
    read False (pad convention)."""
    safe = jnp.maximum(idx, 0)
    lo = safe & ((1 << _slot_log2(stride)) - 1)
    shift = (lo * stride + bit).astype(jnp.uint32)
    return ((words >> shift) & _ONE).astype(bool) & (idx >= 0)


def set_bits(bm: jax.Array, idx: jax.Array, on: jax.Array, stride: int = 1,
             words: jax.Array | None = None) -> jax.Array:
    """Scatter-OR: set the first bit of node ``idx[q, j]``'s slot in row q
    where ``on[q, j]``; the slot's other bits are untouched.

    Negative indices are ignored. Safe for duplicate indices within a row
    and for bits that are already set: only the first ``on`` occurrence of
    a not-yet-set index contributes its bit to its word, so the underlying
    scatter-add equals a bitwise OR. ``words`` are the slots' words as
    ``gather_words(bm, idx, stride)`` reads them, where the caller already
    holds them: the already-set test then gathers nothing.
    """
    q, m = idx.shape
    if words is None:
        words = gather_words(bm, idx, stride)
    safe = jnp.maximum(idx, 0)
    on = on & (idx >= 0) & ~bit_of(words, idx, stride)
    # dup[q, j] <=> an earlier position i<j carries the same index with on
    eq = safe[:, :, None] == safe[:, None, :]            # [q, i, j]
    earlier = jnp.arange(m)[:, None] < jnp.arange(m)[None, :]
    dup = (eq & on[:, :, None] & earlier[None]).any(axis=1)
    log2 = _slot_log2(stride)
    shift = ((safe & ((1 << log2) - 1)) * stride).astype(jnp.uint32)
    add = jnp.where(on & ~dup, _ONE << shift, jnp.uint32(0))
    return bm.at[jnp.arange(q)[:, None], safe >> log2].add(add)


def _spread16(x: jax.Array) -> jax.Array:
    """The low 16 bits of each word moved to the even bit positions."""
    x = x & jnp.uint32(0x0000FFFF)
    x = (x | (x << 8)) & jnp.uint32(0x00FF00FF)
    x = (x | (x << 4)) & jnp.uint32(0x0F0F0F0F)
    x = (x | (x << 2)) & jnp.uint32(0x33333333)
    return (x | (x << 1)) & jnp.uint32(0x55555555)


def _gather16(x: jax.Array) -> jax.Array:
    """The even bits of each word moved to the low 16 (``_spread16``'s
    inverse)."""
    x = x & jnp.uint32(0x55555555)
    x = (x | (x >> 1)) & jnp.uint32(0x33333333)
    x = (x | (x >> 2)) & jnp.uint32(0x0F0F0F0F)
    x = (x | (x >> 4)) & jnp.uint32(0x00FF00FF)
    return (x | (x >> 8)) & jnp.uint32(0x0000FFFF)


def state_map(pass_bm: jax.Array, n: int) -> jax.Array:
    """The walk's state map from packed pass bitmaps: ``(Q, ceil(n/32))
    -> (Q, ceil(n/16)) uint32`` at stride ``STATE_STRIDE``, each node's
    ``PASS`` bit its pass bit, every ``VISITED`` bit clear."""
    q, w = pass_bm.shape
    halves = jnp.stack([pass_bm, pass_bm >> 16], axis=-1).reshape(q, 2 * w)
    return _spread16(halves[:, :n_words(n, STATE_STRIDE)]) << PASS


def visited_bits(sm: jax.Array, n: int) -> jax.Array:
    """The ``VISITED`` bits of a state map, packed: ``(Q, ceil(n/16)) ->
    (Q, ceil(n/32)) uint32`` (``pack_bits``' layout)."""
    w = n_words(n)
    lo16 = _gather16(sm >> VISITED)
    lo16 = jnp.pad(lo16, ((0, 0), (0, 2 * w - lo16.shape[1])))
    pairs = lo16.reshape(sm.shape[0], w, 2)
    return pairs[..., 0] | (pairs[..., 1] << 16)


def popcount(bm: jax.Array) -> jax.Array:
    """``(..., W) uint32 -> (...,) int32`` total set bits (SWAR per word)."""
    x = bm
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    per_word = (x * jnp.uint32(0x01010101)) >> 24
    return per_word.astype(jnp.int32).sum(-1)
