"""Attention: chunked (flash-style) softmax for train/prefill, plain decode
attention over a cache, and a shard_map flash-decode combine for
sequence-sharded caches (long-context serving).

The chunked form never materializes the (S, S) score matrix: an outer scan
over query blocks and an inner scan over KV blocks carry running
(max, sum, acc) — the standard online-softmax recurrence, which is also the
memory shape a TPU flash kernel would use (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import settings

NEG_INF = -1e30


def _block_mask(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
                window) -> jax.Array:
    """(qc, kc) bool mask. window may be a traced scalar; <=0 means
    unbounded lookback (full attention)."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    win = jnp.where(jnp.asarray(window) > 0, jnp.asarray(window), 1 << 30)
    m &= q_pos[:, None] - k_pos[None, :] < win
    return m


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      q_chunk: int = 512, kv_chunk: int = 1024,
                      q_offset: int = 0) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd); GQA via H % KV == 0.

    ``q_offset``: absolute position of q[0] (for prefill continuation).
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    if settings.UNROLL_SCANS:  # accounting mode: coarse blocks, same FLOPs
        q_chunk, kv_chunk = settings.ACCT_Q_CHUNK, settings.ACCT_KV_CHUNK
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0
    nq, nk = Sq // q_chunk, Sk // kv_chunk

    # (nq, B, qc, KV, G, hd) query blocks; kv -> (nk, B, kc, KV, hd)
    qb = q.reshape(B, nq, q_chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(B, nk, kv_chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, kv_chunk, KV, hd).transpose(1, 0, 2, 3, 4)

    # Flash-style memory discipline under autodiff: remat BOTH scan bodies so
    # the backward pass recomputes p-blocks and masks instead of storing all
    # (nq x nk) of them (observed 200+ GiB/device otherwise on train_4k).
    @jax.checkpoint
    def q_step(_, qi):
        qblk, qidx = qi  # (B, qc, KV, G, hd), ()
        q_pos = q_offset + qidx * q_chunk + jnp.arange(q_chunk)

        @jax.checkpoint
        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kidx = ki
            k_pos = kidx * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqkgh,bckh->bkgqc", qblk, kblk) * scale
            s = s.astype(jnp.float32)
            mask = _block_mask(q_pos, k_pos, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqc,bckh->bkgqh", p.astype(vblk.dtype), vblk)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (kb, vb, jnp.arange(nk)),
            unroll=settings.scan_unroll())
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, KV, G, qc, hd) -> (B, qc, KV, G, hd)
        return None, out.transpose(0, 3, 1, 2, 4).astype(q.dtype)

    _, ob = jax.lax.scan(q_step, None, (qb, jnp.arange(nq)),
                         unroll=settings.scan_unroll())
    # (nq, B, qc, KV, G, hd) -> (B, Sq, H, hd)
    return ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, hd)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, window: int = 0) -> jax.Array:
    """Single-step decode. q: (B, 1, H, hd); caches: (B, S, KV, hd).

    ``cache_len``: scalar count of valid positions (new token included).
    """
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qr = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qr, k_cache) * (hd ** -0.5)
    s = s.astype(jnp.float32)
    pos = jnp.arange(S)
    valid = pos < cache_len
    win = jnp.asarray(window)  # may be traced (per-layer scan input)
    valid = valid & ((win <= 0) | (pos >= cache_len - win))
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, hd)


def flash_decode_sharded(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_len: jax.Array, *, mesh, seq_axis: str,
                         window: int = 0) -> jax.Array:
    """Decode attention over a cache whose SEQUENCE dim is sharded on
    ``seq_axis`` (long-context serving). Each shard computes a partial
    online-softmax over its cache slice; partials combine with one psum —
    the flash-decoding pattern, expressed in shard_map (DESIGN.md §5 SP).
    """
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[seq_axis]
    S = k_cache.shape[1]
    S_loc = S // n_shards

    def local(qb, kb, vb, clen):
        B, _, H, hd = qb.shape
        KV = kb.shape[2]
        G = H // KV
        shard = jax.lax.axis_index(seq_axis)
        base = shard * S_loc
        qr = qb.reshape(B, KV, G, hd)
        s = jnp.einsum("bkgh,bskh->bkgs", qr, kb) * (hd ** -0.5)
        s = s.astype(jnp.float32)
        pos = base + jnp.arange(S_loc)
        valid = pos < clen
        if window > 0:
            valid = valid & (pos >= clen - window)
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        m = s.max(axis=-1)                                   # (B,KV,G)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        pv = jnp.einsum("bkgs,bskh->bkgh", p.astype(vb.dtype), vb)
        # combine partials across shards with one fused psum
        g_m = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - g_m)
        l_g = jax.lax.psum(l * corr, seq_axis)
        pv_g = jax.lax.psum(pv.astype(jnp.float32) * corr[..., None], seq_axis)
        out = pv_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.reshape(B, 1, H, hd).astype(qb.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, seq_axis, None, None),
                  P(None, seq_axis, None, None), P()),
        out_specs=P(),
        check_vma=False,
    )(q, k_cache, v_cache, cache_len)
