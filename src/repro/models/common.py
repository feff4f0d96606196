"""Shared model building blocks: norms, RoPE, projections, embedding, loss.

Conventions:
* params are stored fp32 and cast to bf16 for compute (``cdt``);
* activations flow bf16, residual stream bf16, norms/softmax in fp32;
* layer stacks are scanned — per-layer params carry a leading L dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CDT = jnp.bfloat16  # compute dtype


def cast(x):
    return jax.tree.map(lambda a: a.astype(CDT) if a.dtype == jnp.float32 else a, x)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, w_down)


def pad_vocab(v: int, multiple: int = 256) -> int:
    """Pad vocab to a TP-shardable multiple (DESIGN.md §4)."""
    return ((v + multiple - 1) // multiple) * multiple


def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0).astype(CDT)


def unembed_logits(h: jax.Array, table: jax.Array, real_vocab: int) -> jax.Array:
    """h @ table.T with padded-id masking; logits fp32 for a stable loss."""
    logits = jnp.einsum("...d,vd->...v", h, table.astype(CDT))
    logits = logits.astype(jnp.float32)
    v_pad = table.shape[0]
    if v_pad > real_vocab:
        mask = (jnp.arange(v_pad) < real_vocab)
        logits = jnp.where(mask, logits, -1e30)
    return logits


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 z_loss: float = 1e-4) -> jax.Array:
    """Stable token-mean cross-entropy (+ z-loss); works with a vocab-sharded
    last axis (XLA SPMD inserts the reductions).

    The gold logit is picked with a fused iota-compare reduction rather than
    take_along_axis: a vocab-axis gather on a vocab-sharded operand would
    force an all-gather of fp32 logits (observed 13 GB/device on the 256-chip
    dry-run); the masked reduce stays sharded and fuses.
    """
    m = jnp.max(logits, axis=-1, keepdims=True)
    shifted = logits - jax.lax.stop_gradient(m)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    vocab_ids = jax.lax.broadcasted_iota(labels.dtype, logits.shape,
                                         logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_ids == labels[..., None], logits, 0.0),
                   axis=-1)
    loss = lse - gold
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return jnp.mean(loss)


def init_dense(key, shape, scale: float | None = None) -> jax.Array:
    fan_in = shape[-2] if len(shape) >= 2 else shape[0]
    s = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * s)
