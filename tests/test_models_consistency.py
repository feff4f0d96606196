"""Stronger model correctness: decode continuation matches teacher forcing;
MoE matches its dense oracle; attention chunking is mask-exact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.launch.mesh import make_mesh
from repro.models.attention import chunked_attention, decode_attention
from repro.models.moe import MoEDims, moe_ffn
from repro.models.transformer import (ShardEnv, decode_step, forward_loss,
                                      init_params, prefill)


def _env():
    return ShardEnv(make_mesh((1, 1), ("data", "model")))


@pytest.mark.parametrize("arch", ["llama3.2-1b", "gemma3-1b", "rwkv6-3b",
                                  "hymba-1.5b"])
def test_decode_matches_teacher_forcing(arch):
    """prefill(t[:S]) then decode(t[S]) must equal the final-position logits
    of prefill(t[:S+1]) — the KV-cache/state path is exact."""
    cfg = reduced_config(arch)
    env = _env()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    S = 32
    toks = jax.random.randint(key, (2, S + 1), 0, cfg.vocab_size)
    logits_full, _ = prefill(params, {"tokens": toks}, cfg, env)
    _, cache = prefill(params, {"tokens": toks[:, :S]}, cfg, env)
    logits_dec, _ = decode_step(params, cache, {"tokens": toks[:, S:S + 1]},
                                cfg, env)
    np.testing.assert_allclose(
        np.asarray(logits_dec[:, 0], np.float32),
        np.asarray(logits_full[:, 0], np.float32), rtol=0.15, atol=0.6)


def test_chunked_attention_matches_naive():
    key = jax.random.PRNGKey(0)
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    for window in (0, 16):
        out = chunked_attention(q, k, v, causal=True, window=window,
                                q_chunk=16, kv_chunk=32)
        # naive reference
        G = H // KV
        qr = q.reshape(B, S, KV, G, hd)
        s = jnp.einsum("bqkgh,bckh->bkgqc", qr, k) / np.sqrt(hd)
        mask = jnp.tril(jnp.ones((S, S), bool))
        if window:
            mask &= (jnp.arange(S)[:, None] - jnp.arange(S)[None, :]) < window
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("bkgqc,bckh->bqkgh", p, v).reshape(B, S, H, hd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


def test_decode_attention_matches_full():
    key = jax.random.PRNGKey(3)
    B, S, H, KV, hd = 2, 40, 4, 4, 8
    q = jax.random.normal(key, (B, 1, H, hd))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    out = decode_attention(q, kc, vc, jnp.asarray(S))
    s = jnp.einsum("bkh,bskh->bks", q.reshape(B, H, hd), kc) / np.sqrt(hd)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bks,bskh->bkh", p, vc).reshape(B, 1, H, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def test_moe_matches_dense_oracle():
    mesh = make_mesh((1, 1), ("data", "model"))
    E, K, d, f = 8, 2, 16, 32
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    params = {"router": jax.random.normal(ks[0], (d, E)) * 0.1,
              "w1": jax.random.normal(ks[1], (E, d, f)) * 0.1,
              "w3": jax.random.normal(ks[2], (E, d, f)) * 0.1,
              "w2": jax.random.normal(ks[3], (E, f, d)) * 0.1}
    x = jax.random.normal(ks[4], (2, 16, d))
    dims = MoEDims(E, K, capacity_factor=8.0)  # no drops -> exact

    xt = x.reshape(-1, d)
    logits = xt @ params["router"]
    tl, ti = jax.lax.top_k(logits, K)
    w = jax.nn.softmax(tl, axis=-1)
    g = jnp.einsum("td,edf->tef", xt, params["w1"])
    u = jnp.einsum("td,edf->tef", xt, params["w3"])
    h = jax.nn.silu(g) * u
    y_all = jnp.einsum("tef,efd->ted", h, params["w2"])
    ref = (jnp.take_along_axis(y_all, ti[:, :, None], axis=1)
           * w[..., None]).sum(1).reshape(x.shape)
    for mode in ("train", "decode"):
        out = moe_ffn(x, params, dims, mesh, mode=mode)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_capacity_drops_are_bounded():
    """With cf=1.0 and adversarially skewed routing, output degrades
    gracefully (dropped tokens fall back to residual = zero delta)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    E, K, d, f = 4, 1, 8, 16
    key = jax.random.PRNGKey(0)
    params = {"router": jnp.zeros((d, E)).at[:, 0].set(10.0),  # all -> e0
              "w1": jax.random.normal(key, (E, d, f)) * 0.1,
              "w3": jax.random.normal(key, (E, d, f)) * 0.1,
              "w2": jax.random.normal(key, (E, f, d)) * 0.1}
    x = jax.random.normal(key, (1, 32, d))
    out = moe_ffn(x, params, MoEDims(E, K, capacity_factor=1.0), mesh,
                  mode="train")
    assert np.isfinite(np.asarray(out)).all()
