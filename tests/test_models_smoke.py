"""Per-architecture smoke tests (deliverable f): reduced same-family config,
one forward/train step + one decode step on CPU; output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, reduced_config
from repro.launch.mesh import make_mesh
from repro.models.common import pad_vocab
from repro.models.transformer import (ShardEnv, decode_step, forward_loss,
                                      init_params, prefill)

B, S = 2, 64


def _env():
    return ShardEnv(make_mesh((1, 1), ("data", "model")))


def _batch(cfg, key):
    if cfg.frontend == "frame":
        return {"frames": jax.random.normal(key, (B, S, cfg.d_model),
                                            jnp.bfloat16),
                "tokens": jax.random.randint(key, (B, 16), 0, cfg.vocab_size),
                "labels": jax.random.randint(key, (B, 16), 0, cfg.vocab_size)}
    if cfg.frontend == "patch":
        return {"embeds": jax.random.normal(key, (B, S, cfg.d_model),
                                            jnp.bfloat16),
                "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    return {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
            "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_train_step_smoke(arch):
    cfg = reduced_config(arch)
    env = _env()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = _batch(cfg, key)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: forward_loss(p, batch, cfg, env)))(params)
    assert np.isfinite(float(loss)), arch
    gn = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, arch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_prefill_decode_smoke(arch):
    cfg = reduced_config(arch)
    env = _env()
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    batch = {k: v for k, v in _batch(cfg, key).items() if k != "labels"}
    logits, cache = jax.jit(lambda p, b: prefill(p, b, cfg, env))(params,
                                                                  batch)
    assert logits.shape == (B, 1, pad_vocab(cfg.vocab_size)), arch
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch
    if cfg.frontend == "patch":
        db = {"embeds": jax.random.normal(key, (B, 1, cfg.d_model),
                                          jnp.bfloat16)}
    else:
        db = {"tokens": jax.random.randint(key, (B, 1), 0, cfg.vocab_size)}
    dl, cache2 = jax.jit(lambda p, c, b: decode_step(p, c, b, cfg, env))(
        params, cache, db)
    assert np.isfinite(np.asarray(dl, np.float32)).all(), arch
    assert int(cache2["pos"]) == int(cache["pos"]) + 1
