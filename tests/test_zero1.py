"""ZeRO-1 optimizer-state sharding: numerically identical to unsharded
AdamW (8-device subprocess; the sharding must change placement, not math)."""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    import sys; sys.path.insert(0, "src")
    from repro.configs import reduced_config
    from repro.launch.mesh import data_axis_names
    from repro.launch.shardings import (batch_shardings, opt_shardings,
                                        param_shardings)
    from repro.models.transformer import ShardEnv, init_params
    from repro.optim.adamw import AdamWConfig, init_opt_state, make_train_step
    from repro.launch.mesh import make_mesh

    cfg = reduced_config("llama3.2-1b")
    mesh = make_mesh((4, 2), ("data", "model"))
    env = ShardEnv(mesh, policy="dp")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)}
    step = make_train_step(cfg, env, AdamWConfig(peak_lr=1e-2, warmup_steps=1))

    losses = {}
    for zero1 in (False, True):
        p_sh = param_shardings(cfg, mesh, jax.eval_shape(lambda: params), policy="dp")
        o_sh = opt_shardings(cfg, mesh, jax.eval_shape(lambda: opt), policy="dp",
                             zero1=zero1)
        with jax.set_mesh(mesh):
            fn = jax.jit(step, in_shardings=(p_sh, o_sh,
                                             batch_shardings(cfg, mesh, jax.eval_shape(lambda: batch), policy="dp")),
                         out_shardings=(p_sh, o_sh, None))
            p, o, b = params, opt, batch
            ls = []
            for _ in range(3):
                p, o, m = fn(p, o, b)
                ls.append(float(m["loss"]))
        losses[zero1] = ls
    a, b = losses[False], losses[True]
    # step-1 losses must match exactly-ish; later steps accumulate fp32
    # reduction-order noise through the lr=1e-2 updates
    assert abs(a[0] - b[0]) < 1e-5, (a, b)
    assert np.allclose(a, b, rtol=2e-3), (a, b)
    print("zero1 numerics ok", a, b)
""")


@pytest.mark.slow
def test_zero1_matches_unsharded():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=420, cwd=".")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "zero1 numerics ok" in r.stdout
