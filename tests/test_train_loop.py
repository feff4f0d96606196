"""Fault-tolerant loop: loss descends, checkpoint/resume is exact,
preemption checkpoints, straggler log plumbs through."""
import os
import signal

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.data.tokens import TokenPipeline
from repro.launch.mesh import make_mesh
from repro.models.transformer import ShardEnv, init_params
from repro.optim.adamw import AdamWConfig, init_opt_state, make_train_step
from repro.train.loop import LoopConfig, TrainLoop


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("smollm-135m")
    mesh = make_mesh((1, 1), ("data", "model"))
    env = ShardEnv(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(cfg, env, AdamWConfig(
        peak_lr=3e-3, warmup_steps=5, total_steps=200)))
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, batch=4, seq_len=64,
                         seed=0)
    return step, pipe, params, opt


def test_loss_descends(setup, tmp_path):
    step, pipe, params, opt = setup
    loop = TrainLoop(LoopConfig(total_steps=30, ckpt_every=100,
                                ckpt_dir=str(tmp_path)), step, pipe, params,
                     opt)
    out = loop.run()
    losses = [m["loss"] for m in out["metrics"]]
    assert losses[-1] < losses[0] - 0.2, losses


def test_resume_is_exact(setup, tmp_path):
    step, pipe, params, opt = setup
    # uninterrupted 12 steps
    a = TrainLoop(LoopConfig(total_steps=12, ckpt_every=100,
                             ckpt_dir=str(tmp_path / "a"), log_every=1),
                  step, pipe, params, opt)
    out_a = a.run()
    # interrupted at 6 + resume
    b1 = TrainLoop(LoopConfig(total_steps=6, ckpt_every=6,
                              ckpt_dir=str(tmp_path / "b"), log_every=1,
                              async_ckpt=False), step, pipe, params, opt)
    b1.run()
    b2 = TrainLoop(LoopConfig(total_steps=12, ckpt_every=100,
                              ckpt_dir=str(tmp_path / "b"), log_every=1),
                   step, pipe, params, opt)
    start = b2.try_resume()
    assert start == 6
    out_b = b2.run(start_step=start)
    la = {m["step"]: m["loss"] for m in out_a["metrics"]}
    lb = {m["step"]: m["loss"] for m in out_b["metrics"]}
    for s in range(7, 12):
        np.testing.assert_allclose(la[s], lb[s], rtol=1e-4), s


def test_preemption_checkpoints(setup, tmp_path):
    step, pipe, params, opt = setup
    loop = TrainLoop(LoopConfig(total_steps=50, ckpt_every=1000,
                                ckpt_dir=str(tmp_path), async_ckpt=False),
                     step, pipe, params, opt)
    orig = loop.train_step

    def step_then_preempt(*args):
        out = orig(*args)
        loop._preempted = True
        return out

    loop.train_step = step_then_preempt
    out = loop.run()
    assert out["preempted"]
    from repro.checkpoint import ckpt
    assert ckpt.latest_step(str(tmp_path)) == out["last_step"]


def test_straggler_detection(setup, tmp_path):
    step, pipe, params, opt = setup
    loop = TrainLoop(LoopConfig(total_steps=12, ckpt_every=100,
                                ckpt_dir=str(tmp_path),
                                straggler_factor=0.0001), step, pipe, params,
                     opt)
    out = loop.run()
    assert len(out["stragglers"]) > 0   # absurd factor flags everything
