"""Fault-tolerant training driver.

    PYTHONPATH=src python examples/train_lm.py --steps 60
    PYTHONPATH=src python examples/train_lm.py --arch smollm-135m --full \
        --steps 300   # the ~100M-param end-to-end run (slow on CPU)

Resumable: re-running with the same --ckpt-dir resumes from the latest
checkpoint and regenerates identical data batches (step-indexed pipeline).
"""
import argparse

import jax

from repro.configs import get_config, reduced_config
from repro.data.tokens import TokenPipeline
from repro.launch.mesh import make_mesh
from repro.models.transformer import ShardEnv, init_params
from repro.optim.adamw import AdamWConfig, init_opt_state, make_train_step
from repro.train.loop import LoopConfig, TrainLoop

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="smollm-135m")
ap.add_argument("--full", action="store_true")
ap.add_argument("--steps", type=int, default=60)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--seq", type=int, default=128)
ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
args = ap.parse_args()

cfg = get_config(args.arch) if args.full else reduced_config(args.arch)
mesh = make_mesh((1, 1), ("data", "model"))
env = ShardEnv(mesh)
params = init_params(cfg, jax.random.PRNGKey(0))
n_params = sum(p.size for p in jax.tree.leaves(params))
print(f"{args.arch}{' (reduced)' if not args.full else ''}: "
      f"{n_params/1e6:.1f}M params")
opt = init_opt_state(params)
step = jax.jit(make_train_step(cfg, env, AdamWConfig(
    peak_lr=3e-3, warmup_steps=20, total_steps=args.steps)))
pipe = TokenPipeline(vocab_size=cfg.vocab_size, batch=args.batch,
                     seq_len=args.seq, seed=0, frontend=cfg.frontend,
                     d_model=cfg.d_model)
loop = TrainLoop(LoopConfig(total_steps=args.steps, ckpt_every=25,
                            ckpt_dir=args.ckpt_dir, log_every=5),
                 step, pipe, params, opt)
loop.install_signal_handlers()
start = loop.try_resume()
if start:
    print(f"resumed from step {start}")
out = loop.run(start_step=start)
for m in out["metrics"]:
    print(f"step {m['step']:4d} loss {m['loss']:.4f} ({m['dt']*1000:.0f} ms)")
print(f"done at step {out['last_step']}; stragglers flagged: "
      f"{len(out['stragglers'])}")
