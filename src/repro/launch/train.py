"""Training driver: real execution on the local device(s).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b-reduced \
        --steps 200 --batch 8 --seq 128

Runs the same build_train_step the dry-run lowers, on synthetic LM batches,
and reports loss curve + step timing.  Used by examples/train_small.py to
train a ~100M-param model for a few hundred steps on CPU.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.data.workloads import lm_train_batches
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import build_train_step
from repro.models import get_api


def train(arch, *, steps: int, batch: int, seq: int, lr: float = 3e-4,
          seed: int = 0, log_every: int = 10,
          ckpt_dir: str | None = None, ckpt_every: int = 100) -> list[float]:
    from repro import checkpoint as ckptlib

    cfg = arch if not isinstance(arch, str) else get_config(arch)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    n_params = api.count_params(cfg)
    print(f"arch={cfg.name} family={cfg.family} params={n_params/1e6:.1f}M "
          f"devices={jax.device_count()}")

    step_fn, opt = build_train_step(cfg, lr=lr)
    opt_state = opt.init(params)
    start = 0
    if ckpt_dir is not None:
        latest = ckptlib.latest_step(ckpt_dir)
        if latest is not None:
            tree, start, _ = ckptlib.load_checkpoint(
                ckptlib.step_path(ckpt_dir, latest))
            params, opt_state = tree["params"], tree["opt_state"]
            print(f"resumed from step {start}")
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    losses: list[float] = []
    t0 = time.time()
    for i, b in enumerate(lm_train_batches(steps, batch, seq, cfg.vocab_size,
                                           seed=seed + start)):
        loss, params, opt_state = jit_step(params, opt_state, b)
        losses.append(float(loss))
        step_no = start + i + 1
        if i % log_every == 0 or i == steps - 1:
            dt = time.time() - t0
            print(f"step {step_no:4d} loss {losses[-1]:.4f} "
                  f"({dt/(i+1):.3f}s/step)", flush=True)
        if ckpt_dir is not None and step_no % ckpt_every == 0:
            ckptlib.save_checkpoint(
                ckptlib.step_path(ckpt_dir, step_no),
                {"params": params, "opt_state": opt_state}, step=step_no,
                metadata={"arch": cfg.name})
    return losses


def main(argv=None) -> int:
    use_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen3-1.7b-reduced")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    args = p.parse_args(argv)
    losses = train(args.arch, steps=args.steps, batch=args.batch,
                   seq=args.seq, lr=args.lr)
    improved = losses[-1] < losses[0]
    print(f"loss {losses[0]:.4f} -> {losses[-1]:.4f} improved={improved}")
    return 0 if improved else 1


if __name__ == "__main__":
    raise SystemExit(main())
