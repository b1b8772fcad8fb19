"""Training launcher: train a reduced (smoke) variant of any assigned
architecture on the synthetic pipeline, with checkpointing.

  PYTHONPATH=src python -m repro.launch.train --arch internlm2_1_8b \
      --steps 200 --batch 8 --seq 64 --ckpt out/ck.npz
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as T
from repro.train import checkpoint
from repro.train.data import TokenStream
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.step import make_train_step


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (non-smoke) config — production only")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=not args.full_config)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(1, args.steps // 20),
                          total_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg))
    ds = iter(TokenStream(cfg, args.batch, args.seq))

    t0 = time.perf_counter()
    for i in range(1, args.steps + 1):
        b = next(ds)
        params, opt, m = step_fn(params, opt, jnp.asarray(b["inputs"]),
                                 jnp.asarray(b["labels"]))
        if i % 10 == 0 or i == 1:
            print(f"step {i:5d}  loss {float(m['loss']):.4f}  "
                  f"ce {float(m['ce']):.4f}  gnorm {float(m['grad_norm']):.3f}"
                  f"  lr {float(m['lr']):.2e}  "
                  f"({i/(time.perf_counter()-t0):.2f} it/s)")
        if args.ckpt and i % args.ckpt_every == 0:
            checkpoint.save(args.ckpt, params, opt, step=i)
            print(f"checkpointed -> {args.ckpt}")
    if args.ckpt:
        checkpoint.save(args.ckpt, params, opt, step=args.steps)


if __name__ == "__main__":
    main()
