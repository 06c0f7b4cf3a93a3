"""Training launcher: run any zoo architecture on the local host devices.

Production launches use the same StepBundle the dry-run compiles (the
in/out shardings carry over); on this CPU container the default is the
reduced config of the chosen arch with a host mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --steps 20 --batch 8 --seq 128 [--full-config]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced, list_archs
from repro.data import synthetic_batches
from repro.launch.chip import announce_device, enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_optimizer
from repro.models import lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="smollm-360m")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full-config", action="store_true",
                    help="use the published config (needs real hardware)")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()
    enable_compile_cache()
    announce_device("train")

    cfg = get_config(args.arch) if args.full_config \
        else get_reduced(args.arch)
    if cfg.family in ("ssm", "hybrid"):
        args.seq = max(args.seq, cfg.ssm_chunk)
        args.seq -= args.seq % cfg.ssm_chunk
    mesh = make_host_mesh()
    print(f"arch={cfg.name} family={cfg.family} params≈{cfg.param_count():,} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(cfg)
    opt_state = opt.init(params)
    step = jax.jit(lm.make_train_step(cfg, opt))

    gen = synthetic_batches(cfg, batch=args.batch, seq=args.seq, seed=0)
    t0 = time.perf_counter()
    for i in range(args.steps):
        batch = next(gen)
        params, opt_state, metrics = step(params, opt_state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            jax.block_until_ready(metrics["loss"])
            print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "
                  f"aux {float(metrics['aux']):.4f}  "
                  f"{(time.perf_counter() - t0):.1f}s")
    print("done")


if __name__ == "__main__":
    main()
