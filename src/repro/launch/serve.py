"""Serving launcher: batched decode of any zoo architecture.

Prefill is run through the forward path to seed logits (greedy prompt
consumption via repeated decode keeps the code path single — the decode
step is exactly what the dry-run lowers for decode_32k / long_500k).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --batch 4 --prompt 32 --generate 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced, list_archs
from repro.data import synthetic_request_stream
from repro.launch.chip import announce_device, enable_compile_cache
from repro.models import lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="smollm-360m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--generate", type=int, default=32)
    ap.add_argument("--full-config", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    dev = announce_device("serve")

    cfg = get_config(args.arch) if args.full_config \
        else get_reduced(args.arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    capacity = args.prompt + args.generate
    if cfg.sliding_window:
        capacity = min(capacity, cfg.sliding_window)
    cache = lm.init_cache(cfg, args.batch, capacity)
    dec = jax.jit(lambda p, t, c: lm.decode_step(p, cfg, t, c))

    prompts = next(synthetic_request_stream(
        cfg, batch=args.batch, prompt_len=args.prompt, seed=0))
    toks = jnp.asarray(prompts[:, :1], jnp.int32)

    t0 = time.perf_counter()
    generated = []
    for step in range(args.prompt + args.generate - 1):
        logits, cache = dec(params, toks, cache)
        if step < args.prompt - 1:           # teacher-force the prompt
            toks = jnp.asarray(prompts[:, step + 1: step + 2], jnp.int32)
        else:                                # greedy generation
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            generated.append(np.asarray(toks)[:, 0])
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0
    n_tok = args.batch * (args.prompt + args.generate - 1)
    print(f"arch={cfg.name} served {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s on {dev['kind']})")
    gen = np.stack(generated, axis=1)
    print("sample generations (token ids):")
    for row in gen[: min(2, args.batch)]:
        print("  ", row[:16].tolist())


if __name__ == "__main__":
    main()
