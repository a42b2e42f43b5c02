"""Serve a reduced model with batched requests: prefill + greedy decode
through the same decode_step the decode_32k / long_500k dry-run shapes
lower.  Includes a sliding-window decode demo (the long_500k mechanism).

Run:  PYTHONPATH=src python examples/serve_batched.py --arch qwen1.5-0.5b
      PYTHONPATH=src python examples/serve_batched.py --arch mamba2-780m
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import greedy_generate
from repro.models import transformer as tr


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--window", type=int, default=0,
                    help=">0: sliding-window ring-buffer decode")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    rng = jax.random.PRNGKey(0)
    params = tr.init_params(rng, cfg)
    prompt = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    prefix = None
    if cfg.family in ("vlm", "audio"):
        prefix = jnp.zeros((args.batch, cfg.num_prefix, cfg.d_model),
                           jnp.float32)

    cache_len = (min(args.window, args.prompt_len + args.steps)
                 if args.window else args.prompt_len + args.steps)
    t0 = time.time()
    toks = greedy_generate(params, cfg, prompt, args.steps,
                           cache_len=cache_len, window=args.window,
                           prefix=prefix)
    dt = time.time() - t0
    n_new = args.batch * args.steps
    print(f"{cfg.name}: {args.batch} requests x {args.steps} new tokens "
          f"in {dt:.1f}s ({n_new / dt:.1f} tok/s, "
          f"cache_len={cache_len}{', sliding' if args.window else ''})")
    print("first request:", toks[0].tolist())


if __name__ == "__main__":
    main()
