"""Train a reduced assigned-architecture LM on a synthetic Markov stream —
exercises the same make_train_step the production launcher lowers, on the
host mesh, with loss-goes-down validation.

Run:  PYTHONPATH=src python examples/train_lm.py --arch qwen1.5-0.5b
      PYTHONPATH=src python examples/train_lm.py --arch mamba2-780m
Also demonstrates the paper technique on a transformer: --supernet samples
a random choice key per step (one-shot supernet training).
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import make_lm_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import init_opt, make_train_step
from repro.models import transformer as tr


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--supernet", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    if args.supernet:
        cfg = cfg.replace(supernet=True)
    rng = jax.random.PRNGKey(0)
    params = tr.init_params(rng, cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name} (smoke): {n_params/1e6:.2f}M params"
          f"{' [supernet]' if args.supernet else ''}")

    opt = init_opt(params, "adamw")
    step_fn = jax.jit(make_train_step(cfg, optimizer="adamw", lr=args.lr,
                                      remat=False))
    x, y = make_lm_stream(0, args.steps * args.batch, args.seq,
                          cfg.vocab_size)
    key_rng = np.random.default_rng(0)
    first = last = None
    for i in range(args.steps):
        batch = {"tokens": x[i*args.batch:(i+1)*args.batch],
                 "labels": y[i*args.batch:(i+1)*args.batch]}
        if cfg.family in ("vlm", "audio"):
            batch["prefix"] = np.zeros(
                (args.batch, cfg.num_prefix, cfg.d_model), np.float32)
        if args.supernet:
            batch["choice_key"] = jnp.asarray(
                key_rng.integers(0, 4, cfg.num_layers), jnp.int32)
        params, opt, loss = step_fn(params, opt, batch)
        if first is None:
            first = float(loss)
        last = float(loss)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"  step {i:4d}  loss {float(loss):.4f}")
    assert last < first, "loss did not decrease"
    print(f"loss {first:.3f} -> {last:.3f}  (decreased: OK)")


if __name__ == "__main__":
    main()
