"""End-to-end driver: the paper's experiment (Section IV) at CPU scale.

Real-time federated evolutionary NAS on the CNN supernet over IID or
non-IID synthetic clients, against BOTH baselines the paper uses:
  * FedAvg on a fixed all-residual model (the ResNet18 role, Table IV),
  * offline evolutionary NAS (reinit + every client trains every
    individual, Section IV.G).

Writes history JSON next to benchmarks/results for EXPERIMENTS.md.

Run (quick):  PYTHONPATH=src python examples/federated_nas_cifar.py \
                  --generations 5 --clients 8
Full paper-shaped run: --generations 40 --clients 10 (takes ~1 h on CPU).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks import fed_nas
from repro.core import nsga2
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--generations", type=int, default=5)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--population", type=int, default=6)
    ap.add_argument("--noniid", action="store_true")
    ap.add_argument("--offline-generations", type=int, default=2)
    ap.add_argument("--baseline-rounds", type=int, default=0,
                    help="0 = same as --generations")
    ap.add_argument("--engine-backend", default="loop",
                    choices=["loop", "vmap", "mesh"],
                    help="client-execution backend (FedEngine); for "
                         "'mesh' on a CPU host set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 "
                         "before launch to get devices to shard over")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="benchmarks/results")
    args = ap.parse_args()

    api = fed_nas.build_api()
    clients = fed_nas.build_clients(args.clients, iid=not args.noniid,
                                    seed=args.seed)
    tag = ("noniid" if args.noniid else "iid") + f"_c{args.clients}"

    print(f"=== RT-FedENAS ({tag}): {args.generations} generations, "
          f"pop {args.population} ===")
    t0 = time.time()
    hist = fed_nas.run_rt(api, clients, args.generations,
                          population=args.population, seed=args.seed,
                          engine_backend=args.engine_backend)
    rt_wall = time.time() - t0
    front = fed_nas.summarize_front(api, hist)
    print(f"  wall {rt_wall:.0f}s | best err "
          f"{hist['best_err'][0]:.3f} -> {hist['best_err'][-1]:.3f}")
    for r in front:
        print(f"  front: err={r['err']:.3f} flops={r['flops']/1e6:.1f}M")

    print("=== FedAvg fixed baseline (ResNet role) ===")
    rounds = args.baseline_rounds or args.generations
    base = fed_nas.run_fixed_baseline(api, clients, rounds, seed=args.seed,
                                      engine_backend=args.engine_backend)
    print(f"  err {base['err'][0]:.3f} -> {base['err'][-1]:.3f} "
          f"@ {base['flops']/1e6:.1f} MMACs")

    print(f"=== offline ENAS baseline: {args.offline_generations} gens ===")
    t0 = time.time()
    off = fed_nas.run_offline(api, clients, args.offline_generations,
                              population=args.population, seed=args.seed,
                              engine_backend=args.engine_backend)
    off_wall = time.time() - t0
    per_gen_rt = rt_wall / args.generations
    per_gen_off = off_wall / args.offline_generations
    print(f"  per-generation wall: RT {per_gen_rt:.1f}s vs offline "
          f"{per_gen_off:.1f}s -> RT is {per_gen_off/per_gen_rt:.1f}x "
          f"faster (paper: ~5x)")
    print(f"  upload volume: RT {hist['up_gb'][-1]:.3f} GB "
          f"({args.generations} gens) vs offline {off['up_gb'][-1]:.3f} GB "
          f"({args.offline_generations} gens)")

    os.makedirs(args.out, exist_ok=True)
    fed_nas.save_history(
        os.path.join(args.out, f"fednas_rt_{tag}.json"), hist,
        extra={"front": front, "rt_wall_s": rt_wall,
               "baseline_err": base["err"],
               "baseline_flops": base["flops"],
               "offline_per_gen_s": per_gen_off,
               "rt_per_gen_s": per_gen_rt,
               "offline_up_gb": off["up_gb"][-1],
               "offline_gens": args.offline_generations,
               "offline_best_err": off["best_err"]})
    print(f"history saved to {args.out}/fednas_rt_{tag}.json")


if __name__ == "__main__":
    main()
