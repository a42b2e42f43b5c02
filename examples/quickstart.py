"""Quickstart: 60 seconds with the RT-FedENAS framework.

1. build the paper's CNN supernet master model,
2. sample sub-networks with choice keys and inspect their FLOPs,
3. run TWO generations of real-time federated evolutionary NAS
   (double-sampling + fill-aggregation + NSGA-II) on synthetic clients
   through the FedEngine's vectorized ("vmap") execution backend,
4. print the Pareto front.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import make_api, nsga2
from repro.core.choice import random_key
from repro.data import make_classification, make_clients, partition_iid
from repro.engine import FedEngine, RealTimeNas, RunConfig
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    # --- the master model (paper Fig. 3, CPU-reduced) -------------------
    cfg = get_config("cifar-supernet", smoke=True)
    api = make_api(cfg)
    print(f"master model: {cfg.name}, {cfg.num_layers} choice blocks, "
          f"{api.master_params() / 1e6:.2f}M params")

    rng = np.random.default_rng(0)
    for _ in range(3):
        key = random_key(rng, api.num_blocks)
        print(f"  choice key {key} -> {api.flops(key) / 1e6:7.1f} MMACs, "
              f"payload {api.payload_params(key) / 1e6:.2f}M params")

    # --- synthetic federated clients ------------------------------------
    x, y = make_classification(0, 1200, image=16)
    clients = make_clients(x, y, partition_iid(0, len(x), 8),
                           batch=50, test_batch=50)
    print(f"{len(clients)} clients, ~{clients[0].n_train} train samples each")

    # --- two generations of real-time evolutionary NAS ------------------
    engine = FedEngine(api, clients,
                       RunConfig(population=4, generations=2, seed=0,
                                 backend="vmap"),
                       strategy=RealTimeNas())
    hist = engine.run().history()
    objs = hist["objs"][-1]
    front = nsga2.fast_non_dominated_sort(objs)[0]
    print("\nPareto front after 2 generations (err, MMACs):")
    for i in sorted(front, key=lambda i: objs[i, 1]):
        print(f"  err={objs[i, 0]:.3f}  flops={objs[i, 1] / 1e6:8.1f}M")
    print(f"\ncomm so far: down {hist['down_gb'][-1]:.3f} GB, "
          f"up {hist['up_gb'][-1]:.3f} GB, "
          f"client passes {hist['train_passes'][-1]}, "
          f"jitted dispatches {engine.backend.dispatches}")


if __name__ == "__main__":
    main()
