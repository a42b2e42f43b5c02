"""Benchmark harness — one entry per paper table/figure + system micro-
benchmarks.  Prints ``name,us_per_call,derived`` CSV lines.

Paper mapping:
  fig8_pareto_*      -> Fig. 8   (Pareto fronts, IID vs non-IID)
  table4_vs_baseline -> Table IV (High/Knee vs fixed ResNet-role model)
  fig9_realtime      -> Fig. 9   (stability of best/knee during search)
  sec4g_rt_vs_offline-> Sec. IV.G (per-generation cost, RT vs offline)
  roofline_*         -> EXPERIMENTS.md §Roofline (from dry-run records)
Micro:
  nsga2_select, fill_aggregate_{xla,pallas}, client_update, evaluate,
  fused_ce_vs_naive, kernel_* (interpret-mode correctness + call overhead)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, n=3, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.time()
    for _ in range(n):
        fn()
    return (time.time() - t0) / n * 1e6  # us


def emit(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}")


def bench_micro():
    from repro.core import nsga2
    rng = np.random.default_rng(0)
    objs = rng.random((200, 2))
    emit("nsga2_select_n200", _timeit(lambda: nsga2.select(objs, 100)),
         f"fronts={len(nsga2.fast_non_dominated_sort(objs))}")

    from repro.kernels import ops, ref
    m, p = 8, 1_000_000
    cl = jnp.asarray(rng.normal(size=(m, p)), jnp.float32)
    mk = jnp.asarray(rng.integers(0, 2, (m, p)), jnp.float32)
    w = jnp.full((m,), 1.0 / m)
    prev = jnp.asarray(rng.normal(size=(p,)), jnp.float32)
    r = ref.fill_aggregate(cl, mk, w, prev)
    emit("fill_aggregate_xla_8x1M",
         _timeit(lambda: jax.block_until_ready(
             ref.fill_aggregate(cl, mk, w, prev))),
         "bytes=%d" % (cl.nbytes * 2))
    out = ops.fill_aggregate(cl, mk, w, prev)
    err = float(jnp.abs(out - r).max())
    emit("fill_aggregate_pallas_interp_8x1M",
         _timeit(lambda: jax.block_until_ready(
             ops.fill_aggregate(cl, mk, w, prev)), n=1),
         f"allclose_err={err:.1e}")

    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    o1 = ops.flash_attention(q, k, v)
    o2 = ref.flash_attention(q, k, v)
    emit("kernel_flash_attn_interp_s256",
         _timeit(lambda: jax.block_until_ready(
             ops.flash_attention(q, k, v)), n=1),
         f"allclose_err={float(jnp.abs(o1 - o2).max()):.1e}")

    from repro.models.layers import cross_entropy, fused_cross_entropy
    h = jnp.asarray(rng.normal(size=(4, 512, 256)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(8192, 256)), jnp.float32) * 0.05
    labels = jnp.asarray(rng.integers(0, 8192, (4, 512)), jnp.int32)
    naive = jax.jit(lambda h_, t_, l_: cross_entropy(
        jnp.einsum("bsd,vd->bsv", h_, t_), l_))
    fused = jax.jit(lambda h_, t_, l_: fused_cross_entropy(h_, t_, l_,
                                                           chunk=512))
    us_n = _timeit(lambda: jax.block_until_ready(naive(h, table, labels)))
    us_f = _timeit(lambda: jax.block_until_ready(fused(h, table, labels)))
    emit("fused_ce_vs_naive", us_f, f"naive_us={us_n:.1f}")


def bench_federated(generations: int):
    from benchmarks import fed_nas
    api = fed_nas.build_api()
    clients = fed_nas.build_clients(6, iid=True, n=1200)

    xb, yb = clients[0].train
    from repro.core.federated import make_client_update, make_evaluator
    update = make_client_update(api)
    evaluate = make_evaluator(api)
    key = jnp.asarray(np.array([1, 2, 3, 0]), jnp.int32)
    params = api.init(jax.random.PRNGKey(0))
    jax.block_until_ready(update(params, key, xb, yb, 0.1))
    emit("client_update_1epoch", _timeit(
        lambda: jax.block_until_ready(update(params, key, xb, yb, 0.1)),
        n=2), f"batches={xb.shape[0]}")
    emit("client_evaluate", _timeit(
        lambda: jax.block_until_ready(evaluate(params, key, *clients[0].test)),
        n=2), "")

    # Sec IV.G: RT vs offline per-generation cost
    t0 = time.time()
    hist_rt = fed_nas.run_rt(api, clients, generations, population=4)
    rt_s = (time.time() - t0) / generations
    t0 = time.time()
    off_gens = max(1, generations // 2)
    hist_off = fed_nas.run_offline(api, clients, off_gens, population=4)
    off_s = (time.time() - t0) / off_gens
    ratio = off_s / rt_s
    emit("sec4g_rt_per_generation", rt_s * 1e6,
         f"passes={hist_rt['train_passes'][-1]}")
    emit("sec4g_offline_per_generation", off_s * 1e6,
         f"speedup_rt={ratio:.1f}x;paper_claims>=5x")
    emit("sec4g_upload_gb_rt", hist_rt["up_gb"][-1] * 1e6,
         f"offline_gb={hist_off['up_gb'][-1]:.4f}")

    # Fig 8 Pareto front + Fig 9 stability + Table IV vs fixed baseline
    front = fed_nas.summarize_front(api, hist_rt)
    emit("fig8_pareto_iid", len(front),
         ";".join(f"err={r['err']:.3f}@{r['flops']/1e6:.1f}MMac"
                  for r in front[:4]))
    best_curve = hist_rt["best_err"]
    emit("fig9_realtime_best_err_final", best_curve[-1] * 1e6,
         f"start={best_curve[0]:.3f};min={min(best_curve):.3f}")

    base = fed_nas.run_fixed_baseline(api, clients, rounds=generations)
    high = min(front, key=lambda r: r["err"])
    from repro.core import nsga2
    if len(front) > 1:
        knee_objs = np.asarray([[r["err"], r["flops"]] for r in front])
        knee = front[nsga2.knee_point(knee_objs, list(range(len(front))))]
    else:
        knee = high
    emit("table4_vs_baseline", base["err"][-1] * 1e6,
         f"high_err={high['err']:.3f};knee_err={knee['err']:.3f};"
         f"base_flops={base['flops']/1e6:.1f}M;"
         f"high_flops={high['flops']/1e6:.1f}M;"
         f"knee_flops={knee['flops']/1e6:.1f}M")


def bench_rt_property():
    """Hillclimb C2 (EXPERIMENTS §Perf): the supernet's traced choice key
    means ONE compilation serves every sub-model in the population — the
    property that makes the search real-time on the server.  Compare wall
    time of N distinct keys through the traced-key step vs re-jitting a
    static-key step per key (what per-key PyTorch module rebuilds cost)."""
    import numpy as np
    from repro.configs import get_config
    from repro.launch.train import init_opt, make_train_step
    from repro.models import transformer as tr

    cfg = get_config("qwen1.5-0.5b", smoke=True).replace(supernet=True)
    params = tr.init_params(jax.random.PRNGKey(0), cfg)
    opt = init_opt(params)
    rng = np.random.default_rng(0)
    keys = [jnp.asarray(rng.integers(0, 4, cfg.num_layers), jnp.int32)
            for _ in range(5)]
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32),
             "labels": jnp.zeros((2, 64), jnp.int32)}

    step = jax.jit(make_train_step(cfg, remat=False))
    jax.block_until_ready(
        step(params, opt, dict(batch, choice_key=keys[0]))[2])  # compile once
    t0 = time.time()
    for k in keys:
        jax.block_until_ready(step(params, opt, dict(batch, choice_key=k))[2])
    traced_s = time.time() - t0

    t0 = time.time()
    for k in keys:
        fn = jax.jit(lambda p, o, b, kk=k: make_train_step(cfg, remat=False)(
            p, o, dict(b, choice_key=kk)))
        jax.block_until_ready(fn(params, opt, batch)[2])
    static_s = time.time() - t0
    emit("c2_realtime_traced_5keys", traced_s / 5 * 1e6,
         f"static_rejit_us={static_s/5*1e6:.0f};speedup={static_s/traced_s:.1f}x")


def bench_roofline():
    from benchmarks import roofline_table
    recs = roofline_table.load_records()
    counts = {}
    for r in recs:
        d = r.get("dominant", "?")
        counts[d] = counts.get(d, 0) + 1
    emit("roofline_records", len(recs),
         ";".join(f"{k}={v}" for k, v in sorted(counts.items())))
    for r in recs:
        if "compute_s" not in r:
            continue
        emit(f"roofline_{r['arch']}_{r['shape']}",
             max(r["compute_s"], r["memory_s"], r["collective_s"]) * 1e6,
             f"bound={r['dominant']};model/hlo="
             f"{r.get('useful_flops_ratio', 0):.2f}")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--generations", type=int, default=2,
                    help="NAS generations for the federated benches")
    ap.add_argument("--skip-federated", action="store_true")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    bench_micro()
    bench_rt_property()
    if not args.skip_federated:
        bench_federated(args.generations)
    bench_roofline()


if __name__ == "__main__":
    main()
