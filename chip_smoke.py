"""Chip smoke test: one full-width real-time federated NAS search on a TPU.

Drives the main path through the entry points a user calls: the paper's
12-block CIFAR supernet (``get_config("cifar-supernet")``, 26.1 M
parameters, 32x32 inputs) searched by ``FedEngine`` + ``RealTimeNas`` on
the fused ``vmap`` backend for three generations, with random weights and
synthetic data made from ``--seed``.  It checks what comes out, then runs
the compiled Pallas kernels of the main path (Algorithm 3's
``fill_aggregate`` and the ``int8:pallas`` uplink codec) against their
XLA routes on the same chip.

    python chip_smoke.py              # one chip: search + kernel phase
    python chip_smoke.py --chips 4    # only the mesh backend over four
                                      # chips against vmap on one

It needs a TPU and exits non-zero on any other platform.  Every check
raises, so any failed phase exits non-zero; the last line of standard
output, printed only when every phase passed, is one JSON object naming
the device.  ``JAX_COMPILATION_CACHE_DIR`` chooses the compile cache
(default: ``.jax_cache`` in the checkout).  This is a smoke test: its
times include compilation and are not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

NUM_SAMPLES = 2000        # synthetic CIFAR-shaped images, IID over clients
NUM_CLIENTS = 16
BATCH = 50
POPULATION = 10
GENERATIONS = 3
KERNEL_M = 16             # uploads in the direct fill_aggregate call
# The route comparisons (pallas vs xla, mesh vs vmap) run at the
# learning rate of the repository's parity tests: SGD through batch norm
# without affine parameters amplifies rounding differences faster at the
# search's default lr0 of 0.1.
PARITY_LR0 = 0.01

# Tolerances.  A wrong kernel or a wrong reduction errs by the size of
# an SGD update (>= 1e-3 on this master); what may differ is f32
# rounding.  The kernel routes sum in another order than XLA, and the TPU
# rounds convolution inputs through bf16 at default precision, so an ulp
# of difference in an aggregated weight can cross a bf16 rounding step
# and the next local SGD step carries it: hence 1e-4 on the masters, ten
# times the repository's CPU parity bound.  One flipped test prediction
# moves an error objective by ERR_STEP = 1 / (clients * test batch);
# near-tied logits can flip, so objectives may differ by a few steps.
PALLAS_MASTER_TOL = 1e-4
ERR_STEP = 1.0 / (NUM_CLIENTS * BATCH)
ERR_TOL = 3 * ERR_STEP
# fill_aggregate vs its jnp oracle on one call: the repo's kernel sweep
# bound for float32 (tests/test_kernels.py)
KERNEL_TOL = 2e-5
# Mesh vs vmap: the mesh runs its own program and sums its four
# devices' group partials in a psum, vmap sums the groups one after the
# other.  On a v5e two one-chip programs that compute the same
# generation (this script's fused pallas and xla routes, at PARITY_LR0)
# differed by 6.28e-4 on the master and 6.25e-3 on the objectives after
# one generation; the bounds are ten times that, or ten times what a
# repeat of the one-chip run differs by if that is larger.
MESH_MASTER_TOL = 10 * 6.28e-4
MESH_ERR_TOL = 10 * 6.25e-3


FAILED = []


def check(cond, what: str) -> None:
    """Record one check; a failed one makes the script exit non-zero
    after the phases have run (an exception still ends it at once)."""
    if not cond:
        FAILED.append(what)
    print(f"  {'ok' if cond else 'FAILED'}  {what}", flush=True)


def require_tpu(count: int):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{platform!r} ({len(devices)} device(s))")
    if len(devices) < count:
        sys.exit(f"chip_smoke: --chips {count} needs {count} TPU devices; "
                 f"JAX found {len(devices)}")
    return devices


def make_fleet(seed: int):
    from repro.data import make_classification, make_clients, partition_iid
    x, y = make_classification(seed, NUM_SAMPLES, image=32)
    return make_clients(x, y, partition_iid(seed, NUM_SAMPLES, NUM_CLIENTS),
                        batch=BATCH, test_batch=BATCH)


def search(api, clients, seed: int, generations: int, **kw):
    from repro.engine import FedEngine, RunConfig
    eng = FedEngine(api, clients, RunConfig(
        population=POPULATION, generations=generations, seed=seed, **kw))
    return eng, eng.run()


def max_leaf_diff(a, b) -> float:
    import jax
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def all_finite(tree) -> bool:
    import jax
    import jax.numpy as jnp
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


def obj_diff(a, b) -> float:
    import numpy as np
    return max(float(np.max(np.abs(ra.objs - rb.objs)))
               for ra, rb in zip(a.reports, b.reports))


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use",
                                               "not reported")


def check_search(eng, res, generations: int) -> None:
    """The checks every full search must pass."""
    import numpy as np
    check(all_finite(res.extras["final_master"]), "final master is finite")
    objs = np.concatenate([r.objs for r in res.reports])
    check(np.all(np.isfinite(objs)), "every objective is finite")
    check(np.all((objs[:, 0] >= 0) & (objs[:, 0] <= 1)),
          "every error objective lies in [0, 1]")
    check(res.stats.up_bytes > 0 and res.stats.down_bytes > 0,
          f"CommStats up {res.stats.up_bytes:.0f} B, "
          f"down {res.stats.down_bytes:.0f} B are > 0")
    check(eng.backend.dispatches == 2 * generations + 1,
          f"dispatches == 2*{generations}+1 ({eng.backend.dispatches})")


def search_phase(api, clients, seed: int, device) -> None:
    from repro.obs import TelemetryConfig
    print(f"search: {api.cfg.name}, {api.num_blocks} choice blocks, "
          f"{api.master_params():,} params; {NUM_CLIENTS} clients, "
          f"population {POPULATION}, {GENERATIONS} fused vmap generations",
          flush=True)
    eng, res = search(api, clients, seed, GENERATIONS, backend="vmap",
                      telemetry=TelemetryConfig(sink="memory"))
    for r in res.reports:
        note = "  (includes compile)" if r.gen == 1 else ""
        print(f"  gen {r.gen} round_s {r.round_s!r}{note}", flush=True)
    print(f"  best_err {res.reports[-1].best_err!r}", flush=True)
    check_search(eng, res, GENERATIONS)
    tc = res.telemetry.trace_counts
    check(tc == {"fused_fill": 1, "fused_eval_shared": 1},
          f"each fused program traced once {tc}")
    print(f"  peak_bytes_in_use {peak_bytes(device)}", flush=True)
    return res.extras["final_master"]


def kernel_phase(api, clients, seed: int, master) -> None:
    """The compiled kernels against their XLA routes; ``master`` is the
    search phase's final master."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.comm import make_codec
    from repro.core import choice
    from repro.core.aggregate import _flat_f32, fill_aggregate_stacked
    from repro.kernels import ops, ref

    def close(a, b):
        """Max |a - b| and whether every element is within KERNEL_TOL
        (relative, plus as much absolute) — the repo's kernel sweep."""
        err = [jnp.abs(x - y) for x, y in zip(jax.tree.leaves(a),
                                              jax.tree.leaves(b))]
        ok = all(bool(jnp.all(e <= KERNEL_TOL * (1.0 + jnp.abs(y))))
                 for e, y in zip(err, jax.tree.leaves(b)))
        return max(float(jnp.max(e)) for e in err), ok

    p = api.master_params()
    m = NUM_CLIENTS // POPULATION
    print(f"kernel phase: P = {p:,}; the engine's fill_aggregate calls "
          f"take m = {m} upload(s) (clients per group)", flush=True)

    # -- one direct kernel call at KERNEL_M uploads vs the jnp oracle
    flat = _flat_f32(jax.tree.leaves(master))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    cl = flat[None] + 0.01 * jax.random.normal(k1, (KERNEL_M, p))
    mk = jax.random.bernoulli(k2, 0.5, (KERNEL_M, p)).astype(jnp.float32)
    w = jax.random.uniform(k3, (KERNEL_M,))
    w = w / jnp.sum(w)
    check("tpu_custom_call" in
          ops._fill_aggregate_jit.lower(cl, mk, w, flat).as_text(),
          "fill_aggregate lowers to a compiled tpu_custom_call")
    d, ok = close(ops.fill_aggregate(cl, mk, w, flat),
                  ref.fill_aggregate(cl, mk, w, flat))
    check(ok, f"fill_aggregate m={KERNEL_M} vs jnp oracle within "
              f"{KERNEL_TOL} (max|diff| {d!r})")
    del cl, mk

    # -- Algorithm 3 alone, both routes, on the same master and uploads:
    # one m-upload chunk per individual, keys drawn like the search's
    rng = np.random.default_rng(seed)
    leaves, treedef = jax.tree.flatten(master)
    chunks = []
    for g in range(POPULATION):
        ks = jax.random.split(jax.random.PRNGKey(seed + 1 + g), len(leaves))
        up = [x[None] + 0.01 * jax.random.normal(k, (m,) + x.shape)
              for x, k in zip(leaves, ks)]
        key = choice.random_key(rng, api.num_blocks)
        chunks.append((jax.tree.unflatten(treedef, up),
                       np.tile(key, (m, 1)),
                       np.full(m, clients[g].weight, np.float32)))
    routes = {b: fill_aggregate_stacked(master, chunks, api.trained_mask,
                                        backend=b) for b in ("xla", "pallas")}
    d, ok = close(routes["pallas"], routes["xla"])
    check(ok, f"Algorithm 3, {POPULATION} chunks: pallas vs xla within "
              f"{KERNEL_TOL} (max|diff| {d!r})")
    del chunks, routes

    # -- one generation through the engine with each route.  Non-fused,
    # both routes train with the same SGD program, so Algorithm 3 gets
    # the same master and uploads; fused, the pallas route trains in its
    # own program (uploads out, kernel outside), which the TPU may round
    # differently, so that pair is checked for health and reported.
    for fused in (False, True):
        runs = {agg: search(api, clients, seed, 1, backend="vmap",
                            fused=fused, aggregate_backend=agg,
                            lr0=PARITY_LR0)[1]
                for agg in ("xla", "pallas")}
        a, b = runs["xla"], runs["pallas"]
        tag = "fused" if fused else "non-fused"
        d_master = max_leaf_diff(a.extras["final_master"],
                                 b.extras["final_master"])
        d_obj = obj_diff(a, b)
        print(f"  {tag} generation: pallas vs xla master max|diff| "
              f"{d_master!r}, objectives max|diff| {d_obj!r}", flush=True)
        check(all_finite(b.extras["final_master"])
              and np.all(np.isfinite(b.reports[0].objs)),
              f"{tag} pallas generation: finite master and objectives")
        check(a.stats == b.stats, f"{tag} pallas vs xla CommStats identical")
        if not fused:
            check(d_master <= PALLAS_MASTER_TOL,
                  f"{tag} pallas vs xla master within {PALLAS_MASTER_TOL}")
            check(d_obj <= ERR_TOL,
                  f"{tag} pallas vs xla objectives within {ERR_TOL}")
        del runs, a, b

    # -- int8 uplink codec: kernel quantize/dequantize vs the XLA route
    scale = jnp.max(jnp.abs(flat)) / 127.0
    for fn, args in ((ops.quantize_int8, (flat, scale)),
                     (ops.dequantize_int8,
                      (jnp.zeros((p,), jnp.int8), scale))):
        check("tpu_custom_call" in fn.lower(*args).as_text(),
              f"{fn.__name__} lowers to a compiled tpu_custom_call")
    q_k = ops.quantize_int8(flat, scale)
    q_r = ref.quantize_int8(flat, scale)
    code = np.abs(np.asarray(q_k, np.int32) - np.asarray(q_r, np.int32))
    # the kernel and XLA divide by the scale separately; a value within
    # an ulp of a rounding tie may land on the neighbouring code
    check(code.max() <= 1 and np.count_nonzero(code) <= p * 1e-5,
          f"int8 codes: {np.count_nonzero(code)} of {p} differ, "
          f"by at most {code.max()}")
    check(bool(jnp.all(ops.dequantize_int8(q_k, scale)
                       == ref.dequantize_int8(q_k, scale))),
          "int8 dequantize: kernel == XLA exactly")
    rt_k = make_codec("int8:pallas").roundtrip(master)
    rt_r = make_codec("int8").roundtrip(master)
    steps = [float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(x)) / 127.0))
             for a, b, x in zip(jax.tree.leaves(rt_k), jax.tree.leaves(rt_r),
                                jax.tree.leaves(master))
             if float(jnp.max(jnp.abs(x))) > 0]
    check(max(steps) <= 1.0,
          f"int8:pallas vs int8 codec roundtrip within one grid step "
          f"(max {max(steps)!r} steps)")


def mesh_phase(api, clients, seed: int) -> None:
    """``backend="mesh"`` over four chips against ``backend="vmap"`` on
    one, same seed and config; the vmap run is repeated to measure how
    far two one-chip runs differ."""
    print(f"mesh phase: {GENERATIONS} generations, vmap on one chip (x2) "
          f"vs mesh over 4 chips", flush=True)
    _, a = search(api, clients, seed, GENERATIONS, backend="vmap",
                  lr0=PARITY_LR0)
    _, b = search(api, clients, seed, GENERATIONS, backend="vmap",
                  lr0=PARITY_LR0)
    rep_master = max_leaf_diff(a.extras["final_master"],
                               b.extras["final_master"])
    rep_obj = obj_diff(a, b)
    print(f"  vmap repeat: master max|diff| {rep_master!r}, objectives "
          f"max|diff| {rep_obj!r}", flush=True)

    from repro.engine import FedEngine, RunConfig
    eng = FedEngine(api, clients, RunConfig(
        population=POPULATION, generations=GENERATIONS, seed=seed,
        lr0=PARITY_LR0, backend="mesh"))
    placed = []
    put_pop = eng.backend._put_pop

    def recording_put_pop(arr):
        out = put_pop(arr)
        placed.append((out.shape,
                       {s.device for s in out.addressable_shards},
                       out.sharding.is_fully_replicated))
        return out

    eng.backend._put_pop = recording_put_pop
    m = eng.run()
    for r in m.reports:
        note = "  (includes compile)" if r.gen == 1 else ""
        print(f"  mesh gen {r.gen} round_s {r.round_s!r}{note}", flush=True)
    check_search(eng, m, GENERATIONS)
    check(eng.backend.num_devices == 4,
          f"mesh num_devices == 4 ({eng.backend.num_devices})")
    check(placed and all(len(devs) == 4 and not rep
                         for _, devs, rep in placed),
          f"all {len(placed)} population-stacked arrays are split over "
          f"4 distinct devices")
    check(dataclasses.asdict(m.stats) == dataclasses.asdict(a.stats),
          "mesh vs vmap CommStats byte-identical")
    tol_master = max(MESH_MASTER_TOL, 10 * rep_master)
    tol_obj = max(MESH_ERR_TOL, 10 * rep_obj)
    d_master = max_leaf_diff(a.extras["final_master"],
                             m.extras["final_master"])
    d_obj = obj_diff(a, m)
    check(d_master <= tol_master,
          f"mesh vs vmap master max|diff| {d_master!r} <= {tol_master!r}")
    check(d_obj <= tol_obj,
          f"mesh vs vmap objectives max|diff| {d_obj!r} <= {tol_obj!r}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the data, the partition and the search")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only mesh over four chips vs vmap on one")
    args = ap.parse_args()

    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import cache_stats, enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    from repro.configs import get_config
    from repro.core import make_api
    api = make_api(get_config("cifar-supernet"))
    clients = make_fleet(args.seed)
    if args.chips == 4:
        mesh_phase(api, clients, args.seed)
    else:
        master = search_phase(api, clients, args.seed, devices[0])
        kernel_phase(api, clients, args.seed, master)
    print(f"peak_bytes_in_use over the run {peak_bytes(devices[0])}",
          flush=True)
    stats = cache_stats()
    if FAILED:
        sys.exit(f"chip_smoke: {len(FAILED)} check(s) failed: {FAILED}")
    print(f"compile cache: {stats['hits']} hits, {stats['misses']} misses "
          f"({'hit' if stats['hits'] else 'not hit'})", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
