"""Shared harness for the paper-shaped federated NAS experiments.

Scaled to this container (16x16 synthetic images, tens of generations) —
the *relative* claims of the paper (RT vs offline cost, Pareto shape,
FLOPs reduction vs the fixed baseline) are what the benchmarks validate;
see DESIGN.md Section 8 for the simulation boundary.

Everything routes through ``repro.engine.FedEngine``; the
``engine_backend`` argument selects the client-execution path ("loop" =
reference per-pair dispatch, "vmap" = ClientBatch-stacked, "mesh" =
population sharded over a jax device mesh).  Run

    PYTHONPATH=src python benchmarks/fed_nas.py

to compare the backend variants — loop, vmap and mesh, each of the
batched pair with the fused-generation path on AND off — on the default
cross-device config (many small clients — the regime where dispatch
count, not compute, is the bottleneck) AND the payload codecs
(``--mode codecs``: per-codec wire bytes, compression ratio vs fp32,
and the int8+error-feedback vs fp32 search trajectory; ``--out`` writes
the JSON that ``benchmarks/results/`` tracks).  ``--mode availability``
sweeps the real-time client model (``ClientSimConfig``): 0-50%
post-download dropout under IID and Dirichlet partitions plus a
deterministic-straggler scenario, reporting search quality, survivor
counts and the wasted-download ledger.  ``--mode scale`` sweeps the
client axis 10^2 -> 10^6 at a fixed per-round participant count over the
lazy stack (``VirtualClassification`` sample source + index-space
``partition_iid`` + ``ClientFleet``): per-round wall time and peak live
bytes must stay flat — fleet size only ever touches O(num_clients)
integer vectors, never materialized data — and the sweep lands in
``benchmarks/results/scale.json`` plus a ``"scale"`` point inside
``BENCH_engine.json``.  ``--mode backends``
writes ``BENCH_engine.json`` (dispatches/gen, wall-clock/gen, peak live
bytes per variant, the fused speedups and the scalar-vs-batched-key
measurement) — the repo root keeps the CI-host point of that perf
trajectory and CI uploads it as an artifact.  ``--mode obs`` measures
the telemetry subsystem itself (``repro.obs``): steady-state overhead
with telemetry on vs off at the same dispatch-bound point (<3%
acceptance, recorded as an ``"obs"`` block inside
``BENCH_engine.json``), the phase-time breakdown from the structured
round events, the fused recompile counters (a nonzero exit on any
unexpected retrace — the CI gate) and a JSONL round-event log
(``--obs-out``).  As a script it forces an
8-way host device mesh (``--xla_force_host_platform_device_count=8``)
so the mesh backend has devices to shard over; equivalently set
XLA_FLAGS yourself.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

if __name__ == "__main__" and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    # must happen before the first jax import; library importers
    # (examples, tests) are left untouched
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

import jax
import numpy as np

from repro.configs import get_config
from repro.core import make_api, nsga2
from repro.data import ClientFleet, VirtualClassification, \
    make_classification, make_clients, partition_dirichlet, partition_iid, \
    partition_label
from repro.engine import ClientSimConfig, FedAvgBaseline, FedEngine, \
    OfflineNas, RealTimeNas, RunConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import PeakLiveBytes, steady_mean

IMAGE = 16
RESNET_LIKE_KEY = np.ones(4, dtype=np.int32)   # all-residual master path


def build_clients(num_clients: int, iid: bool = True, seed: int = 0,
                  n: int = 2000, batch: int = 50, test_batch: int = 50,
                  image: int = IMAGE, partition: Optional[str] = None):
    x, y = make_classification(seed, n, image=image, signal=1.2, noise=0.8)
    partition = partition or ("iid" if iid else "label")
    if partition == "iid":
        shards = partition_iid(seed, n, num_clients)
    elif partition == "label":
        shards = partition_label(seed, y, num_clients, classes_per_client=5)
    elif partition == "dirichlet":
        shards = partition_dirichlet(seed, y, num_clients, alpha=0.5)
    else:
        raise ValueError(f"unknown partition {partition!r}")
    return make_clients(x, y, shards, batch=batch, test_batch=test_batch)


def build_api():
    return make_api(get_config("cifar-supernet", smoke=True))


def run_rt(api, clients, generations: int, population: int = 6,
           seed: int = 0, backend: str = "xla",
           engine_backend: str = "loop") -> Dict:
    rc = RunConfig(population=population, generations=generations,
                   seed=seed, aggregate_backend=backend,
                   backend=engine_backend)
    return FedEngine(api, clients, rc,
                     strategy=RealTimeNas()).run().history()


def run_offline(api, clients, generations: int, population: int = 6,
                seed: int = 0, engine_backend: str = "loop") -> Dict:
    rc = RunConfig(population=population, generations=generations,
                   seed=seed, backend=engine_backend)
    return FedEngine(api, clients, rc,
                     strategy=OfflineNas()).run().history()


def run_fixed_baseline(api, clients, rounds: int, key=RESNET_LIKE_KEY,
                       seed: int = 0, engine_backend: str = "loop") -> Dict:
    """FedAvg on a fixed architecture (the paper's ResNet18 role)."""
    rc = RunConfig(generations=rounds, seed=seed, backend=engine_backend)
    res = FedEngine(api, clients, rc,
                    strategy=FedAvgBaseline(key)).run()
    return {"err": [r.best_err for r in res.reports],
            "flops": res.extras["flops"],
            "params": res.extras["params"],
            "stats": res.stats}


def _max_param_diff(a, b) -> float:
    return float(max(
        np.abs(np.asarray(p) - np.asarray(q)).max()
        for p, q in zip(jax.tree.leaves(a.extras["final_master"]),
                        jax.tree.leaves(b.extras["final_master"]))))


def _max_err_diff(a, b) -> float:
    return float(max(
        np.abs(np.asarray(x.objs) - np.asarray(y.objs)).max()
        for x, y in zip(a.reports, b.reports)))


def _variant(name: str):
    """'vmap' -> ('vmap', fused=True); 'vmap-nofused' -> ('vmap', False).
    The loop backend has no fused path (the flag is ignored there)."""
    base, _, suffix = name.partition("-")
    return base, suffix != "nofused"


BACKEND_VARIANTS = ("loop", "vmap", "vmap-nofused", "mesh", "mesh-nofused")


def compare_backends(api=None, clients=None, generations: int = 5,
                     population: int = 10, seed: int = 0,
                     backends=BACKEND_VARIANTS) -> Dict:
    """Same search on every execution-backend variant (``'vmap'`` =
    fused, ``'vmap-nofused'`` = per-bucket dispatches, etc.): wall clock
    (total and steady-state per generation, from ``RoundReport.round_s``),
    dispatch counts, peak live bytes, and result agreement (vs the first
    variant, plus the fused-vs-nonfused and mesh-vs-vmap pairs the fused
    path is certified against).  The default client set is the
    paper-scale cross-device regime — population 10 over 16 clients with
    minibatch-sized local shards, one local pass per round — where
    dispatch overhead, not compute, bounds the generation wall clock
    (larger per-client workloads converge to hardware-limited, where
    fused ~= non-fused by construction; pass ``clients`` to measure that
    end)."""
    import dataclasses

    api = api or build_api()
    if clients is None:
        clients = build_clients(16, iid=True, n=64, batch=2,
                                test_batch=2, image=8)
    out: Dict = {"generations": generations, "population": population,
                 "clients": len(clients), "devices": len(jax.devices()),
                 "backends": list(backends)}
    hists = {}
    for name in backends:
        base, fused = _variant(name)
        eng = FedEngine(api, clients,
                        RunConfig(population=population,
                                  generations=generations, seed=seed,
                                  backend=base, fused=fused))
        # peak is growth over the pre-run baseline (PeakLiveBytes), so
        # arrays retained by earlier variants (their final masters in
        # `hists`) don't bias later variants' numbers
        pk = PeakLiveBytes()
        t0 = time.time()
        res = eng.run(callback=pk.sample)
        wall = time.time() - t0
        rounds = [r.round_s for r in res.reports]
        hists[name] = res
        out[name] = {"backend": base, "fused": fused,
                     "wall_s": wall,
                     "steady_gen_s": steady_mean(rounds),
                     "round_s": [round(r, 4) for r in rounds],
                     "peak_live_bytes": pk.growth,
                     "dispatches": eng.backend.dispatches,
                     "dispatches_per_gen": eng.backend.dispatches / generations}
    ref = hists[backends[0]]
    for name in backends[1:]:
        out[name]["max_err_diff"] = _max_err_diff(ref, hists[name])
        out[name]["max_param_diff"] = _max_param_diff(ref, hists[name])
    for base in ("vmap", "mesh"):      # the acceptance pair: fused wins
        f, nf = base, f"{base}-nofused"
        if f in hists and nf in hists:
            out[f"{base}_fused_vs_nonfused"] = {
                "steady_speedup": (out[nf]["steady_gen_s"]
                                   / out[f]["steady_gen_s"]),
                "total_speedup": out[nf]["wall_s"] / out[f]["wall_s"],
                "max_err_diff": _max_err_diff(hists[nf], hists[f]),
                "max_param_diff": _max_param_diff(hists[nf], hists[f]),
                "comm_stats_equal": dataclasses.asdict(hists[nf].stats)
                == dataclasses.asdict(hists[f].stats),
            }
    if "vmap" in hists and "mesh" in hists:
        out["mesh_vs_vmap"] = {
            "comm_stats_equal": dataclasses.asdict(hists["mesh"].stats)
            == dataclasses.asdict(hists["vmap"].stats),
            "max_param_diff": _max_param_diff(hists["vmap"], hists["mesh"]),
            "max_err_diff": _max_err_diff(hists["vmap"], hists["mesh"]),
        }
    if backends[0] == "loop" and "vmap" in hists:  # legacy two-way summary
        out["speedup_total"] = out["loop"]["wall_s"] / out["vmap"]["wall_s"]
        out["speedup_steady"] = (out["loop"]["steady_gen_s"]
                                 / out["vmap"]["steady_gen_s"])
        out["max_err_diff"] = out["vmap"]["max_err_diff"]
        out["max_param_diff"] = out["vmap"]["max_param_diff"]
    return out


def measure_key_batching(api=None, clients=None, n_keys: int = 12,
                         repeats: int = 3, seed: int = 0) -> Dict:
    """Re-measure the "batched keys lower ``lax.switch`` to
    compute-all-branches-and-select" trade, separately for training and
    forward-only evaluation, now that fused execution makes dispatch
    count equal (one program either way): scalar-key ``lax.scan`` vs
    batched-key ``vmap`` over the same stacked shards.  The winner per
    phase is the documented default — see docs/architecture.md "Fused
    generations"."""
    from repro.core.federated import client_update_fn, eval_count_fn

    api = api or build_api()
    if clients is None:
        clients = build_clients(8, iid=True, n=480, batch=20, test_batch=20)
    rng = np.random.default_rng(seed)
    keys = jax.numpy.asarray(
        rng.integers(0, 4, size=(n_keys, api.num_blocks)), np.int32)
    params = api.init(jax.random.PRNGKey(seed))
    ev = eval_count_fn(api)
    upd = client_update_fn(api, 1, 0.5)
    import jax.numpy as jnp
    exb = jnp.stack([jnp.asarray(c.test[0]) for c in clients])
    eyb = jnp.stack([jnp.asarray(c.test[1]) for c in clients])
    txb = jnp.stack([jnp.asarray(c.train[0]) for c in clients])
    tyb = jnp.stack([jnp.asarray(c.train[1]) for c in clients])

    def eval_one(p, key):
        def per_client(a, c):
            return a + ev(p, key, c[0], c[1]), None
        return jax.lax.scan(per_client, jnp.zeros((), jnp.int32),
                            (exb, eyb))[0]

    def train_one(p, key):
        def per_client(_, c):
            return None, upd(p, key, c[0], c[1], 0.05)
        return jax.lax.scan(per_client, None, (txb, tyb))[1]

    variants = {
        "eval": {
            "scalar_key_scan": jax.jit(lambda p, ks: jax.lax.scan(
                lambda _, k: (None, eval_one(p, k)), None, ks)[1]),
            "batched_key_vmap": jax.jit(lambda p, ks: jax.vmap(
                lambda k: eval_one(p, k))(ks)),
        },
        "train": {
            "scalar_key_scan": jax.jit(lambda p, ks: jax.lax.scan(
                lambda _, k: (None, train_one(p, k)), None, ks)[1]),
            "batched_key_vmap": jax.jit(lambda p, ks: jax.vmap(
                lambda k: train_one(p, k))(ks)),
        },
    }

    def bench(fn):
        jax.block_until_ready(fn(params, keys))      # compile
        t0 = time.time()
        for _ in range(repeats):
            jax.block_until_ready(fn(params, keys))
        return (time.time() - t0) / repeats

    rep: Dict = {"n_keys": n_keys, "clients": len(clients)}
    for phase, fns in variants.items():
        s = bench(fns["scalar_key_scan"])
        v = bench(fns["batched_key_vmap"])
        rep[phase] = {"scalar_key_scan_s": s, "batched_key_vmap_s": v,
                      "vmap_over_scan": v / s,
                      "winner": ("scalar_key_scan" if s <= v
                                 else "batched_key_vmap")}
    return rep


def compare_codecs(api=None, clients=None, generations: int = 3,
                   population: int = 6, seed: int = 0,
                   engine_backend: str = "vmap",
                   codecs=("none", "cast", "int8", "topk")) -> Dict:
    """Same search under every payload codec (applied to both wire
    directions): wire vs fp32-logical bytes, the compression ratio vs
    the ``none`` baseline, and the search-quality cost (final best test
    error vs fp32).  This is the comm trajectory the paper's "reduce the
    local payload" claim asks for — ``benchmarks/results/`` records it
    next to the dispatch counts."""
    api = api or build_api()
    if clients is None:
        clients = build_clients(8, iid=True, n=480, batch=20, test_batch=20)
    out: Dict = {"generations": generations, "population": population,
                 "clients": len(clients), "engine_backend": engine_backend,
                 "codecs": {}}
    codecs = tuple(codecs)
    if codecs[:1] != ("none",):       # the fp32 baseline anchors the ratios
        codecs = ("none",) + tuple(c for c in codecs if c != "none")
    base = None
    for codec in codecs:
        eng = FedEngine(api, clients,
                        RunConfig(population=population,
                                  generations=generations, seed=seed,
                                  backend=engine_backend,
                                  uplink_codec=codec,
                                  downlink_codec=codec))
        t0 = time.time()
        res = eng.run()
        s = res.stats
        rec = {"down_bytes": s.down_bytes, "up_bytes": s.up_bytes,
               "down_wire_bytes": s.down_wire_bytes,
               "up_wire_bytes": s.up_wire_bytes,
               "best_err": float(res.reports[-1].best_err),
               "wall_s": time.time() - t0}
        wire_total = s.down_wire_bytes + s.up_wire_bytes
        if codec == "none":
            base = res
        base_total = (base.stats.down_wire_bytes
                      + base.stats.up_wire_bytes)
        rec["compression_vs_fp32"] = base_total / wire_total
        rec["best_err_delta_vs_fp32"] = (
            rec["best_err"] - float(base.reports[-1].best_err))
        out["codecs"][codec] = rec
    return out


def codec_trajectory(api=None, clients=None, generations: int = 30,
                     population: int = 6, seed: int = 0,
                     codec: str = "int8",
                     engine_backend: str = "vmap") -> Dict:
    """Long-horizon search-quality check: ``codec`` (with the engine's
    server-side error feedback) vs fp32 over ``generations`` rounds on
    the synthetic task.  The acceptance bar is the final best test-error
    rates within 2 points — i.e. compression costs bytes, not search
    quality."""
    api = api or build_api()
    if clients is None:
        clients = build_clients(8, iid=True, n=480, batch=20, test_batch=20)
    runs = {}
    for name, spec in (("fp32", "none"), (codec, codec)):
        res = FedEngine(api, clients,
                        RunConfig(population=population,
                                  generations=generations, seed=seed,
                                  backend=engine_backend,
                                  uplink_codec=spec,
                                  downlink_codec=spec)).run()
        runs[name] = res
    best = {k: [float(r.best_err) for r in v.reports]
            for k, v in runs.items()}
    return {"generations": generations, "codec": codec,
            "best_err": best,
            "final_fp32": best["fp32"][-1], "final_codec": best[codec][-1],
            "final_delta": best[codec][-1] - best["fp32"][-1],
            "wire_ratio": ((runs["fp32"].stats.down_wire_bytes
                            + runs["fp32"].stats.up_wire_bytes)
                           / (runs[codec].stats.down_wire_bytes
                              + runs[codec].stats.up_wire_bytes))}


def compare_availability(api=None, generations: int = 10,
                         population: int = 6, seed: int = 0,
                         num_clients: int = 8, samples: int = 960,
                         dropouts=(0.0, 0.1, 0.3, 0.5),
                         partitions=("iid", "dirichlet"),
                         engine_backend: str = "vmap") -> Dict:
    """The real-time availability sweep the paper's headline claim asks
    for: the same search under 0-50% post-download dropout, on IID and
    Dirichlet(0.5) partitions.  Reports the final best test error, the
    survivor counts and the wasted-download ledger per setting, plus a
    deterministic-straggler scenario (slowdown 10x vs deadline 2.0 —
    the stragglers miss every round).  dropout=0.0 is the synchronous
    baseline: it reproduces the no-sim trajectory bit for bit, so the
    sweep's deltas are pure availability effects."""
    api = api or build_api()
    out: Dict = {"generations": generations, "population": population,
                 "clients": num_clients, "engine_backend": engine_backend,
                 "partitions": {}}
    for part in partitions:
        clients = build_clients(num_clients, seed=seed, n=samples,
                                batch=10, test_batch=10, image=8,
                                partition=part)
        rows = {}
        for rate in dropouts:
            sim = ClientSimConfig(dropout=rate, seed=seed + 1)
            res = FedEngine(api, clients,
                            RunConfig(population=population,
                                      generations=generations, seed=seed,
                                      lr0=0.05, backend=engine_backend,
                                      client_sim=sim)).run()
            s = res.stats
            rows[str(rate)] = {
                "best_err": float(res.reports[-1].best_err),
                "mean_survivors": (float(np.mean(
                    [r.n_survivors for r in res.reports]))
                    if sim.is_active else float(num_clients)),
                "dropped_total": (int(sum(r.n_dropped
                                          for r in res.reports))
                                  if sim.is_active else 0),
                "up_mb": s.up_bytes / 1e6,
                "down_mb": s.down_bytes / 1e6,
                "wasted_down_mb": s.wasted_down_bytes / 1e6,
                "wasted_frac_of_down": (s.wasted_down_bytes
                                        / max(s.down_bytes, 1.0)),
            }
        out["partitions"][part] = rows
    # deterministic stragglers: a third of the fleet 10x slower than a
    # 2.0-round deadline — they receive every broadcast and finish none
    clients = build_clients(num_clients, seed=seed, n=samples,
                            batch=10, test_batch=10, image=8,
                            partition="iid")
    sim = ClientSimConfig(straggler_fraction=1 / 3,
                          straggler_slowdown=10.0, round_deadline=2.0,
                          seed=seed + 1)
    res = FedEngine(api, clients,
                    RunConfig(population=population,
                              generations=generations, seed=seed,
                              lr0=0.05, backend=engine_backend,
                              client_sim=sim)).run()
    out["stragglers"] = {
        "config": {"fraction": 1 / 3, "slowdown": 10.0, "deadline": 2.0},
        "best_err": float(res.reports[-1].best_err),
        "mean_survivors": float(np.mean([r.n_survivors
                                         for r in res.reports])),
        "wasted_down_mb": res.stats.wasted_down_bytes / 1e6,
        "wasted_frac_of_down": (res.stats.wasted_down_bytes
                                / max(res.stats.down_bytes, 1.0)),
    }
    return out


def scale_sweep(api=None,
                client_counts=(100, 1000, 10000, 100000, 1000000),
                sampled: int = 16, generations: int = 4,
                population: int = 10, seed: int = 0,
                samples_per_client: int = 8, image: int = 8,
                batch: int = 2, engine_backend: str = "vmap") -> Dict:
    """The million-client axis: the same search at a FIXED per-round
    participant count (``sampled``) while the fleet grows 10^2 -> 10^6.

    Every fleet is fully lazy — a ``VirtualClassification`` source (no
    dense dataset ever exists), an index-space ``partition_iid`` (one
    permutation + one cut vector) and a ``ClientFleet`` that
    materializes only the clients a round actually samples.  The
    acceptance claim is flatness: per-round steady-state wall time and
    peak live bytes within 2x across the whole sweep, because nothing
    downstream of participant sampling ever scales with ``len(fleet)``.
    ``partition_host_bytes`` (the O(dataset) permutation) is reported
    separately — it is the one intentionally size-dependent cost."""
    api = api or build_api()
    out: Dict = {"sampled": sampled, "generations": generations,
                 "population": population, "engine_backend": engine_backend,
                 "samples_per_client": samples_per_client,
                 "devices": len(jax.devices()), "points": {}}
    steadies, peaks = [], []
    for k in client_counts:
        n = k * samples_per_client
        t0 = time.time()
        source = VirtualClassification(seed, n, image=image,
                                       signal=1.2, noise=0.8)
        part = partition_iid(seed, n, k)
        fleet = ClientFleet(source, part, batch=batch, test_batch=batch,
                            cache_size=4 * sampled)
        build_s = time.time() - t0
        eng = FedEngine(api, fleet,
                        RunConfig(population=population,
                                  generations=generations, seed=seed,
                                  participation=sampled / k,
                                  backend=engine_backend))
        pk = PeakLiveBytes()
        t0 = time.time()
        res = eng.run(callback=pk.sample)
        wall = time.time() - t0
        rounds = [r.round_s for r in res.reports]
        steady = steady_mean(rounds)   # round 1 pays compile; excluded
        steadies.append(steady)
        peaks.append(pk.growth)
        out["points"][str(k)] = {
            "clients": k, "participation": sampled / k,
            "build_s": build_s, "wall_s": wall,
            "steady_round_s": steady,
            "round_s": [round(r, 4) for r in rounds],
            "peak_live_bytes": pk.growth,
            "partition_host_bytes": part.nbytes,
            "clients_materialized": fleet.materialized,
            "clients_cached": fleet.cached,
            "best_err": float(res.reports[-1].best_err),
        }
    # flatness over the WHOLE sweep (max/min, not endpoints — a bulge in
    # the middle is just as much a scaling leak)
    steady_ratio = max(steadies) / min(steadies)
    peak_ratio = max(peaks) / max(min(peaks), 1)
    out["summary"] = {
        "client_counts": list(client_counts),
        "steady_round_s": steadies,
        "peak_live_bytes": peaks,
        "steady_round_ratio": steady_ratio,
        "peak_live_ratio": peak_ratio,
        "flat_within_2x": steady_ratio < 2.0 and peak_ratio < 2.0,
    }
    return out


def measure_telemetry(api=None, clients=None, generations: int = 25,
                      population: int = 10, seed: int = 0,
                      engine_backend: str = "vmap", repeats: int = 3,
                      jsonl_path: Optional[str] = None) -> Dict:
    """Measure the telemetry subsystem itself (``repro.obs``) at the
    dispatch-bound backends point: steady-state per-generation wall time
    with ``RunConfig.telemetry`` off vs on (the <3% acceptance bar), the
    phase-time breakdown from the structured round events, and the fused
    recompile counters — ``fused_fill`` / ``fused_eval_shared`` must
    trace exactly once, and no program may retrace after round 1
    (``retrace_ok`` is the CI gate).

    ``repeats`` off/on pairs are interleaved (alternating which side
    leads each pair) and the *minimum steady round* of each side is
    compared (every round after the compile
    round, pooled across repeats).  Min, not mean: scheduler/contention
    noise is one-sided — it inflates a round but never deflates one —
    and on a shared machine it dwarfs the effect being measured
    (run-mean swings of ±20% are routine), so the per-side floor is the
    faithful estimate of what telemetry itself costs.  Timing runs use
    a memory sink; the last telemetry-on run writes the JSONL
    round-event log when ``jsonl_path`` is given (file recreated: one
    run's events, one line per generation)."""
    api = api or build_api()
    if clients is None:
        clients = build_clients(16, iid=True, n=64, batch=2,
                                test_batch=2, image=8)

    def run(telemetry):
        eng = FedEngine(api, clients,
                        RunConfig(population=population,
                                  generations=generations, seed=seed,
                                  backend=engine_backend,
                                  telemetry=telemetry))
        t0 = time.time()
        res = eng.run()
        return res, time.time() - t0

    repeats = max(1, repeats)
    off_rounds, on_rounds = [], []
    res_off = res_on = wall_off = wall_on = None
    for i in range(repeats):
        sink = "memory"
        if i == repeats - 1 and jsonl_path:
            os.makedirs(os.path.dirname(jsonl_path) or ".", exist_ok=True)
            if os.path.exists(jsonl_path):
                os.remove(jsonl_path)  # one run's events, not an append log
            sink = f"jsonl:{jsonl_path}"
        # alternate which side of the pair runs first: process-age
        # effects (allocator warm-up, growing jit caches) would
        # otherwise bias whichever side always ran second
        if i % 2 == 0:
            res_off, wall_off = run(None)
            res_on, wall_on = run({"sink": sink})
        else:
            res_on, wall_on = run({"sink": sink})
            res_off, wall_off = run(None)
        off_rounds += [r.round_s for r in res_off.reports[1:]
                       or res_off.reports]
        on_rounds += [r.round_s for r in res_on.reports[1:]
                      or res_on.reports]

    tel = res_on.telemetry
    off_best, on_best = min(off_rounds), min(on_rounds)
    unexpected = {k: v for k, v in tel.trace_counts.items() if v > 1}
    late = {str(e.gen): e.recompiles for e in tel.events[1:] if e.recompiles}
    return {
        "generations": generations, "population": population,
        "clients": len(clients), "engine_backend": engine_backend,
        "repeats": repeats,
        "steady_gen_s_off": off_best, "steady_gen_s_on": on_best,
        "wall_s_off": wall_off, "wall_s_on": wall_on,
        "overhead_frac": (on_best - off_best) / off_best,
        "overhead_under_3pct": (on_best - off_best) / off_best < 0.03,
        # the zero-overhead claim is about numerics before it is about
        # time: on and off must agree bit for bit
        "masters_bitwise_equal": _max_param_diff(res_off, res_on) == 0.0,
        "max_err_diff": _max_err_diff(res_off, res_on),
        "trace_counts": dict(tel.trace_counts),
        "unexpected_retraces": unexpected,
        "late_recompiles": late,
        "retrace_ok": not unexpected and not late,
        "phase_totals": {k: round(v, 4)
                         for k, v in sorted(tel.phase_totals().items())},
        "events": len(tel.events),
        "jsonl_path": jsonl_path,
    }


def summarize_front(api, hist) -> List[Dict]:
    """Final-generation Pareto front -> [{key, err, flops}] (Fig 8)."""
    objs = hist["objs"][-1]
    sel = nsga2.select(objs, len(hist["parent_keys"][-1]))
    front = nsga2.fast_non_dominated_sort(objs[sel])[0]
    out = []
    for i in front:
        out.append({"err": float(objs[sel][i, 0]),
                    "flops": float(objs[sel][i, 1])})
    out.sort(key=lambda r: r["flops"])
    return out


def save_history(path: str, hist: Dict, extra: Optional[Dict] = None):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    rec = {
        "gen": hist["gen"],
        "best_err": hist["best_err"],
        "knee_err": hist.get("knee_err"),
        "down_gb": hist["down_gb"],
        "up_gb": hist["up_gb"],
        "train_passes": hist["train_passes"],
        "wall_s": hist["wall_s"],
        "final_objs": np.asarray(hist["objs"][-1]).tolist(),
    }
    if extra:
        rec.update(extra)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def _run_backend_mode(args) -> Dict:
    clients = build_clients(args.clients, iid=True, n=args.samples,
                            batch=args.batch, test_batch=args.batch,
                            image=args.image)
    api = build_api()
    population = 10 if args.population is None else args.population
    # 25 generations by default: steady-state is ~30 ms/gen at the
    # dispatch-bound point, so short runs read timer noise — and the
    # recorded repo-root BENCH_engine.json must stay comparable run to
    # run (CI uses the same default)
    gens = 25 if args.generations is None else args.generations
    rep = compare_backends(api, clients, generations=gens,
                           population=population, seed=args.seed,
                           backends=tuple(args.backends))
    print(f"{rep['clients']} clients x {rep['generations']} generations, "
          f"population {rep['population']}, {rep['devices']} devices")
    ref = args.backends[0]
    for bk in args.backends:
        r = rep[bk]
        agree = (f" | vs {ref}: err {r['max_err_diff']:.1e} "
                 f"params {r['max_param_diff']:.1e}"
                 if "max_err_diff" in r else "")
        print(f"{bk:>13}: total {r['wall_s']:7.1f}s | steady "
              f"{r['steady_gen_s']:6.2f}s/gen | "
              f"{r['dispatches_per_gen']:7.1f} dispatches/gen | "
              f"{r['peak_live_bytes'] / 1e6:7.1f} MB live{agree}")
    if "speedup_total" in rep:
        print(f"vmap speedup vs loop: {rep['speedup_total']:.2f}x total, "
              f"{rep['speedup_steady']:.2f}x steady-state")
    for base in ("vmap", "mesh"):
        key = f"{base}_fused_vs_nonfused"
        if key in rep:
            fv = rep[key]
            print(f"{base} fused vs non-fused: "
                  f"{fv['steady_speedup']:.2f}x steady | err diff "
                  f"{fv['max_err_diff']:.1e} | param diff "
                  f"{fv['max_param_diff']:.1e} | CommStats equal: "
                  f"{fv['comm_stats_equal']}")
    if "mesh_vs_vmap" in rep:
        mv = rep["mesh_vs_vmap"]
        print(f"mesh vs vmap: CommStats equal: {mv['comm_stats_equal']} | "
              f"max err diff {mv['max_err_diff']:.2e} | "
              f"max master-param diff {mv['max_param_diff']:.2e}")
    if args.key_batching:
        kb = measure_key_batching(api)
        rep["key_batching"] = kb
        for phase in ("train", "eval"):
            r = kb[phase]
            print(f"key batching [{phase}]: scalar-key scan "
                  f"{r['scalar_key_scan_s']:.3f}s vs batched-key vmap "
                  f"{r['batched_key_vmap_s']:.3f}s "
                  f"({r['vmap_over_scan']:.2f}x) -> {r['winner']}")
    if args.bench_out:
        with open(args.bench_out, "w") as f:
            json.dump(rep, f, indent=1)
        print(f"wrote {args.bench_out}")
    return rep


def _run_codec_mode(args) -> Dict:
    api = build_api()
    population = 6 if args.population is None else args.population
    gens = 3 if args.generations is None else args.generations
    clients = build_clients(args.codec_clients, iid=True,
                            n=args.codec_samples, batch=20, test_batch=20)
    rep = compare_codecs(api, clients, generations=gens,
                         population=population, seed=args.seed,
                         codecs=tuple(args.codecs))
    print(f"\ncodecs ({rep['clients']} clients x {rep['generations']} "
          f"generations, population {rep['population']}, "
          f"{rep['engine_backend']} backend):")
    for codec, r in rep["codecs"].items():
        print(f"{codec:>6}: down {r['down_wire_bytes'] / 1e6:8.2f} MB | "
              f"up {r['up_wire_bytes'] / 1e6:8.2f} MB | "
              f"{r['compression_vs_fp32']:5.2f}x vs fp32 | "
              f"best err {r['best_err']:.3f} "
              f"({r['best_err_delta_vs_fp32']:+.3f})")
    if args.trajectory_generations > 0:
        traj = codec_trajectory(api, clients,
                                generations=args.trajectory_generations,
                                population=population, seed=args.seed)
        rep["trajectory"] = traj
        print(f"{traj['codec']}+EF vs fp32 over "
              f"{traj['generations']} generations: final err "
              f"{traj['final_codec']:.3f} vs {traj['final_fp32']:.3f} "
              f"(delta {traj['final_delta']:+.3f}) at "
              f"{traj['wire_ratio']:.2f}x fewer wire bytes")
    return rep


def _run_availability_mode(args) -> Dict:
    api = build_api()
    population = 6 if args.population is None else args.population
    gens = 10 if args.generations is None else args.generations
    rep = compare_availability(api, generations=gens, population=population,
                               seed=args.seed,
                               num_clients=args.avail_clients,
                               samples=args.avail_samples,
                               dropouts=tuple(args.dropouts))
    print(f"\navailability ({rep['clients']} clients x {rep['generations']} "
          f"generations, population {rep['population']}, "
          f"{rep['engine_backend']} backend):")
    for part, rows in rep["partitions"].items():
        for rate, r in rows.items():
            print(f"{part:>9} dropout {float(rate):4.2f}: best err "
                  f"{r['best_err']:.3f} | surv {r['mean_survivors']:4.1f} | "
                  f"up {r['up_mb']:7.2f} MB | wasted down "
                  f"{r['wasted_down_mb']:7.2f} MB "
                  f"({100 * r['wasted_frac_of_down']:4.1f}% of down)")
    s = rep["stragglers"]
    print(f"stragglers (1/3 at 10x vs deadline 2.0): best err "
          f"{s['best_err']:.3f} | surv {s['mean_survivors']:4.1f} | "
          f"wasted down {s['wasted_down_mb']:7.2f} MB "
          f"({100 * s['wasted_frac_of_down']:4.1f}% of down)")
    return rep


def _run_scale_mode(args) -> Dict:
    api = build_api()
    population = 10 if args.population is None else args.population
    gens = 4 if args.generations is None else args.generations
    rep = scale_sweep(api, client_counts=tuple(args.scale_clients),
                      sampled=args.scale_sampled, generations=gens,
                      population=population, seed=args.seed)
    print(f"\nscale ({args.scale_sampled} sampled/round x {gens} "
          f"generations, population {rep['population']}, "
          f"{rep['engine_backend']} backend):")
    for k, r in rep["points"].items():
        print(f"{int(k):>9} clients: build {r['build_s']:6.2f}s | steady "
              f"{r['steady_round_s']:6.2f}s/round | peak "
              f"{r['peak_live_bytes'] / 1e6:7.1f} MB live | partition "
              f"{r['partition_host_bytes'] / 1e6:7.1f} MB host | "
              f"{r['clients_materialized']:3d} clients ever built")
    s = rep["summary"]
    print(f"steady-round ratio {s['steady_round_ratio']:.2f}x, peak-bytes "
          f"ratio {s['peak_live_ratio']:.2f}x across "
          f"{s['client_counts'][0]} -> {s['client_counts'][-1]} clients "
          f"(flat within 2x: {s['flat_within_2x']})")
    if args.scale_out:
        os.makedirs(os.path.dirname(args.scale_out) or ".", exist_ok=True)
        with open(args.scale_out, "w") as f:
            json.dump(rep, f, indent=1)
        print(f"wrote {args.scale_out}")
    if args.bench_out:
        # fold the summary into the recorded perf trajectory next to the
        # backend timings (leave their keys untouched)
        bench = {}
        if os.path.exists(args.bench_out):
            with open(args.bench_out) as f:
                bench = json.load(f)
        bench["scale"] = rep["summary"]
        with open(args.bench_out, "w") as f:
            json.dump(bench, f, indent=1)
        print(f"merged scale summary into {args.bench_out}")
    return rep


def _run_obs_mode(args) -> Dict:
    api = build_api()
    clients = build_clients(args.clients, iid=True, n=args.samples,
                            batch=args.batch, test_batch=args.batch,
                            image=args.image)
    population = 10 if args.population is None else args.population
    gens = 25 if args.generations is None else args.generations
    rep = measure_telemetry(api, clients, generations=gens,
                            population=population, seed=args.seed,
                            jsonl_path=args.obs_out or None)
    print(f"\nobs ({rep['clients']} clients x {rep['generations']} "
          f"generations, population {rep['population']}, "
          f"{rep['engine_backend']} backend):")
    print(f"steady gen: {rep['steady_gen_s_off'] * 1e3:7.1f} ms off | "
          f"{rep['steady_gen_s_on'] * 1e3:7.1f} ms on | overhead "
          f"{100 * rep['overhead_frac']:+.2f}% (target <3%: "
          f"{rep['overhead_under_3pct']}) | masters bitwise equal: "
          f"{rep['masters_bitwise_equal']}")
    total = sum(rep["phase_totals"].values()) or 1.0
    for path, s in rep["phase_totals"].items():
        print(f"  {path:<24} {s:8.3f}s ({100 * s / total:5.1f}% of "
              "span time)")
    print(f"trace counts: {rep['trace_counts']} | retrace ok: "
          f"{rep['retrace_ok']}")
    if args.obs_out:
        print(f"wrote {rep['events']} round events to {args.obs_out}")
    if args.bench_out:
        # fold into the recorded perf trajectory next to the backend
        # timings and the scale summary (leave their keys untouched)
        bench = {}
        if os.path.exists(args.bench_out):
            with open(args.bench_out) as f:
                bench = json.load(f)
        bench["obs"] = rep
        with open(args.bench_out, "w") as f:
            json.dump(bench, f, indent=1)
        print(f"merged obs summary into {args.bench_out}")
    if not rep["retrace_ok"]:
        # the CI gate: a fused program that traces more than once (or any
        # program that retraces after round 1) is a silent perf regression
        raise SystemExit(
            f"unexpected fused retraces: trace_counts={rep['trace_counts']} "
            f"late={rep['late_recompiles']}")
    return rep


def main():
    import argparse
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        description="execution-backend, payload-codec and "
                    "client-availability comparisons")
    ap.add_argument("--mode",
                    choices=["backends", "codecs", "availability", "scale",
                             "obs", "both", "all"],
                    default="both")
    ap.add_argument("--generations", type=int, default=None,
                    help="defaults to 25 in backends mode (steady-state "
                         "per-gen times are ~30 ms — shorter runs read "
                         "timer noise) and 3 in codecs mode")
    ap.add_argument("--population", type=int, default=None,
                    help="defaults to 10 in backends mode (the recorded "
                         "perf point) and 6 in codecs mode")
    ap.add_argument("--clients", type=int, default=16,
                    help="backends mode: client count — default is the "
                         "paper-scale dispatch-bound point BENCH_engine"
                         ".json records (the codec mode has its own "
                         "--codec-* sizing)")
    ap.add_argument("--samples", type=int, default=64,
                    help="backends mode: total samples")
    ap.add_argument("--image", type=int, default=8,
                    help="backends mode: image size")
    ap.add_argument("--batch", type=int, default=2,
                    help="backends mode: per-client batch size")
    ap.add_argument("--codec-clients", type=int, default=8,
                    help="codecs mode: client count")
    ap.add_argument("--codec-samples", type=int, default=480,
                    help="codecs mode: total samples")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backends", nargs="+",
                    default=list(BACKEND_VARIANTS),
                    choices=list(BACKEND_VARIANTS))
    ap.add_argument("--bench-out", default="BENCH_engine.json",
                    help="backends mode: write the perf-trajectory JSON "
                         "here (repo root by convention; '' disables)")
    ap.add_argument("--key-batching", type=int, default=1,
                    help="backends mode: re-measure scalar-key scan vs "
                         "batched-key vmap per phase (0 disables)")
    ap.add_argument("--codecs", nargs="+",
                    default=["none", "cast", "int8", "topk"])
    ap.add_argument("--dropouts", nargs="+", type=float,
                    default=[0.0, 0.1, 0.3, 0.5],
                    help="availability mode: post-download dropout rates")
    ap.add_argument("--avail-clients", type=int, default=8,
                    help="availability mode: client count")
    ap.add_argument("--avail-samples", type=int, default=960,
                    help="availability mode: total samples")
    ap.add_argument("--scale-clients", nargs="+", type=int,
                    default=[100, 1000, 10000, 100000, 1000000],
                    help="scale mode: fleet sizes to sweep")
    ap.add_argument("--scale-sampled", type=int, default=16,
                    help="scale mode: participants per round (fixed "
                         "across the sweep)")
    ap.add_argument("--scale-out", default="benchmarks/results/scale.json",
                    help="scale mode: write the full sweep JSON here "
                         "('' disables)")
    ap.add_argument("--obs-out",
                    default="benchmarks/results/obs_rounds.jsonl",
                    help="obs mode: write the telemetry round-event JSONL "
                         "here — one line per generation of the last "
                         "telemetry-on run ('' disables)")
    ap.add_argument("--trajectory-generations", type=int, default=30,
                    help="int8-vs-fp32 trajectory length in codec mode "
                         "(0 disables)")
    ap.add_argument("--out", default=None,
                    help="write the report JSON here "
                         "(e.g. benchmarks/results/codec_compare.json)")
    args = ap.parse_args()

    rep: Dict = {}
    if args.mode in ("backends", "both", "all"):
        rep["backends"] = _run_backend_mode(args)
    if args.mode in ("codecs", "both", "all"):
        rep["codecs"] = _run_codec_mode(args)
    if args.mode in ("availability", "all"):
        rep["availability"] = _run_availability_mode(args)
    if args.mode in ("scale", "all"):
        rep["scale"] = _run_scale_mode(args)
    if args.mode in ("obs", "all"):
        rep["obs"] = _run_obs_mode(args)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
