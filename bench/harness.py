"""One run of one cell: set-up, the measured window, the traced
reading, and the comparison with the plain reference that decides
``correct``.

The cell is data.  ``BENCHMARK.json`` names its configuration and
traffic; ``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<workload>.json`` and ``metrics/<metric>.py`` hold the rest,
and this module finds each by name.  What knows the model sits in the
supernet family's module, ``families/<family>.py``, found by the name
the configuration gives under ``model.family`` (``family``): it makes
the fleet, builds the program's supernet through its public entry
points, counts the MACs of one sample, and holds the plain reference.
This module knows no family: it drives the search through
``FedEngine.run`` with ``RealTimeNas`` on the configuration's backend,
pins the window, reads the trace and compares.
"""
from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

# Generation 1 trains the parents and the offspring (two master updates),
# evaluates, and compiles; generation 2 makes the third update and the
# second evaluation.  Both are set-up: the reference follows the first
# update and scores both evaluations on the masters they were given.
WARM_GENS = 2
COMPARED_FILLS = 3
# generations read from the profiler in a --trace 1 run
TRACE_GENS = 2


class ChipError(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    per_layer: List[Dict]
    root: str = ROOT


def _json(path):
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    """The module at ``path``, loaded once per process under ``name``."""
    mod = sys.modules.get(name)
    if mod is None or mod.__file__ != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return mod


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """Everything one cell is made of, found by name."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; known: {sorted(by_name)}")
    wl = by_name[workload]
    bench = os.path.join(root, "bench")
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])]
    cell = Cell(
        name=workload, chips=int(wl["chips"]),
        config=_json(_config_path(root, wl["config"])),
        traffic=_json(os.path.join(bench, "traffic",
                                   wl["traffic"] + ".json")),
        limits=_json(os.path.join(bench, "limits", workload + ".json")),
        per_layer=per_layer, root=root)
    family(cell)
    return cell


def _config_path(root: str, config: str) -> str:
    return os.path.join(root, "bench", "configs", config + ".json")


def family(cell: Cell):
    """The module of ``families/<family>.py`` under the cell's root, for
    the family its configuration names; a configuration that names none,
    or a family with no module, is an error."""
    name = cell.config["model"].get("family")
    if not name:
        raise ValueError(f"{_config_path(cell.root, cell.config['name'])}: "
                         f"the configuration's model states no family")
    path = os.path.join(cell.root, "bench", "families", name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no module of supernet family {name!r}: "
                                f"{path}")
    return _module(path, f"bench_family_{name}")


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(record)`` of ``metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    return _module(path, f"bench_metric_{name}").read


def check_chips(jax, chips: int):
    """The devices the cell runs on; no TPU or too few chips is an
    error, never a fallback."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise ChipError(f"needs a TPU; JAX found platform {platform!r}")
    if len(devices) < chips:
        raise ChipError(f"the cell asks for {chips} chips; JAX found "
                        f"{len(devices)}")
    return devices[:chips]


def enable_cache(jax):
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program whatever its compile time."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # unbounded: an environment that bounds the cache turns on eviction
    # bookkeeping that fails on entries written without it
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts programs compiled or read from the compilation cache."""

    EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
              "/jax/compilation_cache/cache_hits",
              "/jax/compilation_cache/cache_misses")

    def __init__(self, monitoring):
        self.counts = {e: 0 for e in self.EVENTS}
        self.backend_compiles = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event in self.counts:
            self.counts[event] += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def total(self) -> int:
        return (self.counts[self.EVENTS[0]] + self.backend_compiles)


class Recorder:
    """The outermost backend layer: passes every call through unchanged
    and notes the search's decisions (keys, client groups, evaluated
    clients, error rates), plus host copies of the master before the
    first update and after each of the first ``capture`` updates."""

    def __init__(self, inner, capture: int):
        self.inner = inner
        self.capture = capture
        self.calls: List[Dict] = []
        self.masters: List = []
        self._fills = 0
        self._evals = 0

    @property
    def name(self):
        return self.inner.name

    @property
    def dispatches(self):
        return self.inner.dispatches

    @dispatches.setter
    def dispatches(self, value):
        self.inner.dispatches = value

    def reset(self):
        reset = getattr(self.inner, "reset", None)
        if reset is not None:
            reset()

    def train_fill(self, master, keys, groups, lr, survivors=None):
        capture = self._fills < self.capture
        if capture and self._fills == 0:
            self.masters.append(_host(master))
        out = self.inner.train_fill(master, keys, groups, lr,
                                    survivors=survivors)
        if capture:
            self.masters.append(_host(out))
        self._fills += 1
        self.calls.append({"kind": "fill", "gen": self._evals + 1,
                           "keys": [np.asarray(k) for k in keys],
                           "groups": [np.asarray(g) for g in groups]})
        return out

    def eval_shared(self, params, keys, client_ids, survivors=None):
        errs = self.inner.eval_shared(params, keys, client_ids,
                                      survivors=survivors)
        self._evals += 1
        self.calls.append({"kind": "eval",
                           "keys": [np.asarray(k) for k in keys],
                           "clients": np.asarray(client_ids),
                           "errs": np.asarray(errs, np.float64)})
        return errs


def _host(tree):
    import jax
    return jax.tree.map(lambda x: np.asarray(x, np.float32),
                        jax.device_get(tree))


class WindowClosed(Exception):
    """Raised from the engine's round callback to end the run."""


def set_precision(jax, cell: Cell):
    """The configuration's matmul precision for every program the process
    compiles from here on (JAX's ``jax_default_matmul_precision``)."""
    jax.config.update("jax_default_matmul_precision",
                      cell.config["model"]["matmul_precision"])


def build_engine(cell: Cell, seed: int, clients, trace: bool):
    """The search the window times, wrapped in the ``Recorder``."""
    from repro.engine import FedEngine, RunConfig
    from repro.obs import TelemetryConfig
    cfg, tr = cell.config, cell.traffic
    search, ex = cfg["search"], cfg["execution"]
    api = family(cell).build_api(cfg["model"])
    if round(tr["participation"] * tr["clients"]) % search["population"]:
        raise ValueError("participants per generation must be a multiple "
                         "of the population")
    run_cfg = RunConfig(
        population=search["population"], generations=10 ** 9,
        participation=tr["participation"], lr0=search["lr0"],
        lr_decay=search["lr_decay"], momentum=search["momentum"],
        local_epochs=search["local_epochs"],
        crossover=search["crossover"], mutation=search["mutation"],
        seed=seed, backend=ex["backend"], fused=ex["fused"],
        aggregate_backend=ex["aggregate_backend"],
        uplink_codec=ex["uplink_codec"],
        downlink_codec=ex["downlink_codec"],
        telemetry=(TelemetryConfig(sink="memory", gauges=False, ring=64)
                   if trace else None))
    eng = FedEngine(api, clients, run_cfg)
    rec = Recorder(eng.backend, COMPARED_FILLS)
    eng.backend = rec
    return eng, rec, api


def window_population(cell: Cell) -> List[np.ndarray]:
    """The parents every window generation breeds from: ``population``
    keys drawn uniformly from the traffic file's fixed seed, the same in
    every run."""
    rng = np.random.default_rng(cell.traffic["window"]["population_seed"])
    model = cell.config["model"]
    return [rng.integers(0, len(model["branches"]), model["choice_blocks"]
                         ).astype(np.int32)
            for _ in range(cell.config["search"]["population"])]


def window_flops(cell: Cell, calls: List[Dict], clients) -> float:
    """Model FLOPs of the recorded calls (``yardstick.model_flops``)."""
    from bench.yardstick import model_flops
    train, evals = [], []
    for c in calls:
        if c["kind"] == "fill":
            for k, g in zip(c["keys"], c["groups"]):
                for cid in g:
                    xb = clients[int(cid)].train[0]
                    train.append((k, xb.shape[0] * xb.shape[1]))
        else:
            n = sum(clients[int(cid)].test[0].shape[0]
                    * clients[int(cid)].test[0].shape[1]
                    for cid in c["clients"])
            evals.extend((k, n) for k in c["keys"])
    model, sample_macs = cell.config["model"], family(cell).sample_macs
    return model_flops(train, evals, lambda k: sample_macs(k, model),
                       cell.config["search"]["local_epochs"])


def mean_eval_macs(cell: Cell, calls: List[Dict]) -> Optional[float]:
    keys = [k for c in calls if c["kind"] == "eval" for k in c["keys"]]
    if not keys:
        return None
    model, sample_macs = cell.config["model"], family(cell).sample_macs
    return float(np.mean([sample_macs(k, model) for k in keys]))


def reference_steps(calls: List[Dict]) -> List[Dict]:
    """The calls the reference follows: every call up to the evaluation
    that follows the ``COMPARED_FILLS``-th master update."""
    out, fills = [], 0
    for c in calls:
        out.append(c)
        fills += c["kind"] == "fill"
        if fills == COMPARED_FILLS and c["kind"] == "eval":
            break
    return out


def first_update(steps: List[Dict]) -> List[Dict]:
    """The one master update the reference trains: the search's first."""
    return [next(c for c in steps if c["kind"] == "fill")]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices=None, log=print) -> Dict:
    """One run of ``cell``.  ``devices`` are the chips it runs on (the
    caller checks them).  Returns the result line's object."""
    import jax
    from jax import monitoring
    from bench import correctness
    from bench.yardstick import peaks

    devices = devices or jax.devices()[:cell.chips]
    counter = CompileCounter(monitoring)
    fam = family(cell)
    clients = fam.build_fleet(seed, cell.traffic, cell.config["search"],
                              cell.config["model"])
    eng, rec, api = build_engine(cell, seed, clients, trace)

    state = {"t_w0": None, "t_end": None, "gens": 0, "mark": 0,
             "compiles0": 0, "reports": []}
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    pinned = window_population(cell)

    def callback(gen, report):
        now = time.perf_counter()
        if gen >= WARM_GENS:
            # the window's work is fixed: every generation breeds from
            # the same parents, with the same search stream
            eng.strategy.parents = [k.copy() for k in pinned]
        if gen == WARM_GENS:
            eng.rng = np.random.default_rng(
                cell.traffic["window"]["search_seed"])
            state["t_w0"] = state["t_end"] = now
            state["mark"] = len(rec.calls)
            state["compiles0"] = counter.total()
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(tdir, profiler_options=opts)
                state["t_w0"] = state["t_end"] = time.perf_counter()
            return
        if gen < WARM_GENS:
            return
        state["gens"] += 1
        state["t_end"] = now
        state["reports"].append(report)
        if now - state["t_w0"] >= seconds or (trace and
                                              state["gens"] >= TRACE_GENS):
            raise WindowClosed

    try:
        eng.run(callback=callback)
    except WindowClosed:
        pass
    if trace:
        jax.profiler.stop_trace()
    window_s = state["t_end"] - state["t_w0"]
    gens = state["gens"]
    setup_s = state["t_w0"] - t_start
    window_calls = rec.calls[state["mark"]:]
    window_compiles = counter.total() - state["compiles0"]

    failed = sum(1 for r in state["reports"]
                 if not (np.all(np.isfinite(r.objs))
                         and np.all((r.objs[:, 0] >= 0)
                                    & (r.objs[:, 0] <= 1))))
    master = eng.strategy.master
    finite = all(bool(jax.numpy.all(jax.numpy.isfinite(x)))
                 for x in jax.tree.leaves(master))
    flops = window_flops(cell, window_calls, clients)
    log(f"window: {gens} generations in {window_s!r} s; mean MACs per "
        f"evaluated key {mean_eval_macs(cell, window_calls)!r}; model "
        f"TFLOP {flops / 1e12!r}; programs compiled or loaded in the "
        f"window {window_compiles}")
    stats = devices[0].memory_stats() or {}
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                      0))
                     for d in devices)
    log(f"memory: peak_bytes_in_use {peak_bytes} "
        f"(bytes_limit {stats.get('bytes_limit')})")
    events = (list(eng.telemetry.ring.events) if trace else [])
    spans = [e.spans for e in events if e.gen > WARM_GENS]

    # free the program's state before the reference runs
    steps = reference_steps(rec.calls)
    prog_masters = list(rec.masters)
    del eng, master, rec
    shards = {}
    for st in steps:
        ids = ([c for g in st["groups"] for c in g] if st["kind"] == "fill"
               else st["clients"])
        for cid in ids:
            c = clients[int(cid)]
            shards[int(cid)] = (c.train[0], c.train[1], c.test[0], c.test[1])
    t_ref = time.perf_counter()
    ref = fam.Reference(cell.config)
    ref_masters = ref.run(seed, first_update(steps), shards)
    ref_errs = ref.errors(steps, shards, prog_masters)
    prog_errs = [st["errs"] for st in steps if st["kind"] == "eval"]
    numbers = correctness.compare(prog_masters, prog_errs, ref_masters,
                                  ref_errs)
    log(f"reference: {time.perf_counter() - t_ref!r} s; readings "
        f"{numbers!r}")
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in numbers.items() if k in cell.limits}
    checks["nonfinite_master"] = {"value": 0 if finite else 1, "limit": 0}
    correct = (failed == 0 and all(c["value"] <= c["limit"]
                                   for c in checks.values()))

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct), "attempted": gens, "failed": failed}
    if trace:
        paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        from bench import devtrace
        red = devtrace.reduce(paths[0]) if paths else None
        shutil.rmtree(tdir, ignore_errors=True)
        record = {"gens": gens, "window_s": window_s, "spans": spans,
                  "trace": red, "flops": flops, "chips": len(devices),
                  "peaks": peaks(kind), "config": cell.config,
                  "calls": window_calls}
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"], cell.root)(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = red["busy_s"] if red else 0.0
        device["window_s"] = red["window_s"] if red else window_s
        out["device"] = device
        if red:
            out["breakdown"] = {"device_ops": red["top_ops"],
                                "idle_gaps": red["idle_gaps"]}
    else:
        out["metrics"] = {
            "gen_s": {"value": window_s / gens, "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        out["device"] = device
    out["checks"] = checks
    return out
