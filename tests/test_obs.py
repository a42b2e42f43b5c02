"""repro.obs: telemetry is bit-exactly invisible when off, faithful
when on.

The contract under test, per backend x fused variant: enabling
``RunConfig.telemetry`` changes *nothing* about the search — masters
and per-generation objectives bitwise identical, CommStats equal field
for field, dispatch counts equal — while the enabled run emits one
complete ``RoundEvent`` per generation (phase spans with correct
nesting, recompile deltas, resource gauges, CommStats deltas).  Plus
the recompile counter honesty tests (traces counted, cached dispatches
not; the fused programs trace exactly once), the sink implementations,
and the shared gauge helpers the benchmark driver reuses.
"""
import dataclasses
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import make_api
from repro.data import make_classification, make_clients, make_fleet, \
    partition_iid
from repro.engine import ClientSimConfig, FedEngine, RunConfig
from repro.obs import (COMM_FIELDS, NULL_TELEMETRY, InstrumentedBackend,
                       PeakLiveBytes, RoundEvent, TableSink, Telemetry,
                       TelemetryConfig, event_dict, host_rss_bytes,
                       innermost, live_device_bytes, parse_sink_spec,
                       steady_mean, traced)

VARIANTS = (("loop", True), ("vmap", True), ("vmap", False),
            ("mesh", True), ("mesh", False))
GENS = 3


def tiny_clients(num_clients=6, n=240, seed=0):
    x, y = make_classification(seed, n, image=8, signal=1.5, noise=0.5)
    return make_clients(x, y, partition_iid(seed, n, num_clients),
                        batch=10, test_batch=10)


@pytest.fixture(scope="module")
def api():
    return make_api(get_config("cifar-supernet", smoke=True))


def max_leaf_diff(a, b):
    return max(float(jnp.abs(jnp.asarray(x) - jnp.asarray(y)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def run_engine(api, clients, backend, fused, telemetry, **kw):
    eng = FedEngine(api, clients,
                    RunConfig(population=4, generations=GENS, seed=0,
                              lr0=0.01, backend=backend, fused=fused,
                              telemetry=telemetry, **kw))
    return eng, eng.run()


@pytest.fixture(scope="module")
def onoff(api):
    clients = tiny_clients()
    return {(bk, fused): {t: run_engine(api, clients, bk, fused,
                                        True if t == "on" else None)
                          for t in ("off", "on")}
            for bk, fused in VARIANTS}


# ---------------------------------------------------------------------------
# bit-exact invisibility: on == off, per backend x fused variant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bk,fused", VARIANTS)
def test_telemetry_on_off_bitwise(onoff, bk, fused):
    (eng_off, off), (eng_on, on) = (onoff[(bk, fused)]["off"],
                                    onoff[(bk, fused)]["on"])
    assert max_leaf_diff(off.extras["final_master"],
                         on.extras["final_master"]) == 0.0
    for a, b in zip(off.reports, on.reports):
        assert np.array_equal(np.asarray(a.objs), np.asarray(b.objs))
        assert a.best_err == b.best_err
    assert dataclasses.asdict(off.stats) == dataclasses.asdict(on.stats)
    assert eng_off.backend.dispatches == eng_on.backend.dispatches


@pytest.mark.parametrize("bk,fused", VARIANTS)
def test_telemetry_result_presence(onoff, bk, fused):
    off = onoff[(bk, fused)]["off"][1]
    on = onoff[(bk, fused)]["on"][1]
    assert off.telemetry is None
    assert on.telemetry is not None
    assert [e.gen for e in on.telemetry.events] == list(range(1, GENS + 1))


def test_disabled_engine_is_pre_subsystem_graph(api):
    clients = tiny_clients(4, 120)
    rc = dict(population=4, generations=1, seed=0, backend="vmap")
    eng_off = FedEngine(api, clients, RunConfig(**rc))
    # no wrapper at all, and every telemetry hook is the shared no-op
    assert innermost(eng_off.backend) is eng_off.backend
    assert eng_off.telemetry is NULL_TELEMETRY
    assert eng_off.backend.telemetry is NULL_TELEMETRY
    eng_on = FedEngine(api, clients, RunConfig(telemetry=True, **rc))
    assert isinstance(eng_on.backend, InstrumentedBackend)
    assert innermost(eng_on.backend).telemetry is eng_on.telemetry


# ---------------------------------------------------------------------------
# round-event completeness (vmap fused + availability sim + int8 codec)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def full_run(api):
    return run_engine(api, tiny_clients(), "vmap", True, True,
                      uplink_codec="int8", downlink_codec="int8",
                      client_sim=ClientSimConfig(dropout=0.25, seed=1))


def test_round_event_spans_complete(full_run):
    _, res = full_run
    ev = res.telemetry.events[0]
    paths = set(ev.spans)
    for phase in ("sample", "availability", "fill_train", "eval",
                  "aggregate"):
        assert phase in paths, f"missing top-level span {phase!r}"
    # codec + staging spans nest under the backend call that caused them
    assert "fill_train/codec_decode" in paths
    assert "fill_train/codec_encode" in paths
    assert "eval/codec_decode" in paths
    assert any(p.endswith("/download") for p in paths)
    assert all(s >= 0.0 for s in ev.spans.values())
    assert ev.span_counts["eval"] >= 1
    assert set(ev.span_counts) == paths


def test_round_event_comm_deltas_sum_to_stats(full_run):
    _, res = full_run
    events = res.telemetry.events
    stats = dataclasses.asdict(res.stats)
    for f in COMM_FIELDS:
        per_round = [e.comm[f] for e in events]
        assert sum(per_round) == pytest.approx(stats[f])
    assert events[0].comm["down_bytes"] > 0
    assert events[0].comm["up_bytes"] > 0


def test_round_event_gauges(full_run):
    _, res = full_run
    g = res.telemetry.events[-1].gauges
    assert g["live_device_bytes"] > 0
    assert g["peak_live_device_bytes"] >= g["live_device_bytes"]
    assert g["host_rss_bytes"] > 0
    # stacked-store LRU counters (vmap backend): the steady state reuses
    # the staged shards, so by the last round there have been hits
    assert g["train_store_misses"] >= 1
    assert g["test_stack_misses"] >= 1
    assert g["train_store_hits"] + g["test_stack_hits"] >= 1


def test_round_event_times_match_reports(full_run):
    _, res = full_run
    for e, r in zip(res.telemetry.events, res.reports):
        assert e.round_s == r.round_s
        assert e.round_s >= 0.0
        # top-level phases are disjoint intervals inside the round
        # (``init``, the run's set-up, is booked into round 1 but runs
        # before it)
        top = sum(s for p, s in e.spans.items()
                  if "/" not in p and p != "init")
        assert top <= e.round_s + 1e-3


def test_round_event_new_spans_and_compiles(full_run):
    """Leaf spans for staging, launch, accounting and the objectives;
    ``init`` and the programs' compiles in generation 1 only (a long
    session may re-trace a small eager helper later, so steady rounds
    are held to no compile under ``launch``)."""
    _, res = full_run
    first, *steady = res.telemetry.events
    for ev in (first, *steady):
        paths = set(ev.spans)
        assert {"accounting", "objectives", "fill_train/stage",
                "fill_train/launch", "eval/stage", "eval/launch"} <= paths
    assert "fill_train/stage/download" in first.spans
    assert "init" in first.spans
    assert "fill_train/launch/compile" in first.spans
    assert "eval/launch/compile" in first.spans
    for ev in steady:
        assert "init" not in ev.spans
        assert not [p for p in ev.spans if p.endswith("launch/compile")], \
            ev.spans
        assert ev.recompiles == {}


@pytest.mark.parametrize("bk", ["vmap", "mesh"])
def test_launch_spans_count_fused_dispatches(onoff, bk):
    eng, res = onoff[(bk, True)]["on"]
    launches = sum(n for e in res.telemetry.events
                   for p, n in e.span_counts.items()
                   if p.split("/")[-1] == "launch")
    assert launches == eng.backend.dispatches == 2 * GENS + 1


def test_staged_bytes_counts_what_reaches_the_device(api):
    from repro.engine.backends import VmapBackend
    clients = tiny_clients(4, 120)
    be = VmapBackend(api, clients, RunConfig(population=2, backend="vmap"))
    tel = Telemetry(TelemetryConfig(gauges=False))
    be.telemetry = tel

    class Eng:
        backend = be
        stats = object()

    tel.start_run(Eng)
    store = be._train_store([0, 2])
    tests = be._test_batches([1, 3])
    be._train_store([2, 0])              # cache hits stage nothing
    want = (sum(x.nbytes + y.nbytes for _, x, y in store)
            + sum(cb.xb.nbytes + cb.yb.nbytes for cb in tests))
    assert want > 0
    assert tel.end_round(1, 0.0, Eng).counters == {"staged_bytes": want}
    be._test_batches([1, 3])
    assert tel.end_round(2, 0.0, Eng).counters == {}
    off = VmapBackend(api, clients, RunConfig(population=2, backend="vmap"))
    off._train_store([0, 2])             # NULL_TELEMETRY: nothing to book


def test_compile_spans_book_the_union_of_nested_stages():
    tel = Telemetry(TelemetryConfig(gauges=False))

    class Eng:
        class backend:
            trace_counts = {}
        stats = object()

    trace, lower, build = ("/jax/core/compile/" + e for e in (
        "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
        "backend_compile_duration"))
    tel.start_run(Eng)
    with tel.span("fill_train"):
        with tel.span("launch"):
            tel._on_compile(trace, 10.0, 10.5)      # a helper's trace ...
            tel._on_compile(trace, 10.25, 11.5)     # ... overlapping one
            tel._on_compile(trace, 9.0, 12.0)       # ... inside the program's
            tel._on_compile(lower, 12.0, 13.0)
            tel._on_cache_load(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.75)
            tel._on_compile(build, 13.0, 14.0)
            tel._on_compile("/jax/other_duration", 0.0, 100.0)
    ev = tel.end_round(1, 0.0, Eng)
    assert ev.spans["fill_train/launch/compile"] == pytest.approx(5.0)
    assert ev.spans["fill_train/launch/compile/cache_load"] == 0.75


def test_compile_listeners_live_only_in_telemetry_runs(api):
    from jax._src import monitoring

    def listeners():
        return (len(monitoring.get_event_time_span_listeners()),
                len(monitoring.get_event_duration_listeners()))

    before = listeners()
    seen = {}
    clients = tiny_clients(4, 120)
    for tel in (None, True):
        eng = FedEngine(api, clients, RunConfig(
            population=2, generations=1, seed=0, backend="loop",
            telemetry=tel))
        eng.run(callback=lambda gen, rep, t=tel: seen.setdefault(
            t, listeners()))
        assert listeners() == before
    assert seen[None] == before
    assert seen[True] == (before[0] + 1, before[1] + 1)


def test_profile_gens_validation():
    assert TelemetryConfig(profiler_dir="d",
                           profile_gens=[2, 1]).profile_gens == (2, 1)
    with pytest.raises(ValueError, match="go together"):
        TelemetryConfig(profiler_dir="d")
    with pytest.raises(ValueError, match="go together"):
        TelemetryConfig(profile_gens=(1, 1))
    for bad in ((0, 1), (1, 0)):
        with pytest.raises(ValueError, match="profile_gens"):
            TelemetryConfig(profiler_dir="d", profile_gens=bad)


def test_fleet_gauges(api):
    x, y = make_classification(0, 120, image=8, signal=1.5, noise=0.5)
    fleet = make_fleet(x, y, partition_iid(0, 120, 4), batch=10,
                       test_batch=10, cache_size=8)
    _, res = run_engine(api, fleet, "vmap", True, True)
    g = res.telemetry.events[-1].gauges
    assert g["clients_materialized"] == fleet.materialized >= 4
    assert g["clients_cached"] == fleet.cached
    assert g["fleet_hits"] == fleet.hits >= 1


# ---------------------------------------------------------------------------
# recompile counters: traces counted, dispatches not; fused = once
# ---------------------------------------------------------------------------

def test_traced_counts_traces_not_dispatches():
    counts = {}
    f = jax.jit(traced("prog", counts, lambda x: x * 2.0))
    np.testing.assert_allclose(f(jnp.ones(3)), 2.0 * np.ones(3))
    f(jnp.ones(3))                      # cached dispatch: no new trace
    assert counts["prog"] == 1
    f(jnp.ones(4))                      # shape change forces a retrace
    assert counts["prog"] == 2


@pytest.mark.parametrize("bk", ["vmap", "mesh"])
def test_fused_programs_trace_once(onoff, bk):
    res = onoff[(bk, True)]["on"][1]
    tc = res.telemetry.trace_counts
    assert tc.get("fused_fill") == 1
    assert tc.get("fused_eval_shared") == 1
    assert all(v == 1 for v in tc.values()), tc
    events = res.telemetry.events
    assert events[0].recompiles.get("fused_fill") == 1
    for e in events[1:]:                # steady state: no retraces
        assert e.recompiles == {}


def test_injected_retrace_surfaces_in_round_events():
    class FakeBackend:
        def __init__(self):
            self.trace_counts = {}

    class FakeEngine:
        def __init__(self):
            self.backend = FakeBackend()
            self.stats = object()       # comm deltas read 0.0 defaults

    eng = FakeEngine()
    tel = Telemetry(TelemetryConfig(gauges=False))
    f = jax.jit(traced("prog", eng.backend.trace_counts, lambda x: x + 1))
    tel.start_run(eng)
    f(jnp.ones(3))
    assert tel.end_round(1, 0.0, eng).recompiles == {"prog": 1}
    f(jnp.ones(3))                      # cached: clean steady round
    assert tel.end_round(2, 0.0, eng).recompiles == {}
    f(jnp.ones(5))                      # injected shape-varying retrace
    assert tel.end_round(3, 0.0, eng).recompiles == {"prog": 1}


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

def test_jsonl_sink_one_line_per_round(api, tmp_path):
    path = tmp_path / "rounds.jsonl"
    _, res = run_engine(api, tiny_clients(4, 120), "vmap", True,
                        {"sink": f"jsonl:{path}"})
    events = [json.loads(line) for line in path.read_text().splitlines()]
    assert [e["gen"] for e in events] == list(range(1, GENS + 1))
    for e in events:
        assert set(e) == {"gen", "round_s", "spans", "span_counts",
                          "recompiles", "gauges", "comm", "counters"}
    # the file mirrors the in-memory ring, event for event
    assert events[-1] == event_dict(res.telemetry.events[-1])


def test_memory_ring_capacity(api):
    _, res = run_engine(api, tiny_clients(4, 120), "vmap", True,
                        {"ring": 2})
    assert [e.gen for e in res.telemetry.events] == [GENS - 1, GENS]


def test_table_sink_rows():
    buf = io.StringIO()
    sink = TableSink(stream=buf)
    ev = RoundEvent(gen=1, round_s=0.5,
                    spans={"fill_train": 0.3, "fill_train/download": 0.1,
                           "eval": 0.05},
                    span_counts={"fill_train": 2},
                    recompiles={"fused_fill": 1},
                    gauges={"live_device_bytes": 2e6},
                    comm={"up_bytes": 1e6})
    sink.emit(ev)
    sink.emit(ev)
    lines = buf.getvalue().splitlines()
    assert len(lines) == 4              # header + rule + two rows
    assert lines[0].split()[0] == "gen"
    assert "0.400" in lines[2]          # fill_train + nested download


def test_sink_spec_validation():
    assert parse_sink_spec("memory") == ("memory", "")
    assert parse_sink_spec("table") == ("table", "")
    assert parse_sink_spec("jsonl:/tmp/x.jsonl") == ("jsonl", "/tmp/x.jsonl")
    with pytest.raises(ValueError):
        TelemetryConfig(sink="carrier_pigeon")
    with pytest.raises(ValueError):
        TelemetryConfig(sink="jsonl:")
    with pytest.raises(ValueError):
        TelemetryConfig(ring=0)
    with pytest.raises(ValueError):     # RunConfig coercion validates too
        RunConfig(telemetry={"sink": "nope"})


# ---------------------------------------------------------------------------
# gauge helpers shared with benchmarks/fed_nas.py
# ---------------------------------------------------------------------------

def test_steady_mean():
    assert steady_mean([]) is None
    assert steady_mean([2.5]) == 2.5
    assert steady_mean([10.0, 1.0, 3.0]) == 2.0


def test_peak_live_bytes_tracks_growth():
    pk = PeakLiveBytes()
    assert pk.peak == pk.baseline
    x = jnp.zeros((256, 256), jnp.float32)
    jax.block_until_ready(x)
    pk.sample("gen", "report")          # engine-callback signature
    assert pk.growth == pk.peak - pk.baseline >= 0
    assert pk.peak >= pk.baseline
    del x


def test_host_gauges_positive():
    assert live_device_bytes() >= 0
    assert host_rss_bytes() > 0


def test_null_telemetry_noop():
    assert not NULL_TELEMETRY.enabled
    with NULL_TELEMETRY.span("anything"):
        pass
    NULL_TELEMETRY.start_run(None)
    NULL_TELEMETRY.end_round(1, 0.0, None)
    with NULL_TELEMETRY.run_scope():
        with NULL_TELEMETRY.generation(1):
            pass
    NULL_TELEMETRY.count("staged_bytes", 1.0)
    assert NULL_TELEMETRY.result(None) is None
