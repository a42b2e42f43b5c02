"""The host plane read further: generation markers, every program span,
the runtime's copies, and device idle time split by cause; the readers
of the metrics that rest on them; and ``devtrace``'s readings pinned on
its own fixture, so that reading more of the host changes none of
them."""
import glob
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import devtrace, harness, hosttrace  # noqa: E402

MS = 1e6     # nanoseconds


def _trace_fixture():
    """``test_bench_trace._generation``: one generation with the fill
    program, a kernel program and the fitness program."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "test_bench_trace.py")
    spec = importlib.util.spec_from_file_location("bench_trace_fixture",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod._generation()


def test_reduce_events_keys_pinned():
    dev, spans = _trace_fixture()
    red = devtrace.reduce_events([dev], spans)
    assert set(red) == {"eval_s", "fill_s", "alg3_s", "codec_s", "busy_s",
                        "window_s", "top_ops", "idle_gaps"}
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.086)
    assert (red["eval_s"], red["fill_s"], red["alg3_s"], red["codec_s"]) \
        == pytest.approx((0.025, 0.061, 0.002, 0.001))
    assert red["top_ops"] == [
        ["fused_uploads/%conv.2 = f32[8]", pytest.approx(0.037)],
        ["fused_eval_shared/%while.6 = (s32[])", pytest.approx(0.025)],
        ["fused_uploads/%conv.3 = f32[8]", pytest.approx(0.018)],
        ["_fill_aggregate_jit/%custom-call.4 = f32[8]",
         pytest.approx(0.002)],
        ["_roundtrip_pallas/%custom-call.5 = s8[8]", pytest.approx(0.001)]]
    assert red["idle_gaps"] == [
        ["host_fetch", pytest.approx(0.005)],
        ["host_fetch", pytest.approx(0.005)],
        ["sample", pytest.approx(0.002)],
        ["fill_train", pytest.approx(0.002)]]


def _with_new_spans():
    """The fixture's generation with the spans the program now also
    emits, and two runtime copies."""
    dev, spans = _trace_fixture()
    spans = spans + [("stage", 1 * MS, 2.5 * MS),
                     ("launch", 2.5 * MS, 3 * MS),
                     ("accounting", 66 * MS, 66.5 * MS),
                     ("objectives", 98.5 * MS, 99 * MS)]
    copies = [("XlaLinearize", 1.2 * MS, 1.8 * MS),
              ("tpu::System::TransferToDevice", 60.5 * MS, 61 * MS)]
    return dev, spans, copies


def test_idle_by_cause_sums_to_idle_time():
    dev, spans, copies = _with_new_spans()
    red = devtrace.reduce_events([dev], spans)
    causes = hosttrace.idle_by_cause([dev], spans, copies)
    assert sum(causes.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-12)
    assert causes["staging"] == pytest.approx(0.0011)
    # idle 0-2 ms: sample, then stage/download without the copy
    assert causes["sample"] == pytest.approx(0.001)
    assert causes["fill_train/stage/download"] == pytest.approx(0.0004)
    assert causes["fill_train"] == pytest.approx(0.0025)
    assert causes["eval/accounting"] == pytest.approx(0.0005)
    assert causes["eval/host_fetch"] == pytest.approx(0.006)
    assert causes["eval/objectives"] == pytest.approx(0.0005)
    assert "between spans" not in causes


def test_idle_by_cause_between_spans_and_devices():
    dev, spans, _ = _with_new_spans()
    spans = [s for s in spans if s[0] != "accounting"]
    causes = hosttrace.idle_by_cause([dev], spans, [])
    assert "staging" not in causes        # no copy events in the trace
    assert "between spans" not in causes  # the spans tile the window
    gap = [("sample", 0, 1 * MS), ("aggregate", 3 * MS, 4 * MS)]
    two = hosttrace.idle_by_cause(
        [{"modules": [("jit_a(1)", 1 * MS, 2 * MS)]}, {"modules": []}],
        gap, [])
    assert two == pytest.approx({"sample": 0.001, "between spans": 0.0015,
                                 "aggregate": 0.001})
    assert hosttrace.idle_by_cause([], gap, []) == {}
    assert hosttrace.idle_by_cause([dev], [("sample", 0, 1)], []) == {}


def test_span_path_nests():
    spans = [("fill_train", 0, 10), ("stage", 1, 5), ("download", 2, 3)]
    assert hosttrace.span_path(spans, 2.5) == "fill_train/stage/download"
    assert hosttrace.span_path(spans, 7) == "fill_train"
    assert hosttrace.span_path(spans, 11) == "between spans"


# -- the metric readers ------------------------------------------------------

def test_launch_ms_reader():
    read = harness.metric_reader("launch_ms")
    spans = [{"fill_train/launch": 0.004, "eval/launch": 0.002,
              "fill_train": 0.05},
             {"fill_train/launch": 0.006}]
    assert read({"spans": spans}) == pytest.approx(6.0)
    assert read({"spans": [{"fill_train": 0.05}]}) is None
    assert read({"spans": []}) is None


def test_stage_wait_ms_reader():
    read = harness.metric_reader("stage_wait_ms")
    rec = {"gens": 2, "trace": {"idle_by_cause": {"staging": 0.09,
                                                  "aggregate": 0.014}}}
    assert read(rec) == pytest.approx(45.0)
    assert read({"gens": 2, "trace": {"idle_by_cause": {"aggregate": 1}}}) \
        is None
    assert read({"gens": 2, "trace": {"busy_s": 1.0}}) is None
    assert read({"gens": 2, "trace": None}) is None


def test_compile_s_reader():
    read = harness.metric_reader("compile_s")
    setup = [{"init/compile": 1.5, "fill_train/launch/compile": 100.0,
              "fill_train/launch/compile/cache_load": 90.0,
              "fill_train": 102.0},
             {"eval/stage/compile": 0.5}]
    assert read({"setup_spans": setup}) == pytest.approx(102.0)
    assert read({"setup_spans": [{"fill_train": 1.0}]}) is None
    assert read({"spans": []}) is None


# -- a capture of a small engine run, read back ------------------------------

@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    from repro.configs import get_config
    from repro.core import make_api
    from repro.data import make_classification, make_clients, partition_iid
    from repro.engine import FedEngine, RunConfig, TelemetryConfig
    pdir = str(tmp_path_factory.mktemp("capture"))
    api = make_api(get_config("cifar-supernet", smoke=True))
    x, y = make_classification(0, 240, image=8, signal=1.5, noise=0.5)
    clients = make_clients(x, y, partition_iid(0, 240, 6), batch=10,
                           test_batch=10)
    eng = FedEngine(api, clients, RunConfig(
        population=2, generations=4, seed=0, lr0=0.01, backend="vmap",
        participation=0.67,
        telemetry=TelemetryConfig(gauges=False, profiler_dir=pdir,
                                  profile_gens=(2, 2))))
    res = eng.run()
    paths = glob.glob(os.path.join(pdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    return res, hosttrace.load_host(paths[0])


def test_capture_holds_the_chosen_generations(captured):
    _, host = captured
    assert sorted(s[0] for s in host["steps"]) == [2, 3]
    (_, s2, e2), (_, s3, e3) = sorted(host["steps"])
    assert e2 <= s3
    # every program span of the capture lies inside a generation
    assert all(any(s0 <= s and e <= e0 for _, s0, e0 in host["steps"])
               for _, s, e in host["spans"])
    names = {n for n, _, _ in host["spans"]}
    assert {"sample", "fill_train", "stage", "download", "launch",
            "accounting", "eval", "host_fetch", "objectives",
            "aggregate"} <= names


def test_capture_host_steps_covered_by_spans(captured):
    """Against a device that never runs, every idle nanosecond of the
    window is a host step; nearly all of it falls in a named span."""
    _, host = captured
    causes = hosttrace.idle_by_cause([{"modules": []}], host["spans"], [])
    total = sum(causes.values())
    assert total > 0
    assert causes.get("between spans", 0.0) < 0.1 * total
    assert any(k.startswith("fill_train/stage") for k in causes)
