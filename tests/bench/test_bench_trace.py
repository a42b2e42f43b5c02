"""The trace reduction: busy union, attribution of device time to the
fitness program, Algorithm 3 and the codec, the top operations, and idle
gaps named by the host span they fell in."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import devtrace  # noqa: E402

MS = 1e6     # nanoseconds


def test_union_clip_and_leaves():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                               (5, 8)]
    assert devtrace.clip([(0, 4), (6, 9), (10, 12)], 2, 10) == [(2, 4),
                                                               (6, 9)]
    ops = [("%while.1 = f32[]", 0, 10), ("%fusion.2 = f32[4]", 1, 3),
           ("%fusion.3 = f32[4]", 4, 9), ("%copy.4 = f32[4]", 12, 13)]
    assert [o[0] for o in devtrace.leaves(ops)] == [
        "%fusion.2 = f32[4]", "%fusion.3 = f32[4]", "%copy.4 = f32[4]"]


def test_names():
    assert devtrace.program_name("jit_fused_fill(13367507500963659104)") \
        == "fused_fill"
    assert devtrace.op_name(
        "%fusion.59 = bf16[10,50]{1,0:T(8,128)} fusion(f32[10,50]{1,0} "
        "%a)") == "%fusion.59 = bf16[10,50]"


def _generation():
    """One generation: the fill program, a kernel program, the fitness
    program, with host spans around them."""
    modules = [("jit_fused_uploads(1)", 2 * MS, 60 * MS),
               ("jit__fill_aggregate_jit(2)", 62 * MS, 64 * MS),
               ("jit__roundtrip_pallas(3)", 64 * MS, 65 * MS),
               ("jit_fused_eval_shared(4)", 70 * MS, 95 * MS)]
    ops = [("%while.1 = (f32[])", 2 * MS, 60 * MS),
           ("%conv.2 = f32[8]", 3 * MS, 40 * MS),
           ("%conv.3 = f32[8]", 41 * MS, 59 * MS),
           ("%custom-call.4 = f32[8]", 62 * MS, 64 * MS),
           ("%custom-call.5 = s8[8]", 64 * MS, 65 * MS),
           ("%while.6 = (s32[])", 70 * MS, 95 * MS)]
    spans = [("sample", 0, 1 * MS), ("fill_train", 1 * MS, 66 * MS),
             ("download", 1 * MS, 2 * MS), ("eval", 66 * MS, 99 * MS),
             ("host_fetch", 67 * MS, 98 * MS),
             ("aggregate", 99 * MS, 100 * MS)]
    return {"modules": modules, "ops": ops}, spans


def test_reduce_events():
    dev, spans = _generation()
    red = devtrace.reduce_events([dev], spans)
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.058 + 0.003 + 0.025)
    assert red["eval_s"] == pytest.approx(0.025)
    assert red["fill_s"] == pytest.approx(0.058 + 0.002 + 0.001)
    assert red["alg3_s"] == pytest.approx(0.002)
    assert red["codec_s"] == pytest.approx(0.001)
    # leaf operations only, named by their program
    assert red["top_ops"][0] == ["fused_uploads/%conv.2 = f32[8]",
                                 pytest.approx(0.037)]
    assert red["top_ops"][1][0] == "fused_eval_shared/%while.6 = (s32[])"
    # the longest gaps, by the innermost host span at their middle
    gaps = red["idle_gaps"]
    assert gaps[0] == ["host_fetch", pytest.approx(0.005)]
    assert ["fill_train", pytest.approx(0.002)] in gaps
    assert ["sample", pytest.approx(0.002)] in gaps \
        or ["download", pytest.approx(0.002)] in gaps


def test_reduce_averages_devices():
    dev, spans = _generation()
    idle = {"modules": [], "ops": []}
    red = devtrace.reduce_events([dev, idle], spans)
    assert red["busy_s"] == pytest.approx((0.058 + 0.003 + 0.025) / 2)


def test_nothing_to_read():
    dev, _ = _generation()
    assert devtrace.reduce_events([dev], []) is None
    assert devtrace.reduce_events([], _generation()[1]) is None


def test_load_recorded_cpu_trace(tmp_path):
    """A trace recorded here: the host spans are read, and no TPU plane
    is found, so there is nothing to reduce."""
    import glob

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("sample"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("aggregate"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    devices, spans = devtrace.load(path[0])
    assert devices == []
    assert [s[0] for s in sorted(spans, key=lambda s: s[1])] == [
        "sample", "aggregate"]
    assert all(e > s for _, s, e in spans)
    assert devtrace.reduce_events(devices, spans) is None
