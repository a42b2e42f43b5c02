"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpreted on CPU
by ``ops.interpret_mode``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kh,d", [
    (2, 128, 4, 4, 64),
    (1, 256, 4, 2, 128),
    (1, 384, 6, 1, 64),     # MQA, non-pow2 seq (384 = 3 x 128)
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(dtype, b, s, h, kh, d, causal, window):
    q, k, v = (rand((b, s, h, d), dtype), rand((b, s, kh, d), dtype),
               rand((b, s, kh, d), dtype))
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    exp = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 5)


@pytest.mark.parametrize("b,nc,q,h,p,n", [
    (2, 4, 64, 3, 32, 16),
    (1, 2, 128, 2, 64, 64),
    (1, 8, 32, 1, 16, 8),
])
def test_ssd_scan_sweep(b, nc, q, h, p, n):
    xs = rand((b, nc, q, h, p), jnp.float32)
    a = -jnp.abs(rand((b, nc, q, h), jnp.float32)) * 0.1
    bm = rand((b, nc, q, n), jnp.float32)
    cm = rand((b, nc, q, n), jnp.float32)
    y_k, s_k = ops.ssd_scan(xs, a, bm, cm)
    y_r, s_r = ref.ssd_scan(xs, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=2e-4, atol=2e-4)


def test_ssd_scan_matches_chunked_model_path():
    """kernel == models/ssm.ssd_chunked (the xla 'backend') == oracle."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n, chunk = 2, 256, 2, 32, 16, 64
    x = rand((b, s, h, p), jnp.float32)
    dt = jnp.abs(rand((b, s, h), jnp.float32)) * 0.2
    a_head = -jnp.abs(rand((h,), jnp.float32))
    bm = rand((b, s, n), jnp.float32)
    cm = rand((b, s, n), jnp.float32)
    y_x, s_x = ssd_chunked(x, dt, a_head, bm, cm, chunk=chunk, backend="xla")
    y_p, s_p = ssd_chunked(x, dt, a_head, bm, cm, chunk=chunk,
                           backend="pallas")
    np.testing.assert_allclose(np.asarray(y_x, np.float32),
                               np.asarray(y_p, np.float32),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_x), np.asarray(s_p),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,p", [(2, 1000), (8, 8192), (5, 100000)])
def test_fill_aggregate_sweep(dtype, m, p):
    cl = rand((m, p), dtype)
    mk = jnp.asarray(RNG.integers(0, 2, size=(m, p)), dtype)
    w = jnp.asarray(RNG.random(m).astype(np.float32))
    w = w / w.sum()
    prev = rand((p,), dtype)
    out = ops.fill_aggregate(cl, mk, w, prev)
    exp = ref.fill_aggregate(cl, mk, w, prev)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])
    # prev-buffer donation must not change results: the kernel-level
    # aliasing path (input_output_aliases, exercised directly — the ops
    # wrapper's donating jit route is gated off-CPU)
    from repro.kernels import fill_aggregate as _fa
    donated = _fa.fill_aggregate(cl, mk, w, prev, interpret=True,
                                 donate_prev=True)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(donated, np.float32))
    # and the ops wrapper accepts the flag on any host
    wrapped = ops.fill_aggregate(cl, mk, w, prev, donate_prev=True)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(wrapped, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,c,d,f", [
    (2, 128, 256, 128),
    (4, 256, 256, 384),
    (1, 128, 512, 256),
])
def test_expert_gemm_sweep(dtype, e, c, d, f):
    x = rand((e, c, d), dtype)
    w = rand((e, d, f), dtype) * 0.05
    out = ops.expert_gemm(x, w)
    exp = ref.expert_gemm(x, w)
    scale = float(jnp.abs(exp.astype(jnp.float32)).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32) / scale,
                               np.asarray(exp, np.float32) / scale,
                               rtol=TOL[dtype], atol=TOL[dtype] * 10)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("p", [1000, 8192, 100000])
def test_quantize_int8_sweep(dtype, p):
    """Pallas quantize/dequantize vs the jnp references: the int8 grids
    must match exactly (same round/clip math), dequant to fp tolerance."""
    x = rand((p,), dtype).astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / 127.0
    q_k = ops.quantize_int8(x, scale)
    q_r = ref.quantize_int8(x, scale)
    assert q_k.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    d_k = ops.dequantize_int8(q_k, scale)
    d_r = ref.dequantize_int8(q_r, scale)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                               rtol=0, atol=0)
    # roundtrip error bound: half a quantization step
    np.testing.assert_array_less(np.abs(np.asarray(d_k) - np.asarray(x)),
                                 float(scale) / 2 + 1e-7)


def test_quantize_int8_zero_and_extremes():
    x = jnp.asarray([0.0, 1.0, -1.0, 0.5, -0.49], jnp.float32)
    scale = jnp.float32(1.0 / 127.0)
    q = np.asarray(ops.quantize_int8(x, scale))
    np.testing.assert_array_equal(q, [0, 127, -127, 64, -62])
    # values beyond the grid clip instead of wrapping
    big = jnp.asarray([10.0, -10.0], jnp.float32)
    np.testing.assert_array_equal(np.asarray(ops.quantize_int8(big, scale)),
                                  [127, -127])


def test_expert_ffn_kernel_matches_moe_module():
    from repro.models.moe import expert_ffn as moe_ffn
    e, c, d, f = 2, 128, 128, 256
    experts = {"wi": rand((e, d, f), jnp.float32) * 0.05,
               "wg": rand((e, d, f), jnp.float32) * 0.05,
               "wo": rand((e, f, d), jnp.float32) * 0.05}
    x = rand((e, c, d), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.expert_ffn(experts, x)),
                               np.asarray(moe_ffn(experts, x)),
                               rtol=1e-4, atol=1e-5)
