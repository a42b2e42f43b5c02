"""Flash attention Pallas TPU kernel (causal / sliding-window / bidirectional).

Online-softmax attention with the canonical TPU grid layout:
  grid = (batch*heads, q_blocks, kv_blocks); the kv dimension is the
  innermost, sequentially-iterated ("arbitrary") axis, and the running
  (m, l, acc) state lives in VMEM scratch that persists across kv steps.
Q/K/V tiles are (block_q, d) / (block_k, d) VMEM blocks; d is the full
head dim (MXU-aligned when d in {64, 128}).  Scores never touch HBM —
the whole point versus the XLA einsum path (ref.py).

K/V must be pre-expanded to the full query head count (GQA repeat happens
in ops.flash_attention, as in models/attention.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, block_q: int, block_k: int,
            num_kv_blocks: int, sm_scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), dtype=bool)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)

    # skip fully-masked tiles (above the causal diagonal / outside window)
    run = True
    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1
    if window:
        run = jnp.logical_and(
            run, (ki + 1) * block_k - 1 > qi * block_q - window)

    @pl.when(run)
    def _step():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_cur

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, interpret, causal=True, window=0,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """q, k, v: (BH, S, D) with identical head counts.  Returns (BH, S, D).

    ``interpret=True`` executes the kernel body in Python (CPU);
    ``False`` compiles it for the TPU.
    """
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    nq, nk = s // block_q, s // block_k
    sm_scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, num_kv_blocks=nk, sm_scale=sm_scale)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max m
            pltpu.VMEM((block_q,), jnp.float32),       # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
