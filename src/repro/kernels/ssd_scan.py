"""Mamba2 SSD chunk-scan Pallas TPU kernel.

grid = (batch, heads, chunks); the chunk axis is innermost/sequential and
the (P x N) recurrent state lives in VMEM scratch carried across chunks.
Per chunk (Q = chunk length):
  y_diag = ((C B^T) * L) X        -- intra-chunk, two (Q,Q)/(Q,P) MXU matmuls
  y_off  = (C S_in^T) * exp(cumA) -- contribution of the carried state
  S_out  = S_in * exp(A_q) + X^T (B * decay)
All math in fp32 inside VMEM; inputs are the pre-discretized tensors the
jnp oracle (models/ssm.ssd_chunked) produces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, s_ref, state_scr, *,
            num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[...].astype(jnp.float32)          # (Q, P) pre-scaled by dt
    a = a_ref[...].astype(jnp.float32)          # (Q,) log-decay
    bm = b_ref[...].astype(jnp.float32)         # (Q, N)
    cm = c_ref[...].astype(jnp.float32)         # (Q, N)
    q = x.shape[0]

    a_cum = jnp.cumsum(a)                       # (Q,)
    # L[i, j] = exp(a_cum[i] - a_cum[j]) for j <= i (segment decay)
    seg = a_cum[:, None] - a_cum[None, :]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    l_mat = jnp.where(tri, jnp.exp(seg), 0.0)

    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    y_diag = jax.lax.dot_general(cb * l_mat, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    state_in = state_scr[...]                   # (P, N)
    y_off = jax.lax.dot_general(cm, state_in, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (Q, P)
    y_off = y_off * jnp.exp(a_cum)[:, None]
    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: S = S * exp(a_total) + X^T (B * decay_to_end)
    decay = jnp.exp(a_cum[-1] - a_cum)          # (Q,)
    upd = jax.lax.dot_general(x, bm * decay[:, None],
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state_in * jnp.exp(a_cum[-1]) + upd

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        s_ref[...] = state_scr[...]


def ssd_scan(x, a, b, c, initial_state=None, *, interpret):
    """x: (B, H, NC, Q, P); a: (B, H, NC, Q); b, c: (B, NC, Q, N).

    Returns (y: (B, H, NC, Q, P), final_state: (B, H, P, N)).
    ``initial_state`` must be None (zeros) — matching the oracle's default.
    """
    assert initial_state is None, "kernel assumes zero initial state"
    bsz, h, nc, q, p = x.shape
    n = b.shape[-1]

    kernel = functools.partial(_kernel, num_chunks=nc)
    y, s = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((None, None, None, q, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((None, None, None, q),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, q, n),
                         lambda bi, hi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((None, None, q, n),
                         lambda bi, hi, ci: (bi, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, q, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((None, None, p, n),
                         lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, nc, q, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, a, b, c)
    return y, s
