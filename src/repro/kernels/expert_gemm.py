"""Grouped (per-expert) GEMM Pallas TPU kernel.

Computes out[e] = x[e] @ w[e] for E experts: the compute core of the MoE
layer after dispatch.  grid = (E, C/bc, F/bf, D/bd) with the contraction
axis innermost/sequential and a (bc, bf) fp32 accumulator in VMEM scratch —
the canonical MXU matmul tiling, one expert per outer grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_C = 128
BLOCK_F = 128
BLOCK_D = 256


def _kernel(x_ref, w_ref, o_ref, acc_scr, *, num_d_blocks: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(di == num_d_blocks - 1)
    def _emit():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def expert_gemm(x, w, *, interpret, block_c=BLOCK_C, block_f=BLOCK_F,
                block_d=BLOCK_D):
    """x: (E, C, D); w: (E, D, F) -> (E, C, F)."""
    e, c, d = x.shape
    _, _, f = w.shape
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    assert c % block_c == 0 and f % block_f == 0 and d % block_d == 0, \
        (c, f, d, block_c, block_f, block_d)
    nd = d // block_d

    kernel = functools.partial(_kernel, num_d_blocks=nd)
    return pl.pallas_call(
        kernel,
        grid=(e, c // block_c, f // block_f, nd),
        in_specs=[
            pl.BlockSpec((None, block_c, block_d),
                         lambda ei, ci, fi, di: (ei, ci, di)),
            pl.BlockSpec((None, block_d, block_f),
                         lambda ei, ci, fi, di: (ei, di, fi)),
        ],
        out_specs=pl.BlockSpec((None, block_c, block_f),
                               lambda ei, ci, fi, di: (ei, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
    )(x, w)
