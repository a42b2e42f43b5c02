"""Per-tensor symmetric int8 quantize/dequantize Pallas TPU kernels.

The wire codec's hot loop (``repro.comm.quantize``): map a float tensor
onto the 255-level symmetric grid ``{-127..127} * scale`` and back.
Both directions are pure memory-bound elementwise maps over a flat
(P,) vector — same blocking as ``fill_aggregate``: 1-D grid over
(8, 128)-aligned ``block``-sized tiles, the scalar scale broadcast to
every tile.  The scale itself (``max|x| / 127``) is a plain reduction
left to XLA; fusing it here would serialize the two passes the compiler
already overlaps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 64 * 128
QMAX = 127.0


def _quant_kernel(x_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (block,)
    scale = s_ref[...].astype(jnp.float32)      # (1,)
    q = jnp.clip(jnp.round(x / scale), -QMAX, QMAX)
    o_ref[...] = q.astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)          # (block,)
    scale = s_ref[...].astype(jnp.float32)      # (1,)
    o_ref[...] = (q * scale).astype(o_ref.dtype)


def _blocked_1d(kernel, x, scale, out_dtype, block, interpret):
    """Run an elementwise (vector, scalar-scale) kernel over 1-D tiles."""
    p = x.shape[0]
    pad = (-p) % block
    if pad:
        x = jnp.pad(x, (0, pad))
    scale = jnp.reshape(scale, (1,)).astype(jnp.float32)
    out = pl.pallas_call(
        kernel,
        grid=((p + pad) // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((p + pad,), out_dtype),
        interpret=interpret,
    )(x, scale)
    return out[:p]


def quantize_int8(x, scale, *, interpret, block=DEFAULT_BLOCK):
    """x: (P,) float; scale: scalar -> (P,) int8 on the symmetric grid."""
    return _blocked_1d(_quant_kernel, x, scale, jnp.int8, block, interpret)


def dequantize_int8(q, scale, *, interpret, dtype=jnp.float32,
                    block=DEFAULT_BLOCK):
    """q: (P,) int8; scale: scalar -> (P,) ``dtype`` (``q * scale``)."""
    return _blocked_1d(_dequant_kernel, q, scale, dtype, block, interpret)
