"""Fill-aggregation (paper Algorithm 3) Pallas TPU kernel.

The server-side hot loop: for every parameter element,
    out = sum_k w_k * (mask_k * client_k + (1 - mask_k) * prev)
over m client uploads.  Pure memory-bound elementwise reduction over
(m x P) bytes; tiled (m, block_p) so each VMEM tile is reused across the
m-way reduction, with the (8, 128)-aligned block on the last axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 64 * 128


def _kernel(c_ref, m_ref, w_ref, p_ref, o_ref):
    prev = p_ref[...].astype(jnp.float32)       # (block,)
    cl = c_ref[...].astype(jnp.float32)         # (m, block)
    mk = m_ref[...].astype(jnp.float32)         # (m, block)
    w = w_ref[...].astype(jnp.float32)          # (m,)
    filled = mk * cl + (1.0 - mk) * prev[None, :]
    o_ref[...] = jnp.sum(w[:, None] * filled, axis=0).astype(o_ref.dtype)


def fill_aggregate(clients, masks, weights, prev, *, interpret,
                   block=DEFAULT_BLOCK, donate_prev=False):
    """clients, masks: (m, P); weights: (m,); prev: (P,) -> (P,).

    ``interpret`` runs the kernel body in Python (CPU) instead of
    compiling it for the TPU; ``repro.kernels.ops`` decides it by
    platform.

    ``donate_prev`` aliases the ``prev`` buffer into the output
    (``input_output_aliases``): grid step i reads prev's block i before
    writing out's block i and blocks never overlap, so the master update
    can reuse the previous master's buffer instead of allocating a fresh
    (P,) vector.  Only pass it when the caller no longer needs ``prev``
    after the call (XLA copies defensively otherwise, losing the
    saving)."""
    m, p = clients.shape
    pad = (-p) % block
    if pad:
        clients = jnp.pad(clients, ((0, 0), (0, pad)))
        masks = jnp.pad(masks, ((0, 0), (0, pad)))
        prev_p = jnp.pad(prev, (0, pad))
    else:
        prev_p = prev
    n_blocks = (p + pad) // block

    out = pl.pallas_call(
        _kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((m, block), lambda i: (0, i)),
            pl.BlockSpec((m, block), lambda i: (0, i)),
            pl.BlockSpec((m,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((p + pad,), prev.dtype),
        input_output_aliases={3: 0} if donate_prev else {},
        interpret=interpret,
    )(clients, masks, weights, prev_p)
    return out[:p]
