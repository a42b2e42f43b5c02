"""jit'd public wrappers around the Pallas kernels.

Interpret mode is decided by the platform the wrappers trace on
(``interpret_mode``): kernel bodies run in Python on CPU, where the tests
validate them against ``repro.kernels.ref``, and compile to Mosaic
custom calls on a TPU.  Any other platform is an error — there is no
silent fallback that would run the kernel bodies in Python on a device.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import expert_gemm as _eg
from repro.kernels import fill_aggregate as _fa
from repro.kernels import flash_attention as _flash
from repro.kernels import quantize as _q
from repro.kernels import ssd_scan as _ssd



def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: True on CPU, False on
    TPU; any other platform raises (the kernels are written for the TPU
    and would otherwise run their bodies in Python there)."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run on 'tpu' (compiled) or 'cpu' "
                       f"(interpreted); the platform is {platform!r}")


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B, S, H, D); k, v: (B, S, Kh, D) -> (B, S, H, D).

    GQA K/V are repeated to the full head count here (broadcast; stays
    sharded — see models/attention.py) and batch*heads fold into the
    kernel's leading grid axis.
    """
    b, s, h, d = q.shape
    kh = k.shape[2]
    if kh != h:
        k = jnp.repeat(k, h // kh, axis=2)
        v = jnp.repeat(v, h // kh, axis=2)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = _flash.flash_attention(fold(q), fold(k), fold(v), causal=causal,
                                 window=window, interpret=interpret_mode())
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@jax.jit
def ssd_scan(xs, a, bm, cm, initial_state=None):
    """Oracle-layout adapter: xs (B, NC, Q, H, P); a (B, NC, Q, H);
    bm, cm (B, NC, Q, N) -> (y (B, NC, Q, H, P), state (B, H, P, N))."""
    x_k = jnp.moveaxis(xs, 3, 1)                 # (B, H, NC, Q, P)
    a_k = jnp.moveaxis(a, 3, 1)                  # (B, H, NC, Q)
    y, s = _ssd.ssd_scan(x_k, a_k, bm, cm, initial_state,
                         interpret=interpret_mode())
    return jnp.moveaxis(y, 1, 3), s


@jax.jit
def _fill_aggregate_jit(clients, masks, weights, prev):
    return _fa.fill_aggregate(clients, masks, weights, prev,
                              interpret=interpret_mode())


@functools.partial(jax.jit, donate_argnums=(3,))
def _fill_aggregate_donate_jit(clients, masks, weights, prev):
    return _fa.fill_aggregate(clients, masks, weights, prev,
                              interpret=interpret_mode(), donate_prev=True)


def fill_aggregate(clients, masks, weights, prev, donate_prev=False):
    """clients, masks: (m, P); weights: (m,); prev: (P,) -> (P,).

    ``donate_prev`` donates the ``prev`` buffer at the jit boundary AND
    aliases the kernel's (block-padded) prev into its output
    (``input_output_aliases``), so the master update writes over the
    previous master's vector instead of allocating a fresh one.  Pass it
    only when ``prev`` is dead after the call (the last-chunk master
    update).  On CPU — where XLA cannot reuse donated buffers and warns
    per dispatch — the plain path is used regardless."""
    if donate_prev and jax.default_backend() != "cpu":
        return _fill_aggregate_donate_jit(clients, masks, weights, prev)
    return _fill_aggregate_jit(clients, masks, weights, prev)


@jax.jit
def expert_gemm(x, w):
    """x: (E, C, D); w: (E, D, F) -> (E, C, F)."""
    return _eg.expert_gemm(x, w, interpret=interpret_mode())


@jax.jit
def quantize_int8(x, scale):
    """x: (P,) float; scale: scalar -> (P,) int8 (symmetric grid)."""
    return _q.quantize_int8(x, scale, interpret=interpret_mode())


@jax.jit
def dequantize_int8(q, scale):
    """q: (P,) int8; scale: scalar -> (P,) float32 (``q * scale``)."""
    return _q.dequantize_int8(q, scale, interpret=interpret_mode())


@jax.jit
def expert_ffn(experts, x):
    """SwiGLU expert FFN on dispatched slots via the grouped-GEMM kernel.
    x: (E, C, d) -> (E, C, d)."""
    h = expert_gemm(x, experts["wi"])
    g = expert_gemm(x, experts["wg"])
    return expert_gemm(jax.nn.silu(g) * h, experts["wo"])
