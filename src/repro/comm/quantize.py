"""Int8 payload codec: per-tensor symmetric quantization.

Every floating leaf is quantized independently with one float32 scale
``max|x| / 127``; values land on the 255-level symmetric grid
``{-127..127} * scale`` (so ``x == 0`` maps to exactly 0 and the maximum
round-trip error is ``scale / 2``).  Wire cost is 1 byte per parameter
plus ``SCALE_BYTES`` per tensor — the per-payload tensor count is not
recoverable from a parameter count alone, so ``wire_bytes`` charges one
amortized scale per payload (an O(tensors/params) underestimate, well
under 0.1% on the supernet masters).

``backend="pallas"`` routes the elementwise quantize/dequantize through
the ``repro.kernels.quantize`` Pallas TPU kernel (compiled on a TPU,
interpreted on CPU, like every kernel in this repo); ``"xla"`` routes
through the ``repro.kernels.ref`` jnp oracles the kernel is swept
against — one definition of the grid math, so the routes cannot drift
(``tests/test_kernels.py`` / ``tests/test_comm.py``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.comm.codec import SCALE_BYTES, PayloadCodec, tree_map_float

QMAX = 127.0


def leaf_scale(x: jnp.ndarray) -> jnp.ndarray:
    """Per-tensor symmetric scale ``max|x| / 127`` (floored so an
    all-zero tensor round-trips to zeros instead of dividing by 0)."""
    return jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-12) / QMAX


@jax.jit
def _roundtrip_xla(tree):
    from repro.kernels import ref

    def leaf(x):
        xf = x.astype(jnp.float32)
        scale = leaf_scale(xf)
        q = ref.quantize_int8(xf.reshape(-1), scale)
        return ref.dequantize_int8(q, scale).reshape(x.shape).astype(x.dtype)

    return tree_map_float(leaf, tree)


@jax.jit
def _roundtrip_pallas(tree):
    from repro.kernels import ops as kops

    def leaf(x):
        xf = x.reshape(-1).astype(jnp.float32)
        scale = leaf_scale(xf)
        q = kops.quantize_int8(xf, scale)
        return kops.dequantize_int8(q, scale).reshape(x.shape).astype(x.dtype)

    return tree_map_float(leaf, tree)


@dataclasses.dataclass(frozen=True)
class Int8Codec(PayloadCodec):
    """Per-tensor symmetric int8 quantization (1 B/param on the wire)."""

    name: str = "int8"
    backend: str = "xla"        # 'xla' | 'pallas' quantize/dequantize route

    def wire_bytes(self, n_params: int) -> float:
        return 1.0 * n_params + SCALE_BYTES

    def roundtrip(self, tree):
        fn = (_roundtrip_pallas if self.backend == "pallas"
              else _roundtrip_xla)
        return fn(tree)
