"""The benchmark's own arithmetic, whatever the supernet family: the
model FLOPs of a generation from a family's MACs per sample, the bytes
Algorithm 3 and the int8 codec need, and the table of device peaks.

Nothing here imports the program: the byte counts follow from the
shapes the algorithm touches, whatever implements it, and the MACs of
one sample through a choice key's sub-model come from the family
module (``families/<family>.py``, ``sample_macs``).
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence

F32_BYTES = 4
INT8_BYTES = 1

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def model_flops(train: Sequence, evals: Sequence,
                sample_macs: Callable[[Sequence[int]], int],
                epochs: int) -> float:
    """Model FLOPs of the work listed: ``train`` holds (key, samples) for
    every trained (key, client) pair, ``evals`` (key, samples) for every
    evaluated (key, client set), and ``sample_macs(key)`` gives the
    forward MACs of one sample through the sub-model ``key`` selects.
    2 FLOPs per MAC forward, 6 per MAC for a training step (forward and
    backward), per epoch; padding rows and recompute are not counted."""
    cache: Dict[tuple, int] = {}

    def macs(key):
        k = tuple(int(b) for b in key)
        if k not in cache:
            cache[k] = sample_macs(k)
        return cache[k]

    return (sum(6.0 * macs(k) * n * epochs for k, n in train)
            + sum(2.0 * macs(k) * n for k, n in evals))


def alg3_bytes(params: int, uploads: int) -> int:
    """HBM bytes Algorithm 3 needs for one master update: read every
    float32 upload and the previous master once, write the new master
    once.  The trained masks are per-leaf scalars of the choice key, so
    they cost no bytes in the algorithm's need."""
    return (uploads + 2) * params * F32_BYTES


def int8_roundtrip_bytes(params: int) -> int:
    """HBM bytes of one int8 quantize + dequantize pass over ``params``
    float32 values: quantize reads 4 B and writes 1 B per value,
    dequantize reads 1 B and writes 4 B."""
    return params * 2 * (F32_BYTES + INT8_BYTES)
