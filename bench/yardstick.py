"""The benchmark's own arithmetic: model MACs per choice key, the model
FLOPs of a generation, the bytes Algorithm 3 and the int8 codec need,
and the table of device peaks.

Nothing here imports the program: the MAC count restates the supernet
of ``models/cnn.py`` from the configuration file's channel plan (it is
pinned against ``repro.core.flops.cnn_subnet_macs`` in the tests), and
the byte counts follow from the shapes the algorithm touches, whatever
implements it.
"""
from __future__ import annotations

from typing import Dict, Sequence

BRANCHES = ("identity", "residual", "inverted", "sepconv")
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


def _conv_macs(h, w, cin, cout, k, stride=1, groups=1) -> int:
    return (h // stride) * (w // stride) * cout * (cin // groups) * k * k


def branch_macs(name: str, h: int, w: int, cin: int, cout: int) -> int:
    """MACs of one choice-block branch on an ``h`` x ``w`` input; a block
    whose channels change is a reduction block (stride 2).  Strided
    depthwise work is counted at output resolution."""
    stride = 2 if cout != cin else 1
    ho, wo = h // stride, w // stride
    if name == "identity":
        return 0 if stride == 1 else 2 * _conv_macs(h, w, cin, cout // 2,
                                                    1, 2)
    if name == "residual":
        return (_conv_macs(h, w, cin, cout, 3, stride)
                + _conv_macs(ho, wo, cout, cout, 3))
    if name == "inverted":
        hid = 4 * cin
        return (_conv_macs(h, w, cin, hid, 1)
                + _conv_macs(h, w, hid, hid, 3, stride, groups=hid)
                + _conv_macs(ho, wo, hid, cout, 1))
    if name == "sepconv":
        return (_conv_macs(h, w, cin, cin, 3, stride, groups=cin)
                + _conv_macs(ho, wo, cin, cout, 1)
                + _conv_macs(ho, wo, cout, cout, 3, groups=cout)
                + _conv_macs(ho, wo, cout, cout, 1))
    raise ValueError(f"unknown branch {name!r}")


def subnet_macs(key: Sequence[int], model: Dict) -> int:
    """Forward MACs of one image through the subnet ``key`` selects, for
    the configuration file's ``model`` section (``channels``,
    ``stem_channels``, ``image``, ``classes``)."""
    chans = model["channels"]
    cin = model["stem_channels"]
    h = w = model["image"]
    total = _conv_macs(h, w, 3, cin, 3)
    for i, cout in enumerate(chans):
        total += branch_macs(BRANCHES[int(key[i])], h, w, cin, cout)
        if cout != cin:
            h, w = h // 2, w // 2
        cin = cout
    return int(total + cin * model["classes"])


def model_flops(train: Sequence, evals: Sequence, model: Dict,
                epochs: int) -> float:
    """Model FLOPs of the work listed: ``train`` holds (key, images) for
    every trained (key, client) pair, ``evals`` (key, images) for every
    evaluated (key, client set).  2 FLOPs per MAC forward, 6 per MAC
    for a training step (forward and backward), per epoch; padding rows
    and recompute are not counted."""
    cache: Dict[tuple, int] = {}

    def macs(key):
        k = tuple(int(b) for b in key)
        if k not in cache:
            cache[k] = subnet_macs(k, model)
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
