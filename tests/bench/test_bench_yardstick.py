"""The benchmark's own arithmetic: its copy of the supernet's MAC count,
the model FLOPs of a generation, the bytes Algorithm 3 and the int8
codec need, and the table of peaks."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import yardstick  # noqa: E402


def _model(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("config", ["cifar12", "cifar12-int8"])
def test_macs_match_program(config):
    """The copy counts what ``repro.core.flops.cnn_subnet_macs`` counts,
    key for key, at the configuration's published widths."""
    from repro.core.flops import cnn_subnet_macs
    model = _model(config)
    rng = np.random.default_rng(0)
    keys = [rng.integers(0, 4, len(model["channels"])) for _ in range(200)]
    keys += [np.full(len(model["channels"]), b) for b in range(4)]
    for k in keys:
        assert yardstick.subnet_macs(k, model) == cnn_subnet_macs(
            k, len(model["channels"]))


def test_macs_of_smallest_and_largest_subnets():
    model = _model("cifar12")
    ident = yardstick.subnet_macs([0] * 12, model)
    resid = yardstick.subnet_macs([1] * 12, model)
    # stem, the three reduction identities (two 1x1 stride-2 convs) and
    # the head
    stem = 32 * 32 * 64 * 3 * 9
    reds = sum(2 * (h // 2) ** 2 * (c // 2) * (c // 2)
               for h, c in ((32, 128), (16, 256), (8, 512)))
    assert ident == stem + reds + 512 * 10
    assert resid > 10 * ident


def test_model_flops_counts_forward_and_training():
    model = _model("cifar12")
    k = [0] * 12
    m = yardstick.subnet_macs(k, model)
    flops = yardstick.model_flops([(k, 500)], [(k, 1000), (k, 1000)],
                                  model, epochs=2)
    assert flops == 6.0 * m * 500 * 2 + 2.0 * m * 2000


def test_byte_counts():
    p = 26_119_059
    # ten uploads of one client each, the previous master, the new one
    assert yardstick.alg3_bytes(p, 10) == 12 * 4 * p
    # quantize: read f32, write int8; dequantize: read int8, write f32
    assert yardstick.int8_roundtrip_bytes(p) == 10 * p


def test_peaks_table():
    v5e = yardstick.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no peaks"):
        yardstick.peaks("TPU v9 imaginary")
