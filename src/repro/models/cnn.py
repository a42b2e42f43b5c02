"""The paper's master model: CIFAR CNN supernet (Fig. 3 / Fig. 4), faithful.

Conv stem -> 12 choice blocks -> global-avg-pool -> FC.  Each choice block
has 4 branches: identity / residual / inverted-residual (MobileNetV2) /
depthwise-separable, in 'normal' (C->C) or 'reduction' (C->2C, spatial /2)
form depending on position.  Only normal blocks carry shortcut connections
(paper Fig. 4).  BatchNorm affine parameters and moving statistics are
DISABLED per Section IV.C — normalization uses current-batch statistics only.
Depthwise 3x3 convolutions (``_depthwise``) are one grouped
``convolution`` on TPU and nine shifted multiply-adds on CPU, where each
is the faster.

Branch selection is a traced int32 per block (``lax.switch``), so one
compilation serves every choice key — unlike the paper's per-key PyTorch
module rebuild.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.configs import cifar_supernet as cs
from repro.configs.base import ModelConfig

BRANCH_NAMES = ("identity", "residual", "inverted", "sepconv")


def _conv_init(key, kh, kw, cin, cout, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(kh * kw * cin)
    return jax.random.uniform(key, (kh, kw, cin, cout), dtype,
                              minval=-scale, maxval=scale)


def conv(x, w, stride=1, groups=1):
    if groups > 1:
        return _depthwise(x, w, stride)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=1)


def _depthwise(x, w, stride=1):
    """Depthwise KxK conv, w: (K, K, 1, C) (HWIO depthwise layout).

    Output pixel ``i`` sees inputs ``stride*i - K//2 .. stride*i + K//2``
    (zero outside) in both formulations; the platform picks one, no
    setting does.  XLA:TPU runs one grouped convolution faster than K^2
    shifted multiply-adds (each tap re-reads the activation from HBM);
    XLA:CPU runs the grouped convolution 5-25x slower than the taps at
    the shapes CPU runs use (measurements in PERF.md).  The choice is
    made while tracing, from the process's platform, as
    ``repro.kernels.ops.interpret_mode`` makes its own.
    """
    if jax.default_backend() == "cpu":
        return _depthwise_taps(x, w, stride)
    return _depthwise_grouped(x, w, stride)


def _depthwise_grouped(x, w, stride=1):
    """One grouped convolution, one group a channel.  Padding is explicit,
    ``K // 2`` on each side: at stride 2 on an even input ``"SAME"`` would
    pad (0, 1) and shift the window by one.  The stride lives inside the
    convolution, so strided blocks compute only the pixels they keep."""
    p = w.shape[0] // 2
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((p, p), (p, p)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1])


def _depthwise_taps(x, w, stride=1):
    """K^2 shifted elementwise multiply-adds over the padded input, then
    the stride."""
    k = w.shape[0]
    ph = pw = k // 2
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    h, wdt = x.shape[1], x.shape[2]
    out = None
    for i in range(k):
        for j in range(k):
            piece = xp[:, i: i + h, j: j + wdt, :] * w[i, j, 0]
            out = piece if out is None else out + piece
    if stride > 1:
        out = out[:, ::stride, ::stride, :]
    return out


def bn(x, eps=1e-5):
    """Paper-faithful BN: batch statistics only, no affine, no moving stats."""
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _dw_init(key, c, dtype=jnp.float32):
    # depthwise 3x3: HWIO with I=1, groups=c
    scale = 1.0 / math.sqrt(9)
    return jax.random.uniform(key, (3, 3, 1, c), dtype,
                              minval=-scale, maxval=scale)


# ---------------------------------------------------------------------------
# Branch param init (heterogeneous shapes => per-block dicts, no stacking)
# ---------------------------------------------------------------------------

def branch_init(key, name: str, cin: int, cout: int) -> Dict:
    red = cout != cin
    ks = jax.random.split(key, 6)
    if name == "identity":
        if not red:
            return {"_": jnp.zeros((1,), jnp.float32)}  # placeholder leaf
        half = cout // 2
        return {"pw1": _conv_init(ks[0], 1, 1, cin, half),
                "pw2": _conv_init(ks[1], 1, 1, cin, half)}
    if name == "residual":
        return {"c1": _conv_init(ks[0], 3, 3, cin, cout),
                "c2": _conv_init(ks[1], 3, 3, cout, cout)}
    if name == "inverted":
        hid = 4 * cin
        return {"pw1": _conv_init(ks[0], 1, 1, cin, hid),
                "dw": _dw_init(ks[1], hid),
                "pw2": _conv_init(ks[2], 1, 1, hid, cout)}
    if name == "sepconv":
        return {"dw1": _dw_init(ks[0], cin),
                "pw1": _conv_init(ks[1], 1, 1, cin, cout),
                "dw2": _dw_init(ks[2], cout),
                "pw2": _conv_init(ks[3], 1, 1, cout, cout)}
    raise ValueError(name)


def branch_apply(name: str, p: Dict, x, cin: int, cout: int):
    red = cout != cin
    stride = 2 if red else 1
    if name == "identity":
        if not red:
            return x
        a = conv(x, p["pw1"], stride=2)
        b = conv(x, p["pw2"], stride=2)
        return jnp.concatenate([a, b], axis=-1)
    if name == "residual":
        h = jax.nn.relu(bn(conv(x, p["c1"], stride=stride)))
        h = bn(conv(h, p["c2"]))
        if not red:
            h = h + x
        return jax.nn.relu(h)
    if name == "inverted":
        h = jax.nn.relu(bn(conv(x, p["pw1"])))
        h = jax.nn.relu(bn(conv(h, p["dw"], stride=stride,
                                groups=h.shape[-1])))
        h = bn(conv(h, p["pw2"]))
        if not red:
            h = h + x
        return h
    if name == "sepconv":
        h = conv(x, p["dw1"], stride=stride, groups=cin)
        h = jax.nn.relu(bn(conv(h, p["pw1"])))
        h = conv(h, p["dw2"], groups=cout)
        h = jax.nn.relu(bn(conv(h, p["pw2"])))
        if not red:
            h = h + x
        return h
    raise ValueError(name)


# ---------------------------------------------------------------------------
# Supernet init / forward
# ---------------------------------------------------------------------------

def init_params(rng, cfg: ModelConfig) -> Dict:
    n = cfg.num_layers
    chans = cs.channels_for(n)
    stem_c = cs.stem_channels_for(n)
    keys = jax.random.split(rng, n * 4 + 2)
    params: Dict = {
        "stem": _conv_init(keys[-2], 3, 3, 3, stem_c),
        "fc": {"w": _conv_init(keys[-1], 1, 1, chans[-1],
                               cs.NUM_CLASSES)[0, 0],
               "b": jnp.zeros((cs.NUM_CLASSES,), jnp.float32)},
        "blocks": [],
    }
    cin = stem_c
    for i in range(n):
        cout = chans[i]
        blk = {nm: branch_init(keys[i * 4 + j], nm, cin, cout)
               for j, nm in enumerate(BRANCH_NAMES)}
        params["blocks"].append(blk)
        cin = cout
    return params


def forward(params: Dict, images, choice_key) -> jax.Array:
    """images: (B, H, W, 3) float32; choice_key: (num_blocks,) int32."""
    n = len(params["blocks"])
    chans = cs.channels_for(n)
    cin = cs.stem_channels_for(n)
    h = jax.nn.relu(bn(conv(images, params["stem"])))
    for i, blk in enumerate(params["blocks"]):
        cout = chans[i]
        fns = [
            (lambda p=blk[nm], nm=nm, ci=cin, co=cout:
             (lambda hh: branch_apply(nm, p, hh, ci, co)))()
            for nm in BRANCH_NAMES
        ]
        h = jax.lax.switch(choice_key[i], fns, h)
        cin = cout
    h = jnp.mean(h, axis=(1, 2))
    return h @ params["fc"]["w"] + params["fc"]["b"]
