"""Plain reference of one real-time NAS search generation over the CNN
supernet family.

A straightforward restatement, in ``jax.numpy``, of what the program
computes for the steps the benchmark compares: the supernet of the
paper (arXiv:2003.02793, Fig. 3 and Sec. IV.C: 12 choice blocks of
identity / residual / inverted-residual / depthwise-separable branches,
batch norm on batch statistics without affine parameters), its seeded
initialization, local SGD with momentum on each client's shard,
Algorithm 3's fill-aggregation, the weighted test error of every key,
and the per-tensor int8 payload codec with server-side error feedback.

It imports nothing of the program.  Its inputs are the
configuration file, the seed, the client shards, and the choice keys and
client groups the search drew; it makes its own weights and follows the
master updates from them.  The fitness evaluation alone is scored on a
master it is handed (``errors``): the program's, so that the error
rates compared measure the fitness program and not the drift of two
training chains.  Matrix products run at ``Precision.HIGHEST`` in
float32.  The same code one precision step lower is the control that
the comparison must fail: three bfloat16 passes per product
(``three_pass``, what TPU's ``Precision.HIGH`` computes).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BRANCHES = ("identity", "residual", "inverted", "sepconv")
HIGHEST = jax.lax.Precision.HIGHEST
QMAX = 127.0


class Reference:
    """The reference for one configuration file: weights, activations and
    optimizer state in float32, every convolution and matrix product at
    ``Precision.HIGHEST``."""

    def __init__(self, cfg: Dict, three_pass: bool = False,
                 half_batch: bool = False):
        model, search = cfg["model"], cfg["search"]
        self.channels = tuple(model["channels"])
        self.stem = model["stem_channels"]
        self.classes = model["classes"]
        self.momentum = search["momentum"]
        self.epochs = search["local_epochs"]
        self.lr0 = search["lr0"]
        self.decay = search["lr_decay"]
        ex = cfg["execution"]
        self.down_int8 = ex["downlink_codec"].startswith("int8")
        self.up_int8 = ex["uplink_codec"].startswith("int8")
        self.three_pass = three_pass
        # a planted fault for the benchmark's own checks: local SGD on the
        # first half of every batch, the mean taken over that half
        self.half_batch = half_batch
        self._sgd = jax.jit(self._local_sgd)
        self._fill = jax.jit(self._fill_add)
        self._wrong = jax.jit(self._wrong_count)
        self._init = jax.jit(self._init_params)

    # -- model ---------------------------------------------------------------

    def _init_params(self, rng):
        def uniform(key, shape, fan_in):
            s = 1.0 / math.sqrt(fan_in)
            return jax.random.uniform(key, shape, jnp.float32, -s, s)

        def branch(key, name, cin, cout):
            ks = jax.random.split(key, 6)
            if name == "identity":
                if cin == cout:
                    return {"_": jnp.zeros((1,), jnp.float32)}
                return {"pw1": uniform(ks[0], (1, 1, cin, cout // 2), cin),
                        "pw2": uniform(ks[1], (1, 1, cin, cout // 2), cin)}
            if name == "residual":
                return {"c1": uniform(ks[0], (3, 3, cin, cout), 9 * cin),
                        "c2": uniform(ks[1], (3, 3, cout, cout), 9 * cout)}
            if name == "inverted":
                hid = 4 * cin
                return {"pw1": uniform(ks[0], (1, 1, cin, hid), cin),
                        "dw": uniform(ks[1], (3, 3, 1, hid), 9),
                        "pw2": uniform(ks[2], (1, 1, hid, cout), hid)}
            return {"dw1": uniform(ks[0], (3, 3, 1, cin), 9),
                    "pw1": uniform(ks[1], (1, 1, cin, cout), cin),
                    "dw2": uniform(ks[2], (3, 3, 1, cout), 9),
                    "pw2": uniform(ks[3], (1, 1, cout, cout), cout)}

        n = len(self.channels)
        keys = jax.random.split(rng, 4 * n + 2)
        last = self.channels[-1]
        params = {"stem": uniform(keys[-2], (3, 3, 3, self.stem), 27),
                  "fc": {"w": uniform(keys[-1], (1, 1, last, self.classes),
                                      last)[0, 0],
                         "b": jnp.zeros((self.classes,), jnp.float32)},
                  "blocks": []}
        cin = self.stem
        for i, cout in enumerate(self.channels):
            params["blocks"].append(
                {nm: branch(keys[4 * i + j], nm, cin, cout)
                 for j, nm in enumerate(BRANCHES)})
            cin = cout
        return params

    def init(self, seed: int):
        """The master the search starts from, made from the seed."""
        return self._init(jax.random.PRNGKey(seed))

    def _product(self, op, a, b):
        """``op(a, b)`` for a bilinear ``op``; with ``three_pass``, as the
        sum of the three bfloat16 products of the operands' high and low
        halves (the low-by-low term dropped)."""
        if not self.three_pass:
            return op(a, b)

        def halves(v):
            hi = v.astype(jnp.bfloat16).astype(v.dtype)
            return hi, (v - hi).astype(jnp.bfloat16).astype(v.dtype)

        (ah, al), (bh, bl) = halves(a), halves(b)
        return op(ah, bh) + op(ah, bl) + op(al, bh)

    def _conv(self, x, w, stride=1):
        return self._product(lambda a, b: jax.lax.conv_general_dilated(
            a, b, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST), x, w)

    def _depthwise(self, x, w, stride=1):
        # a 3x3 window centred on every output pixel: pad one on each
        # side, then stride (output pixel i sees inputs 2i-1 .. 2i+1)
        return self._product(lambda a, b: jax.lax.conv_general_dilated(
            a, b, (stride, stride), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=a.shape[-1], precision=HIGHEST),
            x, w)

    @staticmethod
    def _bn(x, eps=1e-5):
        mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2), keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)

    def _branch(self, name, p, x, cin, cout):
        relu, bn = jax.nn.relu, self._bn
        stride = 2 if cout != cin else 1
        if name == "identity":
            if stride == 1:
                return x
            return jnp.concatenate([self._conv(x, p["pw1"], 2),
                                    self._conv(x, p["pw2"], 2)], axis=-1)
        if name == "residual":
            h = relu(bn(self._conv(x, p["c1"], stride)))
            h = bn(self._conv(h, p["c2"]))
            return relu(h + x if stride == 1 else h)
        if name == "inverted":
            h = relu(bn(self._conv(x, p["pw1"])))
            h = relu(bn(self._depthwise(h, p["dw"], stride)))
            h = bn(self._conv(h, p["pw2"]))
            return h + x if stride == 1 else h
        h = self._depthwise(x, p["dw1"], stride)
        h = relu(bn(self._conv(h, p["pw1"])))
        h = self._depthwise(h, p["dw2"])
        h = relu(bn(self._conv(h, p["pw2"])))
        return h + x if stride == 1 else h

    def logits(self, params, images, key):
        h = jax.nn.relu(self._bn(self._conv(images, params["stem"])))
        cin = self.stem
        for i, (blk, cout) in enumerate(zip(params["blocks"],
                                            self.channels)):
            fns = [lambda hh, nm=nm, ci=cin, co=cout:
                   self._branch(nm, blk[nm], hh, ci, co) for nm in BRANCHES]
            h = jax.lax.switch(key[i], fns, h)
            cin = cout
        h = jnp.mean(h, axis=(1, 2))
        return self._product(lambda a, b: jnp.dot(
            a, b, precision=HIGHEST), h,
            params["fc"]["w"]) + params["fc"]["b"]

    def _loss(self, params, x, y, key):
        logp = jax.nn.log_softmax(self.logits(params, x, key))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    # -- one client, Algorithm 3, fitness ------------------------------------

    def _local_sgd(self, params, key, xb, yb, lr):
        if self.half_batch:
            half = xb.shape[1] // 2
            xb, yb = xb[:, :half], yb[:, :half]
        vel = jax.tree.map(jnp.zeros_like, params)

        def step(carry, batch):
            p, v = carry
            g = jax.grad(self._loss)(p, batch[0], batch[1], key)
            v = jax.tree.map(lambda vv, gg: self.momentum * vv + gg, v, g)
            p = jax.tree.map(lambda pp, vv: pp - lr * vv, p, v)
            return (p, v), None

        for _ in range(self.epochs):
            (params, vel), _ = jax.lax.scan(step, (params, vel), (xb, yb))
        return params

    def _mask(self, params, key):
        """1 for every leaf the client trained: the stem and head always,
        a block's branch iff the key selects it."""
        mask = jax.tree.map(lambda x: jnp.ones((), x.dtype), params)
        for i, blk in enumerate(mask["blocks"]):
            for b, nm in enumerate(BRANCHES):
                sel = (key[i] == b).astype(jnp.float32)
                blk[nm] = jax.tree.map(lambda _, s=sel: s, blk[nm])
        return mask

    def _fill_add(self, acc, prev, upload, key, w):
        mask = self._mask(prev, key)
        return jax.tree.map(
            lambda a, p, u, m: a + w * (m * u + (1 - m) * p),
            acc, prev, upload, mask)

    def _wrong_count(self, params, key, xb, yb):
        def one(acc, batch):
            pred = jnp.argmax(self.logits(params, batch[0], key), -1)
            return acc + jnp.sum(pred != batch[1]), None

        flat_x = xb.reshape((-1,) + xb.shape[2:])
        flat_y = yb.reshape((-1,) + yb.shape[2:])
        return jax.lax.scan(one, jnp.zeros((), jnp.int32),
                            (flat_x, flat_y))[0]

    # -- the int8 payload codec ----------------------------------------------

    @staticmethod
    def int8_roundtrip(tree):
        """Per-tensor symmetric int8: scale ``max|x| / 127`` (floored),
        round half to even onto {-127..127}, back to float32."""
        def leaf(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / QMAX
            return jnp.clip(jnp.round(x / scale), -QMAX, QMAX) * scale

        return jax.tree.map(
            lambda x: leaf(x.astype(jnp.float32)).astype(x.dtype), tree)

    # -- a whole run ---------------------------------------------------------

    def lr(self, gen: int):
        return jnp.asarray(self.lr0 * self.decay ** (gen - 1), jnp.float32)

    def run(self, seed: int, steps: Sequence[Dict], shards: Dict):
        """Follow the search's first master updates from the reference's
        own weights.

        ``steps`` lists the search's calls in order: ``{"kind": "fill",
        "gen", "keys", "groups"}`` and ``{"kind": "eval", "keys",
        "clients"}`` (skipped here).  ``shards[cid]`` is (train x, train
        y, test x, test y) as numpy batches.  Returns the master before
        the first update and after each (host float32 trees)."""
        master = self.init(seed)
        masters: List = [_host(master)]
        resid = None
        for st in steps:
            if st["kind"] != "fill":
                continue
            sent = self.int8_roundtrip(master) if self.down_int8 else master
            pairs = [(k, c) for k, g in zip(st["keys"], st["groups"])
                     for c in g]
            total = float(sum(shards[c][0].shape[0] * shards[c][0].shape[1]
                              for _, c in pairs))
            acc = jax.tree.map(jnp.zeros_like, sent)
            lr = self.lr(st["gen"])
            for k, c in pairs:
                xb, yb = shards[c][0], shards[c][1]
                key = jnp.asarray(k, jnp.int32)
                up = self._sgd(sent, key, jnp.asarray(xb, jnp.float32),
                               jnp.asarray(yb), lr)
                w = jnp.asarray(xb.shape[0] * xb.shape[1] / total,
                                jnp.float32)
                acc = self._fill(acc, sent, up, key, w)
            if self.up_int8:
                delta = jax.tree.map(lambda a, s: a - s, acc, sent)
                if resid is None:
                    resid = jax.tree.map(jnp.zeros_like, delta)
                target = jax.tree.map(jnp.add, delta, resid)
                q = self.int8_roundtrip(target)
                resid = jax.tree.map(jnp.subtract, target, q)
                acc = jax.tree.map(jnp.add, sent, q)
            master = acc
            masters.append(_host(master))
        return masters

    def errors(self, steps: Sequence[Dict], shards: Dict,
               masters: Sequence) -> List[np.ndarray]:
        """The test error rate of every key of every ``eval`` step, each
        scored on ``masters[n]``, the master after the ``n`` fills that
        precede it (``masters[0]`` is the start), sent through the
        downlink codec as the search sends it."""
        errs: List[np.ndarray] = []
        fills = 0
        for st in steps:
            if st["kind"] == "fill":
                fills += 1
                continue
            master = jax.tree.map(jnp.asarray, masters[fills])
            sent = self.int8_roundtrip(master) if self.down_int8 else master
            txb = jnp.asarray(np.stack([shards[c][2] for c in st["clients"]]),
                              jnp.float32)
            tyb = jnp.asarray(np.stack([shards[c][3] for c in st["clients"]]))
            total = int(np.prod(tyb.shape))
            errs.append(np.asarray(
                [int(self._wrong(sent, jnp.asarray(k, jnp.int32), txb, tyb))
                 / total for k in st["keys"]]))
        return errs


def control(cfg: Dict) -> Reference:
    """The reference one precision step below the configuration's
    ``highest``: three bfloat16 passes per product."""
    if cfg["model"]["matmul_precision"] != "highest":
        raise ValueError("the control is defined for a configuration at "
                         "matmul precision 'highest'")
    return Reference(cfg, three_pass=True)


def _host(tree):
    """A float32 numpy copy of a device tree."""
    return jax.tree.map(lambda x: np.asarray(x, np.float32),
                        jax.device_get(tree))
