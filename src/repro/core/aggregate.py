"""Fill-aggregation (paper Algorithm 3).

Clients upload sub-models; the server reconstructs full master models by
*filling* the branches a client did not train with the previous master's
weights, then weighted-averages the reconstructions:

    theta(t) = sum_k w_k * ( mask_k * theta_k + (1 - mask_k) * theta(t-1) )

``mask_k`` marks the leaves client k actually trained, derived from its
choice key.  Non-choice-block leaves (stem, embeddings, norms, heads) have
mask 1 — they are trained by every client and plain-FedAvg'd, exactly the
``theta_k^i not in choice blocks`` case of Algorithm 3.

The reduction touches m x |theta| bytes and is the server-side hot spot at
production scale; ``repro.kernels.ops.fill_aggregate`` is the Pallas TPU
version of the flat inner loop (this module is its oracle).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Any


# ---------------------------------------------------------------------------
# Trained-leaf masks per model family
# ---------------------------------------------------------------------------

def cnn_trained_mask(params: Params, key: np.ndarray) -> Params:
    """Mask tree for the CIFAR CNN supernet (cnn.init_params layout)."""
    from repro.models.cnn import BRANCH_NAMES

    def ones_like(t):
        return jax.tree.map(lambda x: jnp.ones((), x.dtype), t)

    mask = {"stem": jnp.ones(()), "fc": ones_like(params["fc"]), "blocks": []}
    for i, blk in enumerate(params["blocks"]):
        bm = {}
        for b, name in enumerate(BRANCH_NAMES):
            sel = jnp.asarray(key[i] == b, jnp.float32)
            bm[name] = jax.tree.map(lambda x: sel, blk[name])
        mask["blocks"].append(bm)
    return mask


def supernet_trained_mask(params: Params, key: np.ndarray) -> Params:
    """Mask tree for transformer supernets: layer leaves are (L, 3, ...);
    branch b of layer l is trained iff key[l] == b + 1 (0 = identity trains
    nothing).  Everything outside ``layers`` is trained by every client."""
    key = jnp.asarray(key, jnp.int32)

    def layer_mask(x):
        l, nb = x.shape[0], x.shape[1]
        sel = (key[:, None] - 1) == jnp.arange(nb)[None, :]
        return sel.astype(jnp.float32).reshape((l, nb) + (1,) * (x.ndim - 2))

    mask = {}
    for k, v in params.items():
        if k == "layers":
            mask[k] = jax.tree.map(layer_mask, v)
        else:
            mask[k] = jax.tree.map(lambda x: jnp.ones((), jnp.float32), v)
    return mask


# ---------------------------------------------------------------------------
# Algorithm 3
# ---------------------------------------------------------------------------

def _flat_f32(leaves) -> jnp.ndarray:
    """Flatten leaves into one (P,) float32 vector (kernel layout)."""
    return jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in leaves])


def _flat_mask_f32(mask_leaves, leaves) -> jnp.ndarray:
    """Flatten mask leaves (scalar- or partially-broadcast) against their
    parameter leaves into one (P,) float32 vector."""
    return jnp.concatenate(
        [jnp.broadcast_to(m, x.shape).reshape(-1).astype(jnp.float32)
         for m, x in zip(mask_leaves, leaves)])


def _unflatten_like(flat, leaves_ref, treedef) -> Params:
    """Inverse of ``_flat_f32``: slice a (P,) vector back into the
    reference leaves' shapes and dtypes."""
    out, off = [], 0
    for x in leaves_ref:
        n = x.size
        out.append(flat[off: off + n].reshape(x.shape).astype(x.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def fill_aggregate(prev_master: Params,
                   uploads: Sequence[Tuple[Params, Params, float]],
                   backend: str = "xla") -> Params:
    """uploads: [(client_params, trained_mask, weight n_k/n)].  Weights are
    normalized here so partial participation stays a proper average."""
    total = float(sum(w for _, _, w in uploads))

    if backend == "pallas":
        from repro.kernels import ops as kops
        leaves_prev, treedef = jax.tree.flatten(prev_master)
        flat_prev = _flat_f32(leaves_prev)
        cl, mk = [], []
        for cp, cm, _ in uploads:
            lc = jax.tree.leaves(cp)
            cl.append(_flat_f32(lc))
            mk.append(_flat_mask_f32(jax.tree.leaves(cm), lc))
        ws = jnp.asarray([w / total for _, _, w in uploads], jnp.float32)
        flat = kops.fill_aggregate(jnp.stack(cl), jnp.stack(mk), ws, flat_prev)
        return _unflatten_like(flat, leaves_prev, treedef)

    clients = tuple(cp for cp, _, _ in uploads)
    masks = tuple(cm for _, cm, _ in uploads)
    weights = jnp.asarray([w / total for _, _, w in uploads], jnp.float32)
    return _combine_jit(prev_master, clients, masks, weights)


@jax.jit
def _combine_jit(prev_master, clients, masks, weights):
    def combine(prev, *cm_flat):
        n = len(cm_flat) // 2
        acc = jnp.zeros_like(prev, dtype=jnp.float32)
        for i in range(n):
            cp, m = cm_flat[i], cm_flat[n + i]
            m = jnp.broadcast_to(m, prev.shape).astype(jnp.float32)
            filled = (m * cp.astype(jnp.float32)
                      + (1 - m) * prev.astype(jnp.float32))
            acc = acc + weights[i] * filled
        return acc.astype(prev.dtype)

    return jax.tree.map(combine, prev_master, *clients, *masks)


def fill_aggregate_stacked(prev_master: Params,
                           chunks: Sequence[Tuple[Params, Any, np.ndarray]],
                           mask_fn: Callable,
                           backend: str = "xla",
                           total: Optional[float] = None) -> Params:
    """Batched Algorithm 3 for the vmap/mesh execution backends.

    ``chunks`` holds stacked uploads: each entry is ``(stacked_params,
    keys, weights)`` where every leaf of ``stacked_params`` carries a
    leading (P,) upload axis, ``keys`` is (P, num_blocks) int32 and
    ``weights`` is (P,).  Trained masks are derived inside the jitted body
    via ``vmap(mask_fn)``, so one dispatch per chunk replaces the
    per-upload Python loop of ``fill_aggregate`` (its oracle).

    ``backend="pallas"`` routes the reduction through the
    ``repro.kernels.fill_aggregate`` TPU kernel on the flattened
    parameter vector (the same route ``fill_aggregate`` takes); on CPU
    the kernel body executes in interpret mode
    (``kernels.ops.interpret_mode``), on a TPU it is compiled.  Weight
    normalization is global across chunks, so per-chunk partial sums
    compose exactly; callers whose chunk weights are ALREADY normalized
    pass ``total=1.0`` (the fused/mesh routes) — re-deriving it from the
    float sum would shift every weight by ~1 ulp, and that amplifies
    over generations of SGD.
    """
    if total is None:
        total = float(sum(float(np.sum(w)) for _, _, w in chunks))
    if backend == "pallas":
        return _fill_stacked_pallas(prev_master, chunks, mask_fn, total)
    acc = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                       prev_master)
    for stacked, keys, w in chunks:
        wnorm = jnp.asarray(np.asarray(w, np.float32) / total)
        acc = _fill_stacked_accumulate(acc, prev_master, stacked,
                                       jnp.asarray(keys, jnp.int32), wnorm,
                                       mask_fn=mask_fn)
    return jax.tree.map(lambda a, p: a.astype(p.dtype), acc, prev_master)


def _fill_stacked_pallas(prev_master: Params, chunks, mask_fn: Callable,
                         total: float) -> Params:
    """Kernel route of ``fill_aggregate_stacked``: flatten every chunk to
    the (m, P) client/mask matrices the Pallas kernel consumes and sum
    the per-chunk partials (weights are globally normalized, so the
    kernel's ``sum_k w_k * filled_k`` partials add up to Algorithm 3)."""
    from repro.kernels import ops as kops

    leaves_prev, treedef = jax.tree.flatten(prev_master)
    flat_prev = _flat_f32(leaves_prev)
    flat = None
    for i, (stacked, keys, w) in enumerate(chunks):
        wnorm = jnp.asarray(np.asarray(w, np.float32) / total)
        cl, mk = _flatten_chunk(stacked, jnp.asarray(keys, jnp.int32),
                                mask_fn=mask_fn)
        # flat_prev is dead after the last chunk, so its buffer can be
        # aliased into that call's output (kernel-level donation)
        part = kops.fill_aggregate(cl, mk, wnorm, flat_prev,
                                   donate_prev=(i == len(chunks) - 1))
        flat = part if flat is None else flat + part
    return _unflatten_like(flat, leaves_prev, treedef)


@functools.partial(jax.jit, static_argnames=("mask_fn",))
def _flatten_chunk(stacked, keys, mask_fn):
    """(stacked leaves (m, ...), keys (m, nb)) -> (m, P) client and mask
    matrices over the flattened parameter vector."""
    masks = jax.vmap(mask_fn)(stacked, keys)
    lc = jax.tree.leaves(stacked)
    lm = jax.tree.leaves(masks)
    m = lc[0].shape[0]
    cl = jnp.concatenate(
        [x.reshape(m, -1).astype(jnp.float32) for x in lc], axis=1)
    mk = jnp.concatenate(
        [jnp.broadcast_to(
            mm.reshape(mm.shape + (1,) * (x.ndim - mm.ndim)),
            x.shape).reshape(m, -1).astype(jnp.float32)
         for mm, x in zip(lm, lc)], axis=1)
    return cl, mk


def fill_partial(prev_master: Params, stacked: Params, masks: Params,
                 wnorm) -> Params:
    """The Algorithm 3 partial sum over one stack of uploads: per leaf,
    ``sum_k w_k * (mask_k * client_k + (1 - mask_k) * prev)`` in float32,
    where every ``stacked``/``masks`` leaf carries a leading (P,) upload
    axis and ``wnorm`` is the (P,) globally-normalized weight vector
    (0-weight rows — padding — contribute exactly nothing).

    This is THE reduction expression of the batched fill paths: the
    stacked aggregator below, the mesh backend's shard_map body and the
    fused-generation programs all call it, so their float32 reduction
    order matches expression for expression — the backend-parity
    guarantees rest on that."""

    def combine(prev, cp, m):
        m = m.astype(jnp.float32)
        m = m.reshape(m.shape + (1,) * (cp.ndim - m.ndim))
        filled = (m * cp.astype(jnp.float32)
                  + (1 - m) * prev.astype(jnp.float32)[None])
        w = wnorm.reshape((-1,) + (1,) * (cp.ndim - 1))
        return jnp.sum(w * filled, axis=0)

    return jax.tree.map(combine, prev_master, stacked, masks)


@functools.partial(jax.jit, static_argnames=("mask_fn",))
def _fill_stacked_accumulate(acc, prev_master, stacked, keys, wnorm,
                             mask_fn):
    """``acc`` plus one chunk's Algorithm 3 partial sum, in one program —
    the expression ``fill_bucket_partial`` adds per group inside the
    fused fill, so the compiler rounds both the same way."""
    masks = jax.vmap(mask_fn)(stacked, keys)
    return jax.tree.map(jnp.add, acc,
                        fill_partial(prev_master, stacked, masks, wnorm))


def fedavg(uploads: Sequence[Tuple[Params, float]]) -> Params:
    """Plain FedAvg (Algorithm 1 line 9) — the paper's baseline aggregator."""
    total = float(sum(w for _, w in uploads))
    weights = jnp.asarray([w / total for _, w in uploads], jnp.float32)
    return _fedavg_jit(tuple(p for p, _ in uploads), weights)


@jax.jit
def _fedavg_jit(clients, weights):
    def avg(*xs):
        acc = jnp.zeros_like(xs[0], dtype=jnp.float32)
        for i, x in enumerate(xs):
            acc = acc + weights[i] * x.astype(jnp.float32)
        return acc.astype(xs[0].dtype)

    return jax.tree.map(avg, *clients)
