"""GShard/Switch-style top-k MoE with capacity-bounded gather dispatch.

Dispatch is gather/scatter based (O(T*k*d) data movement) rather than the
classic one-hot-einsum formulation (O(T*E*C*d) FLOPs) — at assigned-config
scale (1M tokens, 16 experts) the einsum dispatch would add ~7e18 flops of
pure bookkeeping.  The expert GEMM itself is a grouped matmul that maps to
the ``expert_gemm`` Pallas kernel on TPU.

Experts are sharded over the ``model`` mesh axis (expert parallelism); with
that sharding XLA turns the gather/scatter pair into the paper-standard
all-to-all exchange.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, mlp, mlp_init, uniform_init


def moe_init(key, cfg):
    kr, ke, ks = jax.random.split(key, 3)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": {"w": uniform_init(kr, (d, e), scale, cfg.jdtype)},
        "experts": {
            "wi": uniform_init(jax.random.fold_in(ke, 0), (e, d, f), scale,
                               cfg.jdtype),
            "wg": uniform_init(jax.random.fold_in(ke, 1), (e, d, f), scale,
                               cfg.jdtype),
            "wo": uniform_init(jax.random.fold_in(ke, 2), (e, f, d),
                               1.0 / math.sqrt(f), cfg.jdtype),
        },
    }
    if cfg.shared_expert:
        p["shared"] = mlp_init(ks, d, cfg.d_ff, cfg.jdtype)
    return p


def _capacity(tokens: int, num_experts: int, top_k: int, factor: float) -> int:
    c = int(math.ceil(tokens * top_k * factor / num_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly tiling


def expert_ffn(experts, x):
    """Grouped SwiGLU over (E, C, d) slots -> (E, C, d)."""
    h = jnp.einsum("ecd,edf->ecf", x, experts["wi"])
    g = jnp.einsum("ecd,edf->ecf", x, experts["wg"])
    h = jax.nn.silu(g) * h
    return jnp.einsum("ecf,efd->ecd", h, experts["wo"])


def moe_apply(p, x, cfg, *, ff_mask=None) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss).  ``ff_mask`` optionally narrows the
    expert hidden dim (supernet 'bottleneck' branch).

    Dispatches to the shard_map expert-parallel implementation when the
    launcher registered a mesh whose axes divide the expert/batch dims;
    otherwise runs the pure-GSPMD gather formulation below.
    """
    from repro.launch import policy
    mesh = policy.get_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        m_size = mesh.shape["model"]
        d_size = policy.data_axis_size(mesh)
        if (cfg.num_experts % m_size == 0 and x.shape[0] % d_size == 0):
            return _moe_apply_shard_map(p, x, cfg, mesh, ff_mask=ff_mask)
    return _moe_apply_gather(p, x, cfg, ff_mask=ff_mask)


def _moe_apply_gather(p, x, cfg, *, ff_mask=None):
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.top_k
    cap = _capacity(t, e, k, cfg.capacity_factor)
    x2 = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)          # (t, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss.
    me = probs.mean(axis=0)                              # mean router prob
    ce = jnp.bincount(expert_idx.reshape(-1), length=e).astype(jnp.float32)
    ce = ce / (t * k)
    aux = e * jnp.sum(me * ce)

    # Sort-based dispatch: rank every (token, choice) within its expert via
    # one argsort over t*k routing decisions — O(t*k) memory, never
    # materializing the O(t*e) one-hot/cumsum bookkeeping (which costs
    # ~280 GB/device of temp at prefill_32k scale for granite's 32 experts).
    flat_expert = expert_idx.reshape(-1).astype(jnp.int32)       # (t*k,)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    starts = jnp.searchsorted(sorted_expert, jnp.arange(e, dtype=jnp.int32))
    rank_sorted = (jnp.arange(t * k, dtype=jnp.int32)
                   - starts[sorted_expert])
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted)
    kept = rank < cap
    slot = jnp.where(kept, flat_expert * cap + rank, e * cap)    # (t*k,)
    token_of_choice = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    # Scatter tokens into (E*C) slots, gather activations, run grouped GEMM.
    slot_token = jnp.zeros((e * cap + 1,), jnp.int32)
    slot_used = jnp.zeros((e * cap + 1,), dtype=x2.dtype)
    slot_token = slot_token.at[slot].set(token_of_choice, mode="drop")
    slot_used = slot_used.at[slot].set(1.0, mode="drop")
    expert_in = x2[slot_token[: e * cap]] * slot_used[: e * cap, None]
    expert_in = expert_in.reshape(e, cap, d)
    if ff_mask is not None:
        # narrow the expert hidden dim by masking (supernet bottleneck)
        h = jnp.einsum("ecd,edf->ecf", expert_in, p["experts"]["wi"])
        g = jnp.einsum("ecd,edf->ecf", expert_in, p["experts"]["wg"])
        h = jax.nn.silu(g) * h * ff_mask.astype(h.dtype)
        expert_out = jnp.einsum("ecf,efd->ecd", h, p["experts"]["wo"])
    else:
        expert_out = expert_ffn(p["experts"], expert_in)
    out_flat = expert_out.reshape(e * cap, d)
    out_flat = jnp.concatenate([out_flat, jnp.zeros((1, d), out_flat.dtype)])

    slot_tk = slot.reshape(t, k)
    y2 = jnp.zeros((t, d), x2.dtype)
    for j in range(k):
        y2 = y2 + (out_flat[slot_tk[:, j]]
                   * gate[:, j, None].astype(x2.dtype))

    if "shared" in p:
        y2 = y2 + mlp(p["shared"], x2)
    return y2.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism (GShard-style all-to-all)
# ---------------------------------------------------------------------------

def _local_dispatch(x2, router_w, e, k, cap):
    """Sort-based local routing.  x2: (t, d) local tokens.
    Returns (expert_in (e, cap, d), slot (t*k,), gate (t, k), aux)."""
    t = x2.shape[0]
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(axis=0)
    ce = jnp.bincount(expert_idx.reshape(-1), length=e).astype(jnp.float32)
    aux = e * jnp.sum(me * ce / (t * k))

    flat_expert = expert_idx.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    starts = jnp.searchsorted(sorted_expert, jnp.arange(e, dtype=jnp.int32))
    rank_sorted = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_expert]
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted)
    kept = rank < cap
    slot = jnp.where(kept, flat_expert * cap + rank, e * cap)
    token_of_choice = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    slot_token = jnp.zeros((e * cap + 1,), jnp.int32)
    slot_used = jnp.zeros((e * cap + 1,), dtype=x2.dtype)
    slot_token = slot_token.at[slot].set(token_of_choice, mode="drop")
    slot_used = slot_used.at[slot].set(1.0, mode="drop")
    expert_in = (x2[slot_token[: e * cap]]
                 * slot_used[: e * cap, None]).reshape(e, cap, x2.shape[1])
    return expert_in, slot, gate, aux


def _moe_apply_shard_map(p, x, cfg, mesh, *, ff_mask=None):
    """Expert parallelism over the 'model' axis with explicit all-to-all.

    Per device: route the LOCAL tokens (local capacity), all-to-all the
    (e, cap, d) dispatch buffer over the model axis so each device holds its
    e/M experts' slots from every peer, run the grouped GEMM with
    FSDP-all-gathered expert weights, all-to-all back, combine locally.
    The paper-standard GShard communication pattern, explicit in the HLO.
    """
    from jax.sharding import PartitionSpec as P

    data_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    m = mesh.shape["model"]
    e, k = cfg.num_experts, cfg.top_k
    b, s, d = x.shape
    d_size = 1
    for a in data_ax:
        d_size *= mesh.shape[a]
    t_loc = (b // d_size) * s
    t_pad = -(-t_loc // m) * m            # pad so the model axis can split
    t_slice = t_pad // m                  # tokens routed per device
    cap = _capacity(t_slice, e, k, cfg.capacity_factor)
    experts = p["experts"]

    def body(x_loc, router_w, wi, wg, wo):
        # x_loc: (b_loc, s, d) — replicated over 'model', sharded over data;
        # each model column routes a distinct 1/M slice of the local tokens.
        bl, sl, _ = x_loc.shape
        x2 = x_loc.reshape(bl * sl, d)
        if t_pad != bl * sl:
            x2 = jnp.pad(x2, ((0, t_pad - bl * sl), (0, 0)))
        col = jax.lax.axis_index("model")
        xs = jax.lax.dynamic_slice(x2, (col * t_slice, 0), (t_slice, d))
        expert_in, slot, gate, aux = _local_dispatch(xs, router_w, e, k, cap)
        # experts <-> tokens exchange (the GShard all-to-all)
        ei = jax.lax.all_to_all(expert_in, "model", split_axis=0,
                                concat_axis=1, tiled=True)   # (e/M, M*cap, d)
        # FSDP-gather this layer's expert weights (d is the sharded dim)
        wi_g = jax.lax.all_gather(wi, data_ax, axis=1, tiled=True)
        wg_g = jax.lax.all_gather(wg, data_ax, axis=1, tiled=True)
        wo_g = jax.lax.all_gather(wo, data_ax, axis=2, tiled=True)
        h = jnp.einsum("ecd,edf->ecf", ei, wi_g)
        g = jnp.einsum("ecd,edf->ecf", ei, wg_g)
        h = jax.nn.silu(g) * h
        if ff_mask is not None:
            h = h * ff_mask.astype(h.dtype)
        eo = jnp.einsum("ecf,efd->ecd", h, wo_g)             # (e/M, M*cap, d)
        eo = jax.lax.all_to_all(eo, "model", split_axis=1,
                                concat_axis=0, tiled=True)   # (e, cap, d)
        out_flat = eo.reshape(e * cap, d)
        out_flat = jnp.concatenate(
            [out_flat, jnp.zeros((1, d), out_flat.dtype)])
        slot_tk = slot.reshape(t_slice, k)
        ys = jnp.zeros((t_slice, d), x2.dtype)
        for j in range(k):
            ys = ys + (out_flat[slot_tk[:, j]]
                       * gate[:, j, None].astype(x2.dtype))
        # reassemble the full local token range: each column contributes its
        # slice; psum over 'model' both combines and restores invariance.
        y2 = jnp.zeros((t_pad, d), x2.dtype)
        y2 = jax.lax.dynamic_update_slice(y2, ys, (col * t_slice, 0))
        y2 = jax.lax.psum(y2, "model")
        aux = jax.lax.pmean(aux, data_ax + ("model",))
        return y2[: bl * sl].reshape(bl, sl, d), aux

    fsdp = data_ax if len(data_ax) > 1 else data_ax[0]
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(data_ax, None, None), P(None, None),
                  P("model", fsdp, None), P("model", fsdp, None),
                  P("model", None, fsdp)),
        out_specs=(P(data_ax, None, None), P()),
    )(x, p["router"]["w"], experts["wi"], experts["wg"], experts["wo"])

    if "shared" in p:
        b_, s_, _ = x.shape
        y = y + mlp(p["shared"], x.reshape(b_ * s_, d)).reshape(b_, s_, d)
    return y, aux
