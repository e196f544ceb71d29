"""Mixture-of-Experts FFN (arctic-480b, qwen2-moe).

Expert-parallel implementation:
  * router softmax -> top-k experts per token + gates (plain jit math),
  * capacity C per expert with GShard-style dropping,
  * dispatch/expert/combine under an explicit ``jax.shard_map`` when a mesh is
    active (§Perf A2): every (data, model) device scatters ITS batch-local
    tokens into a dense buffer for ITS model-local experts, runs the expert
    matmuls, gathers back, and the ONLY cross-device collective is a psum of
    the combined (T_local, D) output over the model axis.  Leaving the
    scatter/gather to the SPMD partitioner instead makes it replicate the full
    token tensor and all-reduce dense buffers (measured 23 TB/device/step on
    arctic-480b train_4k vs ~0.3 TB with this path — EXPERIMENTS.md §Perf).
  * experts that don't divide the model axis (qwen2's 60) are zero-padded to
    the next multiple; the router never selects the dead experts.
  * smoke tests / single-device runs use the same math without shard_map.

Aux losses: load-balance (Switch) + router z-loss, returned for logging.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import _init, mlp_apply, init_mlp
from .sharding import constrain, current_rules, _mesh_sizes
from jax import shard_map
from jax.sharding import get_abstract_mesh


def init_moe(key, cfg):
    ks = jax.random.split(key, 5)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    width = 2 * F if cfg.activation in ("swiglu", "geglu") else F
    p = {
        "router": _init(ks[0], (D, E), scale=0.02),
        "w_in_e": _init(ks[1], (E, D, width)),
        "w_out_e": _init(ks[2], (E, F, D)),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[3], D, cfg.shared_d_ff, cfg.activation)
    if cfg.moe_dense_residual:
        p["dense_res"] = init_mlp(ks[4], D, cfg.d_ff, cfg.activation)
    return p


def _routed_local(xt, expert_idx, gate_vals, w_in, w_out, cfg, e_offset, e_total):
    """Single-device dispatch/expert/combine over a LOCAL expert slab.

    xt: (T, D); expert_idx/gate_vals: (T, K) GLOBAL expert ids; w_in/w_out:
    (E_loc, ...) local expert weights; e_offset: first global id of the slab.
    Tokens routed to other slabs contribute zero (psum over the model axis
    restores the full combine).  Returns (combined (T, D), keep (T, K))."""
    T, D = xt.shape
    E_loc = w_in.shape[0]
    K = expert_idx.shape[1]
    # capacity budget per expert uses the GLOBAL expert count: this shard's
    # tokens spread over all e_total experts, of which E_loc live here
    capacity = int(max(1, round(T * K * cfg.capacity_factor / max(e_total, 1))))
    capacity = min(-(-capacity // 8) * 8, max(T, 8))

    flat_e = expert_idx.reshape(-1)  # (T*K,) global ids
    local_e = flat_e - e_offset
    mine = (local_e >= 0) & (local_e < E_loc)
    safe_e = jnp.where(mine, local_e, 0)
    # position within the LOCAL expert buffer (cumsum over this shard's tokens)
    onehot = jax.nn.one_hot(safe_e, E_loc, dtype=jnp.int32) * mine[:, None].astype(jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, safe_e[:, None], 1)[:, 0]
    keep = mine & (pos < capacity)
    safe_pos = jnp.where(keep, pos, capacity - 1)

    tok_of_choice = jnp.repeat(jnp.arange(T), K)
    contrib = jnp.where(keep[:, None], xt[tok_of_choice], 0.0)
    buf = jnp.zeros((E_loc, capacity, D), xt.dtype).at[safe_e, safe_pos].add(contrib)

    width_gated = cfg.activation in ("swiglu", "geglu")
    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    if width_gated:
        g, u = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(g) * u if cfg.activation == "swiglu" else jax.nn.gelu(g) * u
    else:
        h = jax.nn.gelu(h)
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_out)

    gathered = jnp.where(keep[:, None], out_buf[safe_e, safe_pos], 0.0)
    gates = gate_vals.reshape(-1)[:, None].astype(gathered.dtype)
    combined = (gathered * gates).reshape(T, K, D).sum(axis=1)
    return combined, keep.reshape(T, K)


def _pad_experts(w, n_pad):
    if n_pad == 0:
        return w
    return jnp.concatenate([w, jnp.zeros((n_pad,) + w.shape[1:], w.dtype)], axis=0)


def moe_apply(params, x, cfg):
    """x: (B, S, D) -> (out (B,S,D), aux dict)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, D)
    logits = (xt @ params["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    gate_vals = gate_vals.astype(xt.dtype)

    rules = current_rules()
    sizes = _mesh_sizes() or {}
    model_ax = rules.get("tensor")
    batch_ax = rules.get("batch")
    n_model = sizes.get(model_ax, 1) if isinstance(model_ax, str) else 1

    if rules and n_model > 1 and batch_ax is not None and T % _axes_size(batch_ax, sizes) == 0:
        # §Perf A2: explicit expert-parallel shard_map (see module docstring)
        n_pad = (-E) % n_model
        w_in = _pad_experts(params["w_in_e"], n_pad)
        w_out = _pad_experts(params["w_out_e"], n_pad)
        E_loc = (E + n_pad) // n_model
        mesh = get_abstract_mesh()

        def body(xt_l, ei_l, gv_l, w_in_l, w_out_l):
            off = jax.lax.axis_index(model_ax) * E_loc
            combined, keep = _routed_local(xt_l, ei_l, gv_l, w_in_l, w_out_l, cfg, off, E + n_pad)
            combined = jax.lax.psum(combined, model_ax)
            keep = jax.lax.psum(keep.astype(jnp.int32), model_ax)
            return combined, keep

        combined, keep_ct = shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(batch_ax, None), P(batch_ax, None), P(batch_ax, None),
                P(model_ax, None, None), P(model_ax, None, None),
            ),
            out_specs=(P(batch_ax, None), P(batch_ax, None)),
            check_vma=False,
        )(xt, expert_idx, gate_vals, w_in, w_out)
        keep = keep_ct > 0
        flat_e = expert_idx.reshape(-1)
    else:
        combined, keep = _routed_local(
            xt, expert_idx, gate_vals, params["w_in_e"], params["w_out_e"], cfg, 0, E)
        flat_e = expert_idx.reshape(-1)

    if "shared" in params:
        combined = combined + mlp_apply(params["shared"], xt, cfg.activation)
    if "dense_res" in params:
        combined = combined + mlp_apply(params["dense_res"], xt, cfg.activation)

    # aux losses
    me = probs.mean(axis=0)  # mean router prob per expert
    ce = jnp.zeros((E,)).at[flat_e].add(
        keep.reshape(-1).astype(jnp.float32)) / jnp.maximum(keep.sum(), 1.0)
    aux = {
        "load_balance": E * jnp.sum(me * ce),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "drop_frac": 1.0 - keep.astype(jnp.float32).mean(),
    }
    return combined.reshape(B, S, D), aux


def _axes_size(ax, sizes):
    axes = ax if isinstance(ax, tuple) else (ax,)
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return n
