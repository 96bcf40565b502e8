"""Mixture-of-Experts FFN with capacity-based token dispatch.

Dispatch uses the gather/scatter ("dropping") formulation: tokens are routed
top-k, assigned a position inside their expert's capacity buffer via a cumsum
over the flattened routing order, scattered into an (E, C, d) buffer, processed
by a batched expert SwiGLU, and combined back weighted by the (renormalised)
router probabilities. Experts are sharded over the ``model`` mesh axis
(expert parallelism); the scatter/gather turn into all-to-alls under SPMD.

Shared experts (DeepSeek-V2 style) are fused into a single dense SwiGLU with
hidden dim ``num_shared * d_ff`` applied to every token.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import compute_dtype, dense, glorot, init_mlp, mlp


def init_moe(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, E, ff = cfg.d_model, m.num_experts, m.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "w_router": glorot(ks[0], (d, E)),
        "experts_w_up": glorot(ks[1], (E, d, ff), in_axis=-2, out_axis=-1),
        "experts_w_gate": glorot(ks[2], (E, d, ff), in_axis=-2, out_axis=-1),
        "experts_w_down": glorot(ks[3], (E, ff, d), in_axis=-2, out_axis=-1),
    }
    if m.num_shared:
        p["shared"] = init_mlp(ks[4], d, m.num_shared * ff, act="silu")
    return p


def _expert_ffn(p, buf):
    """buf: (E, C, d) -> (E, C, d), batched SwiGLU over experts."""
    up = jnp.einsum("ecd,edf->ecf", buf.astype(compute_dtype()),
                    p["experts_w_up"].astype(compute_dtype()),
                    preferred_element_type=jnp.float32)
    gate = jnp.einsum("ecd,edf->ecf", buf.astype(compute_dtype()),
                      p["experts_w_gate"].astype(compute_dtype()),
                      preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up).astype(compute_dtype())
    out = jnp.einsum("ecf,efd->ecd", h,
                     p["experts_w_down"].astype(compute_dtype()),
                     preferred_element_type=compute_dtype())
    return out


def moe_ffn(params: dict, cfg: ModelConfig, x: jax.Array,
            deterministic: bool = True) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d). Returns (y, aux_load_balance_loss).

    Under a mesh (launch layer installs the "moe_ep_mesh" hint) dispatch runs
    as explicit expert parallelism via shard_map: activations are already
    replicated across ``model`` for the TP matmuls, so every model rank
    routes its data-shard's tokens to its LOCAL experts and one psum merges
    the partial outputs — no token all-to-all, no (E, C, d) resharding. This
    replaced two GSPMD-chosen formulations that cost 2.5-3.5 TB/step of
    collectives on deepseek-v2-lite train_4k (see EXPERIMENTS §Perf)."""
    from repro.launch.actctx import _SPECS, shard_as

    ep = _SPECS.get("moe_ep_mesh")
    if ep is not None and cfg.moe.num_experts % ep[1] == 0:
        return _moe_ffn_ep(params, cfg, x, ep[0])
    return _moe_ffn_dense(params, cfg, x)


def _moe_ffn_dense(params: dict, cfg: ModelConfig,
                   x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single-program dispatch (CPU tests / decode / meshless runs)."""
    from repro.launch.actctx import shard_as

    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    k, E = m.top_k, m.num_experts
    xf = x.reshape(T, d)

    logits = dense(xf, params["w_router"]).astype(jnp.float32)      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                          # (T, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    # Load-balancing aux loss (Switch-style): E * sum_e f_e * P_e.
    me = jnp.mean(probs, axis=0)                                    # (E,)
    onehot_k = jax.nn.one_hot(top_i, E, dtype=jnp.float32)          # (T,k,E)
    ce = jnp.mean(jnp.sum(onehot_k, axis=1), axis=0) / k            # (E,)
    aux = E * jnp.sum(me * ce)

    # Capacity floor: for small token counts (decode steps, smoke tests) give
    # every expert room for all tokens so routing is drop-free and decode is
    # consistent with prefill; for large T the capacity factor governs.
    capacity = int(max(round(m.capacity_factor * T * k / E), min(T, 512)))

    # --- dispatch: one scatter of (T, d) per routing choice ---
    buf = jnp.zeros((E, capacity + 1, d), compute_dtype())            # +trash lane
    buf = shard_as(buf, "moe_buf")
    counts = jnp.zeros((E,), jnp.int32)
    slots = []
    xc = xf.astype(compute_dtype())
    for j in range(k):
        e_j = top_i[:, j]                                           # (T,)
        oh = jax.nn.one_hot(e_j, E, dtype=jnp.int32)                # (T, E)
        pos_in = jnp.cumsum(oh, axis=0) - oh                        # before me
        pos = jnp.take_along_axis(pos_in, e_j[:, None], axis=1)[:, 0] \
            + counts[e_j]
        counts = counts + jnp.sum(oh, axis=0)
        slot = jnp.where(pos < capacity, pos, capacity)
        slots.append(slot)
        buf = buf.at[e_j, slot].add(xc, mode="drop")
        buf = shard_as(buf, "moe_buf")

    out_buf = _expert_ffn(params, buf[:, :capacity])                # (E,C,d)
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((E, 1, d), compute_dtype())], axis=1)
    out_buf = shard_as(out_buf, "moe_buf")

    # --- combine: gather each choice's slot, weight by router prob ---
    y = jnp.zeros((T, d), compute_dtype())
    for j in range(k):
        got = out_buf[top_i[:, j], slots[j]]                        # (T, d)
        w_j = (top_p[:, j] * (slots[j] < capacity)).astype(compute_dtype())
        y = y + got * w_j[:, None]

    if m.num_shared:
        y = y + mlp(params["shared"], xf, act="silu")
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map)
# ---------------------------------------------------------------------------

def _moe_ffn_ep(params: dict, cfg: ModelConfig, x: jax.Array,
                mesh) -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import axis_size, dp_axes

    m = cfg.moe
    B, S, d = x.shape
    k, E = m.top_k, m.num_experts
    ep_size = axis_size(mesh, "model")
    e_l = E // ep_size
    dp = dp_axes(mesh)
    import numpy as np
    dp_size = int(np.prod([axis_size(mesh, a) for a in dp]))
    b_spec = dp if (B % dp_size == 0 and B >= dp_size) else None
    t_l = (B // dp_size if b_spec else B) * S
    capacity = int(max(round(m.capacity_factor * t_l * k / E), min(t_l, 512)))

    def local_fn(w_router, w_up, w_gate, w_down, xl):
        # xl: (B_l, S, d) — replicated over `model`; w_*: (e_l, ...) local.
        bl = xl.shape[0]
        xf = xl.reshape(bl * S, d)
        logits = dense(xf, w_router).astype(jnp.float32)        # (T_l, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        oh_k = jax.nn.one_hot(top_i, E, dtype=jnp.float32)
        ce = jnp.mean(jnp.sum(oh_k, axis=1), axis=0) / k
        aux = jax.lax.pmean(E * jnp.sum(me * ce), dp)

        first = jax.lax.axis_index("model") * e_l
        xc = xf.astype(compute_dtype())
        buf = jnp.zeros((e_l, capacity + 1, d), compute_dtype())
        counts = jnp.zeros((e_l,), jnp.int32)
        slots, mines = [], []
        for j in range(k):
            le = top_i[:, j] - first                            # (T_l,)
            mine = (le >= 0) & (le < e_l)
            le = jnp.where(mine, le, 0)
            oh = jax.nn.one_hot(le, e_l, dtype=jnp.int32) \
                * mine[:, None].astype(jnp.int32)
            pos = jnp.take_along_axis(jnp.cumsum(oh, 0) - oh,
                                      le[:, None], axis=1)[:, 0] + counts[le]
            counts = counts + jnp.sum(oh, axis=0)
            slot = jnp.where(mine & (pos < capacity), pos, capacity)
            slots.append(slot)
            mines.append(mine)
            buf = buf.at[le, slot].add(
                xc * mine[:, None].astype(compute_dtype()), mode="drop")

        p_loc = {"experts_w_up": w_up, "experts_w_gate": w_gate,
                 "experts_w_down": w_down}
        out_buf = _expert_ffn(p_loc, buf[:, :capacity])
        out_buf = jnp.concatenate(
            [out_buf, jnp.zeros((e_l, 1, d), compute_dtype())], axis=1)

        y = jnp.zeros((bl * S, d), compute_dtype())
        for j in range(k):
            le = jnp.where(mines[j], top_i[:, j] - first, 0)
            got = out_buf[le, slots[j]]
            w_j = (top_p[:, j] * mines[j]
                   * (slots[j] < capacity)).astype(compute_dtype())
            y = y + got * w_j[:, None]
        y = jax.lax.psum(y, "model")          # merge expert-shard partials
        return y.reshape(bl, S, d), aux

    specs_in = (P(), P("model", None, None), P("model", None, None),
                P("model", None, None), P(b_spec, None, None))
    specs_out = (P(b_spec, None, None), P())
    y, aux = jax.shard_map(
        local_fn, mesh=mesh, in_specs=specs_in, out_specs=specs_out,
        check_vma=False,
    )(params["w_router"], params["experts_w_up"], params["experts_w_gate"],
      params["experts_w_down"], x)

    if m.num_shared:
        xf = x.reshape(B * S, d)
        y = y + mlp(params["shared"], xf, act="silu").reshape(B, S, d)
    return y, aux
