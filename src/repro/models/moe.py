"""Mixture-of-Experts FFN: one dropless expert layer for serving and
training.

Routing is a softmax over all ``num_experts`` router outputs, top-k, with
the top-k weights renormalised only where the config says so
(``norm_topk_prob``); the routed sum is not scaled. A chip holds
``MoEConfig.held`` routed experts from ``first_held`` on (all of them by
default); its expert leaves are (held, ...).

``routed_local`` computes the held experts' part: the choices that fall on
held experts, sorted by expert, through one grouped matmul
(``kernels/moe_gmm.py``), with no capacity and no drops. Serving
(``moe_serve``: prefill, decode and paged chunk prefill) and training
(``moe_ffn``, which also returns the Switch-style load-balancing aux loss)
both run it. Under a mesh (the launch layer installs the "moe_ep_mesh"
hint) the same function runs per expert shard inside ``shard_map``, its
held range taken from the shard's ``axis_index``, and one psum merges the
partials.

Serving reads the expert leaves of a layer scan whole: the scan passes the
(L, held, ...) stacks with the layer's index under ``"layer"``, and the
kernel reads that layer's blocks in place of a copied slice.

Shared experts (DeepSeek-V2 style) are fused into a single dense SwiGLU with
hidden dim ``num_shared * d_ff`` applied to every token, once.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import trace
from repro.configs.base import ModelConfig
from repro.kernels.moe_gmm import moe_gmm
from repro.models.layers import compute_dtype, glorot, init_mlp, mlp


def init_moe(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, E, ff = cfg.d_model, m.held, m.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "w_router": glorot(ks[0], (d, m.num_experts)),
        "experts_w_up": glorot(ks[1], (E, d, ff), in_axis=-2, out_axis=-1),
        "experts_w_gate": glorot(ks[2], (E, d, ff), in_axis=-2, out_axis=-1),
        "experts_w_down": glorot(ks[3], (E, ff, d), in_axis=-2, out_axis=-1),
    }
    if m.num_shared:
        p["shared"] = init_mlp(ks[4], d, m.num_shared * ff, act="silu")
    return p


def route(params: dict, cfg: ModelConfig, xf: jax.Array):
    """xf (T, d) -> (weights (T, k) f32, experts (T, k) int32, probs (T, E)).
    The router runs in float32, as DeepSeek-V2's gate does."""
    m = cfg.moe
    logits = jnp.dot(xf.astype(jnp.float32),
                     params["w_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    return top_p, top_i, probs


def _tiles(T: int, k: int, held: int) -> Tuple[int, int]:
    """(tm, R): the grouped matmul's row tile, sized to the mean held
    expert's share of choices, and the tiles of the worst case (every
    choice a token can send to held experts, each run padded to a tile)."""
    most = T * min(k, held)
    tm = 16
    while tm < 256 and tm < -(-most // held):
        tm *= 2
    return tm, -(-most // tm) + held


def routed_local(params: dict, cfg: ModelConfig, xf: jax.Array, first,
                 top_p, top_i) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed sum, dropless.

    xf (T, d); ``first`` the first held expert (int or traced); top_p/top_i
    (T, k) the routing. The expert leaves are (held, ...), or (L, held, ...)
    stacks read at ``params["layer"]``. Returns (y (T, d) f32, counts
    (held,) int32: the token-choices routed to each held expert)."""
    T, d = xf.shape
    k = top_i.shape[1]
    layer = params.get("layer")
    held = params["experts_w_up"].shape[0 if layer is None else 1]
    loc = top_i - first
    mine = (loc >= 0) & (loc < held)
    key = jnp.where(mine, loc, held).reshape(-1)                 # (T*k,)
    onehot = jax.nn.one_hot(key, held + 1, dtype=jnp.int32)
    counts = jnp.sum(onehot, axis=0)[:held]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    tm, R = _tiles(T, k, held)
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    row0 = jnp.concatenate([tile_end - tiles, jnp.zeros((1,), jnp.int32)]) * tm
    dest = jnp.where(key < held, row0[key] + rank, R * tm)        # (T*k,)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(R), side="right"), held - 1)
    # token of each row; padding rows read the appended zero row
    src = jnp.full((R * tm,), T, jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop")
    xz = jnp.concatenate([xf.astype(compute_dtype()),
                          jnp.zeros((1, d), compute_dtype())])
    n_tiles = tile_end[-1]
    h = moe_gmm(xz[src], (params["experts_w_gate"], params["experts_w_up"]),
                tile_expert, n_tiles, tm=tm, layer=layer,
                out_dtype=compute_dtype(), compute_dtype=compute_dtype())
    out = moe_gmm(h, (params["experts_w_down"],), tile_expert, n_tiles,
                  tm=tm, layer=layer, compute_dtype=compute_dtype())  # f32
    got = out[jnp.minimum(dest, R * tm - 1)].reshape(T, k, d)
    w = jnp.where(mine, top_p, 0.0)[..., None]
    y = jnp.sum(jnp.where(mine[..., None], got, 0.0) * w, axis=1)
    return y, counts


def moe_serve(params: dict, cfg: ModelConfig,
              x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The serving expert layer. x: (B, S, d). Returns (y, counts (held,)
    int32 token-choices routed to each held expert; all experts under a
    mesh)."""
    y, _, counts = _expert_layer(params, cfg, x)
    return y, counts


def moe_ffn(params: dict, cfg: ModelConfig, x: jax.Array,
            deterministic: bool = True) -> Tuple[jax.Array, jax.Array]:
    """The training expert layer. x: (B, S, d). Returns (y, aux_loss)."""
    y, aux, _ = _expert_layer(params, cfg, x)
    return y, aux


def _expert_layer(params: dict, cfg: ModelConfig, x: jax.Array):
    """(y, aux, counts). Under a mesh (launch layer installs the
    "moe_ep_mesh" hint) dispatch runs as explicit expert parallelism via
    shard_map: activations are already replicated across ``model`` for the
    TP matmuls, so every model rank routes its data-shard's tokens to its
    LOCAL experts (``routed_local``) and one psum merges the partial
    outputs — no token all-to-all, no (E, C, d) resharding. This replaced
    two GSPMD-chosen formulations that cost 2.5-3.5 TB/step of collectives
    on deepseek-v2-lite train_4k (see EXPERIMENTS §Perf)."""
    from repro.launch.actctx import _SPECS

    m = cfg.moe
    ep = _SPECS.get("moe_ep_mesh")
    if ep is not None and m.num_experts % ep[1] == 0:
        if "layer" in params:        # shard_map takes the layer's slices
            params = {k: v[params["layer"]] if k.startswith("experts_")
                      else v for k, v in params.items()}
        return _moe_ffn_ep(params, cfg, x, ep[0])
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    top_p, top_i, probs = route(params, cfg, xf)
    y, counts = routed_local(params, cfg, xf, m.first_held, top_p, top_i)
    y = y.astype(compute_dtype())
    if m.num_shared:
        y = y + mlp(params["shared"], xf, act="silu")
    return (y.reshape(B, S, d), _aux_loss(probs, top_i, m.num_experts,
                                          m.top_k), counts)


def record_routed(counts) -> np.ndarray:
    """Pull routed token-choice counts (moe layers, held), as
    ``lm.prefill`` / ``lm.decode_step`` return them (or a sum of them), to
    the host and record them as the tracer's counters: ``moe.routed_local``
    (all choices on held experts) and ``moe.routed_max`` (the largest
    load of one held expert in one layer)."""
    c = np.asarray(counts)
    trace.counter("moe.routed_local", float(c.sum()), cat="moe")
    trace.counter("moe.routed_max", float(c.max(initial=0)), cat="moe")
    return c


def _aux_loss(probs, top_i, E: int, k: int):
    """Switch-style load balancing: E * sum_e f_e * P_e."""
    me = jnp.mean(probs, axis=0)                                    # (E,)
    onehot_k = jax.nn.one_hot(top_i, E, dtype=jnp.float32)          # (T,k,E)
    ce = jnp.mean(jnp.sum(onehot_k, axis=1), axis=0) / k            # (E,)
    return E * jnp.sum(me * ce)


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map)
# ---------------------------------------------------------------------------

def _moe_ffn_ep(params: dict, cfg: ModelConfig, x: jax.Array, mesh):
    """Returns (y, aux, counts (E,) token-choices per expert)."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import axis_size, dp_axes

    m = cfg.moe
    B, S, d = x.shape
    k, E = m.top_k, m.num_experts
    e_l = E // axis_size(mesh, "model")
    dp = dp_axes(mesh)
    dp_size = int(np.prod([axis_size(mesh, a) for a in dp]))
    b_spec = dp if (B % dp_size == 0 and B >= dp_size) else None

    def local_fn(w_router, w_up, w_gate, w_down, xl):
        # xl: (B_l, S, d) — replicated over `model`; w_*: (e_l, ...) local.
        bl = xl.shape[0]
        xf = xl.reshape(bl * S, d)
        top_p, top_i, probs = route({"w_router": w_router}, cfg, xf)
        aux = jax.lax.pmean(_aux_loss(probs, top_i, E, k), dp)
        p_loc = {"experts_w_up": w_up, "experts_w_gate": w_gate,
                 "experts_w_down": w_down}
        y, counts = routed_local(p_loc, cfg, xf,
                                 jax.lax.axis_index("model") * e_l,
                                 top_p, top_i)
        y = jax.lax.psum(y.astype(compute_dtype()), "model")
        if b_spec:
            counts = jax.lax.psum(counts, dp)
        return y.reshape(bl, S, d), aux, counts

    specs_in = (P(), P("model", None, None), P("model", None, None),
                P("model", None, None), P(b_spec, None, None))
    specs_out = (P(b_spec, None, None), P(), P("model"))
    y, aux, counts = jax.shard_map(
        local_fn, mesh=mesh, in_specs=specs_in, out_specs=specs_out,
        check_vma=False,
    )(params["w_router"], params["experts_w_up"], params["experts_w_gate"],
      params["experts_w_down"], x)

    if m.num_shared:
        xf = x.reshape(B * S, d)
        y = y + mlp(params["shared"], xf, act="silu").reshape(B, S, d)
    return y, aux, counts
