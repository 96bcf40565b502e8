"""Weights and SHiRA packs of the MLA + MoE block, made on the device.

The benchmark's generator for configurations of the DeepSeek-V2 block
(``configs/deepseek-v2-lite-*.json``): the program's moe-family tree,
stage 0 the leading dense layers and stage 1 the MoE layers, with the
chip's held experts stacked (held, n, m) in each MoE layer. As in
``weights.py``, whose helpers it uses, every value depends only on the
seed, the leaf's path and the layer (and, in a pack, the adapter and the
expert), so the reference (``reference_mla_moe.py``) makes the same
numbers again one layer at a time.

A pack gives every (layer, expert) matrix its own budget: the leaf's
nonzeros are drawn per matrix, one from each of K equal stretches.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

import weights as W

DENSE, MOE = "stages/0/", "stages/1/"


def layer_kinds(cfg: dict) -> List[Tuple[str, int]]:
    """(stage prefix, index in the stage) of every layer, in order."""
    fd = cfg["first_k_dense_replace"]
    return ([(DENSE, i) for i in range(fd)]
            + [(MOE, i) for i in range(cfg["num_hidden_layers"] - fd)])


def leaves(cfg: dict) -> List[W.Leaf]:
    d, H, V = cfg["hidden_size"], cfg["num_attention_heads"], \
        W.padded_vocab(cfg)
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r, E, ff = cfg["kv_lora_rank"], cfg["n_routed_experts"], \
        cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * ff
    attn = [("attn_norm/scale", (d,), "scale"),
            ("attn/wq", (d, H * (nope + rope)), "glorot"),
            ("attn/w_dkv", (d, r + rope), "glorot"),
            ("attn/kv_norm/scale", (r,), "scale"),
            ("attn/w_uk", (r, H * nope), "glorot"),
            ("attn/w_uv", (r, H * vd), "glorot"),
            ("attn/wo", (H * vd, d), "glorot"),
            ("mlp_norm/scale", (d,), "scale")]
    dense = [("mlp/w_up", (d, cfg["intermediate_size"]), "glorot"),
             ("mlp/w_gate", (d, cfg["intermediate_size"]), "glorot"),
             ("mlp/w_down", (cfg["intermediate_size"], d), "glorot")]
    moe = [("moe/w_router", (d, cfg["router_experts"]), "glorot"),
           ("moe/experts_w_up", (E, d, ff), "glorot"),
           ("moe/experts_w_gate", (E, d, ff), "glorot"),
           ("moe/experts_w_down", (E, ff, d), "glorot"),
           ("moe/shared/w_up", (d, shared), "glorot"),
           ("moe/shared/w_gate", (d, shared), "glorot"),
           ("moe/shared/w_down", (shared, d), "glorot")]
    out = [W.Leaf("embed/emb", (V, d), "embed", False),
           W.Leaf("final_norm/scale", (d,), "scale", False),
           W.Leaf("unembed/lm_head", (d, V), "embed", False)]
    out += [W.Leaf(DENSE + p, s, i, True) for p, s, i in attn + dense]
    out += [W.Leaf(MOE + p, s, i, True) for p, s, i in attn + moe]
    return out


def stage_layers(cfg: dict, prefix: str) -> int:
    return sum(1 for p, _ in layer_kinds(cfg) if p == prefix)


def make_params(cfg: dict, seed: int) -> dict:
    """The whole f32 parameter tree, in one jitted call on the device."""
    key = W.root_key(seed, W.WEIGHTS_SALT)
    lv = leaves(cfg)
    n = {DENSE: stage_layers(cfg, DENSE), MOE: stage_layers(cfg, MOE)}

    def build(key):
        flat = {}
        for leaf in lv:
            if leaf.stacked:
                L = n[leaf.path[:len(DENSE)]]
                flat[leaf.path] = jax.vmap(
                    lambda l, leaf=leaf: W.leaf_value(key, leaf, l))(
                        jnp.arange(L))
            else:
                flat[leaf.path] = W.leaf_value(key, leaf, 0)
        return flat

    return W.nest(jax.jit(build)(key))


def layer_weights(cfg: dict, seed: int, prefix: str,
                  layer: int) -> Dict[str, jax.Array]:
    """One layer of one stage, keyed by its path below the stage."""
    key = W.root_key(seed, W.WEIGHTS_SALT)
    lv = [leaf for leaf in leaves(cfg) if leaf.path.startswith(prefix)]
    fn = W._jitted(("mla_moe_layer", prefix, W._cfg_key(cfg)),
                   lambda: jax.jit(lambda key, l: {
                       leaf.path[len(prefix):]: W.leaf_value(key, leaf, l)
                       for leaf in lv}))
    return fn(key, layer)


def global_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    key = W.root_key(seed, W.WEIGHTS_SALT)
    lv = [leaf for leaf in leaves(cfg) if not leaf.stacked]
    fn = W._jitted(("mla_moe_globals", W._cfg_key(cfg)), lambda: jax.jit(
        lambda key: {leaf.path: W.leaf_value(key, leaf, 0) for leaf in lv}))
    return fn(key)


# ---------------------------------------------------------------------------
# SHiRA packs
# ---------------------------------------------------------------------------

def pack_leaves(cfg: dict, targets) -> List[W.Leaf]:
    return [leaf for leaf in leaves(cfg)
            if leaf.stacked and len(leaf.shape) >= 2
            and leaf.name in targets]


def nnz(leaf: W.Leaf, density: float) -> int:
    n, m = leaf.shape[-2:]
    return max(1, int(round(density * n * m)))


def pack_layer(key, adapter, leaf: W.Leaf, layer, density: float,
               std: float) -> Tuple[jax.Array, jax.Array]:
    """One adapter's nonzeros in one layer of one leaf: (K,) flat indices
    into (n, m) and (K,) values, or (E, K) for an expert stack, each
    expert's matrix drawn with its own key."""
    n, m = leaf.shape[-2:]
    K = nnz(leaf, density)
    stride = (n * m) // K
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, adapter), W._path_id(leaf.path)), layer)

    def matrix(k):
        k_idx, k_val = jax.random.split(k)
        idx = (jnp.arange(K, dtype=jnp.int32) * stride
               + jax.random.randint(k_idx, (K,), 0, stride, jnp.int32))
        return idx, std * jax.random.normal(k_val, (K,), jnp.float32)

    if len(leaf.shape) == 2:
        return matrix(k)
    return jax.vmap(lambda e: matrix(jax.random.fold_in(k, e)))(
        jnp.arange(leaf.shape[0]))


def make_pack(cfg: dict, seed: int, adapter: int, density: float,
              std: float, targets) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """One adapter: program path -> (idx, vals), (L, K) or (L, E, K), every
    leaf made by one jitted call, vmapped over each leaf's layers."""
    key = W.root_key(seed, W.PACKS_SALT)
    lv = pack_leaves(cfg, targets)
    n = {leaf.path: stage_layers(cfg, leaf.path[:len(DENSE)]) for leaf in lv}
    fn = W._jitted(("mla_moe_pack", W._cfg_key(cfg), density, std,
                    tuple(targets)),
                   lambda: jax.jit(lambda key, a: {
                       leaf.path: jax.vmap(lambda l, leaf=leaf: pack_layer(
                           key, a, leaf, l, density, std))(
                               jnp.arange(n[leaf.path]))
                       for leaf in lv}))
    return fn(key, adapter)


def pack_for_layer(cfg: dict, seed: int, adapter: int, density: float,
                   std: float, targets, prefix: str, layer: int):
    """One adapter's nonzeros in one layer of one stage: path below the
    stage -> (idx, vals). The reference's copy of ``make_pack``."""
    key = W.root_key(seed, W.PACKS_SALT)
    lv = [leaf for leaf in pack_leaves(cfg, targets)
          if leaf.path.startswith(prefix)]
    fn = W._jitted(("mla_moe_pack1", W._cfg_key(cfg), prefix, density, std,
                    tuple(targets)),
                   lambda: jax.jit(lambda key, a, l: {
                       leaf.path[len(prefix):]: pack_layer(key, a, leaf, l,
                                                           density, std)
                       for leaf in lv}))
    return fn(key, adapter, layer)
