"""Weights and SHiRA packs made on the device from the run's seed.

This is the benchmark's own generator. The program is handed the tree it
makes; the reference (``reference.py``) makes the same numbers again, one
layer at a time, from the same seed. Every value depends only on the seed,
the leaf's path and the layer, so a whole stacked leaf (one jitted call,
vmapped over layers) and a single layer of it hold the same bits.

A configuration file gives the sizes (``d_model``, ``num_heads``,
``num_kv_heads``, ``head_dim``, ``d_ff``, ``vocab_size``, ``num_layers``),
``act`` ("gelu" is a plain MLP, "silu" a gated one) and ``qkv_bias``. The
tree is laid out as the program's dense decoder expects it; the driver
checks the layout against the program before it serves.
"""
from __future__ import annotations

import json
import math
import zlib
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

WEIGHTS_SALT = 0x5EED
PACKS_SALT = 0xAD4B
STACK = "stages/0/"          # the program's one stage of stacked layers


@dataclass(frozen=True)
class Leaf:
    path: str                 # program tree path, "/"-joined
    shape: Tuple[int, ...]    # one layer's shape (stacked leaves add L)
    init: str                 # glorot | embed | bias | scale
    stacked: bool

    @property
    def name(self) -> str:
        return self.path.rsplit("/", 1)[-1]


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def leaves(cfg: dict) -> List[Leaf]:
    d, H, KV = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd, ff, V = cfg["head_dim"], cfg["d_ff"], padded_vocab(cfg)
    out = [Leaf("embed/emb", (V, d), "embed", False),
           Leaf("final_norm/scale", (d,), "scale", False),
           Leaf("unembed/lm_head", (d, V), "embed", False)]
    layer = [("attn_norm/scale", (d,), "scale"),
             ("attn/wq", (d, H * hd), "glorot"),
             ("attn/wk", (d, KV * hd), "glorot"),
             ("attn/wv", (d, KV * hd), "glorot"),
             ("attn/wo", (H * hd, d), "glorot")]
    if cfg["qkv_bias"]:
        layer += [("attn/bq", (H * hd,), "bias"),
                  ("attn/bk", (KV * hd,), "bias"),
                  ("attn/bv", (KV * hd,), "bias")]
    layer += [("mlp_norm/scale", (d,), "scale"),
              ("mlp/w_up", (d, ff), "glorot"),
              ("mlp/w_down", (ff, d), "glorot")]
    if cfg["act"] == "silu":
        layer.append(("mlp/w_gate", (d, ff), "glorot"))
    out += [Leaf(STACK + p, s, i, True) for p, s, i in layer]
    return out


def root_key(seed: int, salt: int) -> jax.Array:
    a, b = np.random.SeedSequence([int(seed), salt]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a)), int(b))


def _path_id(path: str) -> int:
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


def leaf_value(key, leaf: Leaf, layer) -> jax.Array:
    k = jax.random.fold_in(jax.random.fold_in(key, _path_id(leaf.path)),
                           layer)
    z = jax.random.normal(k, leaf.shape, jnp.float32)
    if leaf.init == "glorot":
        return z * math.sqrt(2.0 / (leaf.shape[-2] + leaf.shape[-1]))
    if leaf.init in ("embed", "bias"):
        return z * 0.02
    if leaf.init == "scale":
        return 1.0 + 0.1 * z
    raise ValueError(leaf.init)


def nest(flat: Dict[str, jax.Array]) -> dict:
    """{"a/0/b": x} -> {"a": [{"b": x}]} (numeric parts are list slots)."""
    tree: dict = {}
    for path, x in flat.items():
        *head, last = path.split("/")
        node = tree
        for p, nxt in zip(head, head[1:] + [last]):
            fresh = [] if nxt.isdigit() else {}
            if isinstance(node, list):
                i = int(p)
                node.extend([None] * (i + 1 - len(node)))
                if node[i] is None:
                    node[i] = fresh
                node = node[i]
            else:
                node = node.setdefault(p, fresh)
        node[last] = x
    return tree


def make_params(cfg: dict, seed: int) -> dict:
    """The whole f32 parameter tree, in one jitted call on the device."""
    key = root_key(seed, WEIGHTS_SALT)
    L = cfg["num_layers"]
    lv = leaves(cfg)

    def build(key):
        flat = {}
        for leaf in lv:
            if leaf.stacked:
                flat[leaf.path] = jax.vmap(
                    lambda l, leaf=leaf: leaf_value(key, leaf, l))(
                        jnp.arange(L))
            else:
                flat[leaf.path] = leaf_value(key, leaf, 0)
        return flat

    return nest(jax.jit(build)(key))


def layer_weights(cfg: dict, seed: int, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves, keyed by their path below the stack."""
    key = root_key(seed, WEIGHTS_SALT)
    lv = [leaf for leaf in leaves(cfg) if leaf.stacked]
    fn = _jitted(("layer", _cfg_key(cfg)), lambda: jax.jit(
        lambda key, l: {leaf.path[len(STACK):]: leaf_value(key, leaf, l)
                        for leaf in lv}))
    return fn(key, layer)


def global_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    key = root_key(seed, WEIGHTS_SALT)
    lv = [leaf for leaf in leaves(cfg) if not leaf.stacked]
    fn = _jitted(("globals", _cfg_key(cfg)), lambda: jax.jit(
        lambda key: {leaf.path: leaf_value(key, leaf, 0) for leaf in lv}))
    return fn(key)


# ---------------------------------------------------------------------------
# SHiRA packs
# ---------------------------------------------------------------------------

def pack_leaves(cfg: dict, targets) -> List[Leaf]:
    return [leaf for leaf in leaves(cfg)
            if leaf.stacked and len(leaf.shape) == 2 and leaf.name in targets]


def nnz(leaf: Leaf, density: float) -> int:
    n, m = leaf.shape
    return max(1, int(round(density * n * m)))


def pack_layer(key, adapter, leaf: Leaf, layer, density: float,
               std: float) -> Tuple[jax.Array, jax.Array]:
    """One adapter's nonzeros in one layer of one matrix: K unique flat
    indices into (n, m), one drawn uniformly from each of K equal
    stretches of the matrix (so each entry is picked with probability
    ``density``), and K values of standard deviation ``std``."""
    n, m = leaf.shape
    K = nnz(leaf, density)
    stride = (n * m) // K
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, adapter), _path_id(leaf.path)), layer)
    k_idx, k_val = jax.random.split(k)
    idx = (jnp.arange(K, dtype=jnp.int32) * stride
           + jax.random.randint(k_idx, (K,), 0, stride, jnp.int32))
    return idx, std * jax.random.normal(k_val, (K,), jnp.float32)


def make_pack(cfg: dict, seed: int, adapter: int, density: float,
              std: float, targets) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """One adapter: program path -> (idx (L, K) int32, vals (L, K) f32),
    each leaf made by one jitted call vmapped over the layers."""
    key = root_key(seed, PACKS_SALT)
    L = cfg["num_layers"]
    out = {}
    for leaf in pack_leaves(cfg, targets):
        fn = _jitted(("pack", leaf, L, density, std), lambda leaf=leaf: jax.jit(
            lambda key, a: jax.vmap(lambda l: pack_layer(
                key, a, leaf, l, density, std))(jnp.arange(L))))
        out[leaf.path] = fn(key, adapter)
    return out


def pack_for_layer(cfg: dict, seed: int, adapter: int, density: float,
                   std: float, targets, layer: int):
    """One adapter's nonzeros in one layer: path below the stack ->
    (idx (K,), vals (K,)). The reference's copy of ``make_pack``."""
    key = root_key(seed, PACKS_SALT)
    out = {}
    for leaf in pack_leaves(cfg, targets):
        fn = _jitted(("pack1", leaf, density, std), lambda leaf=leaf: jax.jit(
            lambda key, a, l: pack_layer(key, a, leaf, l, density, std)))
        out[leaf.path[len(STACK):]] = fn(key, adapter, layer)
    return out


_JITS: dict = {}


def _jitted(key, make):
    if key not in _JITS:
        _JITS[key] = make()
    return _JITS[key]


def _cfg_key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)
