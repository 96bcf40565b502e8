"""Adapter modules: SHiRA (the paper), LoRA, DoRA, and SHiRA-masked DoRA.

All adapters share one functional contract so the trainer and server are
adapter-agnostic:

  trainable, aux = init_adapter(key, base_params, acfg, calib_grads=None)
  params_eff     = materialize(base_params, trainable, aux, acfg, alpha)

``trainable`` is the pytree the optimizer sees (for SHiRA-packed: just the
(…, K) value vectors — this is exactly the paper's App. D memory win).
``aux`` holds non-trainable statics (packed indices, etc.).

Gradients flow from the loss through ``materialize`` into ``trainable`` by
ordinary autodiff: d(values) = gather(dW at indices) for SHiRA — the same
math as the paper's gradient-hook (App. C), obtained for free from the
scatter-add's transpose.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AdapterConfig
from repro.core import masks as M


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _lora_init(key, w, rank):
    *lead, n, m = w.shape
    ka, _ = jax.random.split(key)
    a = jax.random.normal(ka, tuple(lead) + (n, rank), jnp.float32) \
        * (1.0 / np.sqrt(n))
    b = jnp.zeros(tuple(lead) + (rank, m), jnp.float32)
    return {"A": a, "B": b}


def _col_norm(w):
    return jnp.sqrt(jnp.sum(jnp.square(w.astype(jnp.float32)),
                            axis=-2, keepdims=True) + 1e-12)


def init_adapter(key, params, acfg: AdapterConfig,
                 calib_grads=None) -> Tuple[Any, Any]:
    kind = acfg.kind
    if kind == "none":
        return None, None

    if kind == "shira":
        idx = M.make_packed_indices(params, acfg, key, calib_grads)
        values = jax.tree.map(
            lambda i: None if i is None else jnp.zeros(i.shape, jnp.float32),
            idx, is_leaf=lambda x: x is None)
        return values, {"indices": idx}

    if kind in ("lora", "dora", "shira-dora"):
        def per_leaf(path, w):
            sub = jax.random.fold_in(key, hash(M.path_str(path)) % (2 ** 31))
            p = _lora_init(sub, w, acfg.rank)
            if kind in ("dora", "shira-dora"):
                p["m"] = _col_norm(w)
            return p

        trainable = M.map_targets(per_leaf, params, acfg.target_modules)
        aux = None
        if kind == "shira-dora":
            aux = {"indices": M.make_packed_indices(params, acfg, key,
                                                    calib_grads)}
        return trainable, aux

    raise ValueError(f"unknown adapter kind {kind!r}")


# ---------------------------------------------------------------------------
# materialize
# ---------------------------------------------------------------------------

def _lora_delta(w, t, scale):
    return scale * jnp.einsum("...nr,...rm->...nm",
                              t["A"].astype(jnp.float32),
                              t["B"].astype(jnp.float32))


def _dora_weight(w, t, scale):
    v = w.astype(jnp.float32) + _lora_delta(w, t, scale)
    return t["m"] * v / _col_norm(v)


def materialize(params, trainable, aux, acfg: AdapterConfig,
                alpha: Optional[float] = None):
    """Return the effective parameter tree for forward passes."""
    if acfg.kind == "none" or trainable is None:
        return params
    a = acfg.alpha if alpha is None else alpha
    scale = acfg.lora_alpha / max(acfg.rank, 1)

    if acfg.kind == "shira":
        idx = aux["indices"]

        def leaf(w, i, v):
            if i is None:
                return w
            return M.scatter_packed_add(w, i, v, alpha=a).astype(w.dtype)

        return jax.tree.map(leaf, params, idx, trainable,
                            is_leaf=lambda x: x is None)

    if acfg.kind == "lora":
        def leaf(w, t):
            if t is None:
                return w
            return (w.astype(jnp.float32) + a * _lora_delta(w, t, scale)
                    ).astype(w.dtype)

        return jax.tree.map(leaf, params, trainable,
                            is_leaf=lambda x: x is None or isinstance(x, dict)
                            and "A" in x)

    if acfg.kind == "dora":
        def leaf(w, t):
            if t is None:
                return w
            wd = _dora_weight(w, t, scale)
            return (w.astype(jnp.float32) + a * (wd - w.astype(jnp.float32))
                    ).astype(w.dtype)

        return jax.tree.map(leaf, params, trainable,
                            is_leaf=lambda x: x is None or isinstance(x, dict)
                            and "A" in x)

    if acfg.kind == "shira-dora":
        idx = aux["indices"]

        def leaf(w, t, i):
            if t is None or i is None:
                return w
            delta = _dora_weight(w, t, scale) - w.astype(jnp.float32)
            dv = M.gather_packed(delta, i)          # keep only the masked 1%
            return M.scatter_packed_add(w, i, dv, alpha=a).astype(w.dtype)

        return jax.tree.map(leaf, params, trainable, idx,
                            is_leaf=lambda x: x is None or isinstance(x, dict)
                            and "A" in x)

    raise ValueError(acfg.kind)


# ---------------------------------------------------------------------------
# Shard-local materialize (multi-pod training path)
# ---------------------------------------------------------------------------

def materialize_sharded(params, values, indices, pspecs, mesh,
                        alpha: float = 1.0):
    """W_eff = W + alpha * scatter(values) with SHARD-LOCAL packed indices.

    ``indices``/``values`` leaves are (L, DPC, TPC, Ks): per (data, model)
    shard of the stacked weight, Ks flat indices into the LOCAL (n/DPC,
    m/TPC) tile. The scatter then runs inside shard_map with zero
    communication — and the value gradients are sharded exactly like the
    weights, so the only cross-replica gradient traffic left is the pod-axis
    all-reduce of the packed values (~1% of the dense sync; §Perf)."""
    import jax.numpy as jnp_

    def leaf(w, v, i, spec):
        if v is None or i is None:
            return w

        def local(wl, il, vl):
            L = wl.shape[0]
            il2 = il.reshape(L, -1)
            vl2 = vl.reshape(L, -1)
            return M.scatter_packed_add(wl, il2, vl2, alpha=alpha).astype(
                wl.dtype)

        from jax.sharding import PartitionSpec as P
        ispec = P(spec[0] if len(spec) > 0 else None,
                  spec[1] if len(spec) > 1 else None,
                  spec[2] if len(spec) > 2 else None, None)
        return jax.shard_map(local, mesh=mesh, in_specs=(spec, ispec, ispec),
                             out_specs=spec, check_vma=False)(w, i, v)

    return jax.tree.map(leaf, params, values, indices, pspecs,
                        is_leaf=lambda x: x is None)


# ---------------------------------------------------------------------------
# Packs — the serialized sparse adapter of Fig. 3(a)
# ---------------------------------------------------------------------------

@dataclass
class AdapterPack:
    """Sparse weights + indices, per target path. Model-size comparable to a
    LoRA but overwrites only 1-2% of entries when loaded."""

    name: str
    entries: Dict[str, Tuple[jax.Array, jax.Array]]  # path -> (idx, val)
    alpha: float = 1.0

    def num_params(self) -> int:
        return int(sum(int(np.prod(v.shape)) for _, v in self.entries.values()))

    def nbytes(self) -> int:
        return int(sum(i.size * i.dtype.itemsize + v.size * v.dtype.itemsize
                       for i, v in self.entries.values()))


def pack_from_shira(name: str, trainable, aux, alpha: float = 1.0) -> AdapterPack:
    entries = {}
    flat_idx = jax.tree_util.tree_flatten_with_path(
        aux["indices"], is_leaf=lambda x: x is None)[0]
    flat_val = jax.tree_util.tree_flatten_with_path(
        trainable, is_leaf=lambda x: x is None)[0]
    for (pi, i), (pv, v) in zip(flat_idx, flat_val):
        if i is not None:
            entries[M.path_str(pi)] = (i, v)
    return AdapterPack(name=name, entries=entries, alpha=alpha)


def pack_from_delta(name: str, base, tuned, acfg: AdapterConfig,
                    alpha: float = 1.0) -> AdapterPack:
    """S = W_new - W gathered at its own nonzeros (paper App. G). Used for
    hook-mode training where the base weights were updated in place."""
    entries = {}
    for (p, w_new), (_, w_old) in zip(
            jax.tree_util.tree_flatten_with_path(tuned)[0],
            jax.tree_util.tree_flatten_with_path(base)[0]):
        if not M.is_target(p, w_new, acfg.target_modules):
            continue
        delta = (w_new.astype(jnp.float32) - w_old.astype(jnp.float32))
        *lead, n, m = delta.shape
        k = M.budget(n, m, acfg.sparsity)
        nl = int(np.prod(lead)) if lead else 1
        df = jnp.reshape(delta, (nl, n * m))
        _, idx = jax.lax.top_k(jnp.abs(df), k)
        val = jax.vmap(lambda row, ix: row[ix])(df, idx)
        entries[M.path_str(p)] = (
            jnp.reshape(idx.astype(jnp.int32), tuple(lead) + (k,)),
            jnp.reshape(val, tuple(lead) + (k,)))
    return AdapterPack(name=name, entries=entries, alpha=alpha)


def apply_pack(params, pack: AdapterPack, alpha: Optional[float] = None,
               sign: float = 1.0):
    """W += sign * alpha * S at the pack's indices (load / unload)."""
    a = (pack.alpha if alpha is None else alpha) * sign

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, prefix + (str(k),)) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = [walk(v, prefix + (str(i),)) for i, v in enumerate(tree)]
            return type(tree)(t) if not isinstance(tree, tuple) else tuple(t)
        key = "/".join(prefix)
        if key in pack.entries:
            idx, val = pack.entries[key]
            return M.scatter_packed_add(tree, idx, val, alpha=a).astype(
                tree.dtype)
        return tree

    return walk(params, ())
