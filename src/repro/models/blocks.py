"""Residual blocks per family + the zamba2 shared-attention block.

A "block" bundles its mixer (attention / MLA / Mamba2) with its FFN
(dense / MoE / none) and pre-norms. Each kind exposes init / train /
prefill / decode with a uniform signature so the LM can scan over stacked
layer parameters regardless of family.

Cache conventions (per layer):
  gqa/mla block : attention.KVCache
  mamba block   : mamba2.MambaCache

The MoE block's prefill, decode and chunk prefill return (h, cache,
counts): counts (held,) int32 are the token-choices its expert layer
routed to each held expert.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba2
from repro.models.layers import dense, init_mlp, init_rms_norm, mlp, rms_norm
from repro.models.moe import init_moe, moe_ffn, moe_serve


# ---------------------------------------------------------------------------
# Dense transformer block (attn + MLP) — also used for vlm/audio backbones.
# ---------------------------------------------------------------------------

def init_dense_block(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    k1, k2 = jax.random.split(key)
    a = attn.init_mla(k1, cfg) if cfg.attn_type == "mla" else attn.init_gqa(k1, cfg)
    return {
        "attn_norm": init_rms_norm(cfg.d_model),
        "attn": a,
        "mlp_norm": init_rms_norm(cfg.d_model),
        "mlp": init_mlp(k2, cfg.d_model, d_ff or cfg.d_ff, cfg.act),
    }


def _attn_train(params, cfg, x, prefix_len):
    if cfg.attn_type == "mla":
        return attn.mla_train(params, cfg, x, prefix_len=prefix_len)
    return attn.gqa_train(params, cfg, x, prefix_len=prefix_len)


def dense_block_train(params, cfg: ModelConfig, h, *, prefix_len=0, aux=None):
    x = rms_norm(h, params["attn_norm"]["scale"], cfg.norm_eps)
    h = h + _attn_train(params["attn"], cfg, x, prefix_len)
    x = rms_norm(h, params["mlp_norm"]["scale"], cfg.norm_eps)
    h = h + mlp(params["mlp"], x, cfg.act)
    return h, aux


def dense_block_prefill(params, cfg: ModelConfig, h, cache_size, *, prefix_len=0):
    x = rms_norm(h, params["attn_norm"]["scale"], cfg.norm_eps)
    if cfg.attn_type == "mla":
        a, cache = attn.mla_prefill(params["attn"], cfg, x, cache_size)
    else:
        a, cache = attn.gqa_prefill(params["attn"], cfg, x, cache_size,
                                    prefix_len=prefix_len)
    h = h + a
    x = rms_norm(h, params["mlp_norm"]["scale"], cfg.norm_eps)
    h = h + mlp(params["mlp"], x, cfg.act)
    return h, cache


def dense_block_decode(params, cfg: ModelConfig, h, cache, pos,
                       block_tables=None):
    x = rms_norm(h, params["attn_norm"]["scale"], cfg.norm_eps)
    if block_tables is not None:
        if cfg.attn_type == "mla":
            a, cache = attn.mla_decode_paged(params["attn"], cfg, x, cache,
                                             block_tables, pos)
        else:
            a, cache = attn.gqa_decode_paged(params["attn"], cfg, x, cache,
                                             block_tables, pos)
    elif cfg.attn_type == "mla":
        a, cache = attn.mla_decode(params["attn"], cfg, x, cache, pos)
    else:
        a, cache = attn.gqa_decode(params["attn"], cfg, x, cache, pos)
    h = h + a
    x = rms_norm(h, params["mlp_norm"]["scale"], cfg.norm_eps)
    h = h + mlp(params["mlp"], x, cfg.act)
    return h, cache


def dense_block_prefill_chunk(params, cfg: ModelConfig, h, cache,
                              block_tables, start, kv_len):
    """Paged chunk prefill: like dense_block_prefill but writing one padded
    chunk of positions [start, kv_len) through a block table."""
    x = rms_norm(h, params["attn_norm"]["scale"], cfg.norm_eps)
    if cfg.attn_type == "mla":
        a, cache = attn.mla_prefill_chunk(params["attn"], cfg, x, cache,
                                          block_tables, start, kv_len)
    else:
        a, cache = attn.gqa_prefill_chunk(params["attn"], cfg, x, cache,
                                          block_tables, start, kv_len)
    h = h + a
    x = rms_norm(h, params["mlp_norm"]["scale"], cfg.norm_eps)
    h = h + mlp(params["mlp"], x, cfg.act)
    return h, cache


# ---------------------------------------------------------------------------
# MoE transformer block (attn + MoE FFN).
# ---------------------------------------------------------------------------

def init_moe_block(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    a = attn.init_mla(k1, cfg) if cfg.attn_type == "mla" else attn.init_gqa(k1, cfg)
    return {
        "attn_norm": init_rms_norm(cfg.d_model),
        "attn": a,
        "mlp_norm": init_rms_norm(cfg.d_model),
        "moe": init_moe(k2, cfg),
    }


def moe_block_train(params, cfg: ModelConfig, h, *, prefix_len=0, aux=None):
    x = rms_norm(h, params["attn_norm"]["scale"], cfg.norm_eps)
    h = h + _attn_train(params["attn"], cfg, x, prefix_len)
    x = rms_norm(h, params["mlp_norm"]["scale"], cfg.norm_eps)
    y, lb = moe_ffn(params["moe"], cfg, x)
    h = h + y
    aux = lb if aux is None else aux + lb
    return h, aux


def moe_block_prefill(params, cfg: ModelConfig, h, cache_size, *, prefix_len=0):
    x = rms_norm(h, params["attn_norm"]["scale"], cfg.norm_eps)
    if cfg.attn_type == "mla":
        a, cache = attn.mla_prefill(params["attn"], cfg, x, cache_size)
    else:
        a, cache = attn.gqa_prefill(params["attn"], cfg, x, cache_size,
                                    prefix_len=prefix_len)
    h = h + a
    x = rms_norm(h, params["mlp_norm"]["scale"], cfg.norm_eps)
    y, counts = moe_serve(params["moe"], cfg, x)
    return h + y, cache, counts


def moe_block_decode(params, cfg: ModelConfig, h, cache, pos,
                     block_tables=None):
    x = rms_norm(h, params["attn_norm"]["scale"], cfg.norm_eps)
    if block_tables is not None:
        if cfg.attn_type == "mla":
            a, cache = attn.mla_decode_paged(params["attn"], cfg, x, cache,
                                             block_tables, pos)
        else:
            a, cache = attn.gqa_decode_paged(params["attn"], cfg, x, cache,
                                             block_tables, pos)
    elif cfg.attn_type == "mla":
        a, cache = attn.mla_decode(params["attn"], cfg, x, cache, pos)
    else:
        a, cache = attn.gqa_decode(params["attn"], cfg, x, cache, pos)
    h = h + a
    x = rms_norm(h, params["mlp_norm"]["scale"], cfg.norm_eps)
    y, counts = moe_serve(params["moe"], cfg, x)
    return h + y, cache, counts


def moe_block_prefill_chunk(params, cfg: ModelConfig, h, cache,
                            block_tables, start, kv_len):
    x = rms_norm(h, params["attn_norm"]["scale"], cfg.norm_eps)
    if cfg.attn_type == "mla":
        a, cache = attn.mla_prefill_chunk(params["attn"], cfg, x, cache,
                                          block_tables, start, kv_len)
    else:
        a, cache = attn.gqa_prefill_chunk(params["attn"], cfg, x, cache,
                                          block_tables, start, kv_len)
    h = h + a
    x = rms_norm(h, params["mlp_norm"]["scale"], cfg.norm_eps)
    y, counts = moe_serve(params["moe"], cfg, x)
    return h + y, cache, counts


# ---------------------------------------------------------------------------
# Mamba2 block (norm + SSD mixer, no FFN — mamba2-780m style).
# ---------------------------------------------------------------------------

def init_mamba_block(key, cfg: ModelConfig) -> dict:
    return {"norm": init_rms_norm(cfg.d_model), "mixer": mamba2.init_mamba(key, cfg)}


def mamba_block_train(params, cfg: ModelConfig, h, *, prefix_len=0, aux=None):
    x = rms_norm(h, params["norm"]["scale"], cfg.norm_eps)
    h = h + mamba2.mamba_train(params["mixer"], cfg, x)
    return h, aux


def mamba_block_prefill(params, cfg: ModelConfig, h, cache_size, *, prefix_len=0):
    x = rms_norm(h, params["norm"]["scale"], cfg.norm_eps)
    y, cache = mamba2.mamba_prefill(params["mixer"], cfg, x)
    return h + y, cache


def mamba_block_decode(params, cfg: ModelConfig, h, cache, pos,
                       block_tables=None):
    # SSM state is O(1) per request — paging does not apply; the kwarg only
    # keeps the scan-body signature uniform.
    del block_tables
    x = rms_norm(h, params["norm"]["scale"], cfg.norm_eps)
    y, cache = mamba2.mamba_decode(params["mixer"], cfg, x, cache, pos)
    return h + y, cache


# ---------------------------------------------------------------------------
# Zamba2 shared attention block: ONE set of weights applied at several depth
# sites. Input is concat(hidden, initial_embedding) fused down to d_model.
# ---------------------------------------------------------------------------

def init_shared_attn(key, cfg: ModelConfig) -> dict:
    k0, k1 = jax.random.split(key)
    from repro.models.layers import glorot
    p = init_dense_block(k1, cfg)
    p["w_fuse"] = glorot(k0, (2 * cfg.d_model, cfg.d_model))
    return p


def shared_attn_train(params, cfg: ModelConfig, h, emb):
    u = dense(jnp.concatenate([h, emb], axis=-1), params["w_fuse"])
    out, _ = dense_block_train(params, cfg, u)
    return h + (out - u)  # residual of the block body only


def shared_attn_prefill(params, cfg: ModelConfig, h, emb, cache_size):
    u = dense(jnp.concatenate([h, emb], axis=-1), params["w_fuse"])
    out, cache = dense_block_prefill(params, cfg, u, cache_size)
    return h + (out - u), cache


def shared_attn_decode(params, cfg: ModelConfig, h, emb, cache, pos):
    u = dense(jnp.concatenate([h, emb], axis=-1), params["w_fuse"])
    out, cache = dense_block_decode(params, cfg, u, cache, pos)
    return h + (out - u), cache


BLOCK_FNS = {
    "dense": (init_dense_block, dense_block_train, dense_block_prefill,
              dense_block_decode),
    "moe": (init_moe_block, moe_block_train, moe_block_prefill,
            moe_block_decode),
    "mamba": (init_mamba_block, mamba_block_train, mamba_block_prefill,
              mamba_block_decode),
}
