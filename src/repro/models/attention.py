"""Attention: GQA/MQA/MHA (chunked, flash-style) and MLA (DeepSeek-V2).

Three entry points per variant:
  *_train(params, cfg, x, ...)            full-sequence, no cache
  *_prefill(params, cfg, x, cache_len)    full-sequence, returns KV cache
  *_decode(params, cfg, x, cache, pos)    one new token against the cache

The sequence dimension of the score matrix is never materialised in full for
long sequences: queries are processed in chunks of ``q_chunk`` via lax.scan
(online peak memory = one chunk row of scores). MLA decode uses the matrix-
absorption trick: attention runs directly in the kv_lora latent space so the
cache stores only (c_kv, k_rope) = (rank + rope_dim) per token.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (apply_rope, compute_dtype, dense, glorot,
                                 rms_norm, yarn_mscale)

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, KV, D)  [MLA: c_kv (B, S_max, rank)]
    v: jax.Array  # (B, S_max, KV, D)  [MLA: k_rope (B, S_max, rope_dim)]


# ---------------------------------------------------------------------------
# Core score/softmax/AV with GQA grouping — one q-chunk against full K.
# ---------------------------------------------------------------------------

def _attend_block(q, k, v, q_pos, k_pos, causal, prefix_len, kv_len=None,
                  scale=None):
    """q: (B, qc, H, D); k,v: (B, Sk, KV, Dk|Dv); positions are (qc,) or
    (B, qc) for per-request decode offsets, and (Sk,). ``kv_len`` may be a
    scalar or (B,) per-request filled-cache length; ``scale`` multiplies the
    scores (default 1/sqrt(D)).

    Returns (B, qc, H, Dv). GQA grouping happens here without repeating KV.
    """
    B, qc, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, qc, KV, G, D)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(compute_dtype()), k.astype(compute_dtype()),
        preferred_element_type=jnp.float32) * scale
    qp = q_pos if q_pos.ndim == 2 else q_pos[None]    # (B|1, qc)
    mask = jnp.ones((qp.shape[0], qc, Sk), bool)
    if causal:
        cm = qp[:, :, None] >= k_pos[None, None, :]
        if prefix_len > 0:  # prefix-LM: prefix tokens are globally visible
            cm = cm | (k_pos[None, None, :] < prefix_len)
        mask = mask & cm
    if kv_len is not None:  # only the filled part of the cache is valid
        kl = jnp.asarray(kv_len)
        kl = kl[:, None, None] if kl.ndim == 1 else kl
        mask = mask & (k_pos[None, None, :] < kl)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(compute_dtype())
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(compute_dtype()),
                     preferred_element_type=compute_dtype())
    return out.reshape(B, qc, H, v.shape[-1])


def chunked_attention(q, k, v, *, causal=True, q_offset=0, prefix_len=0,
                      q_chunk=512, kv_len=None, scale=None):
    """Flash-style attention over q-chunks. q: (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    k_pos = jnp.arange(Sk)
    if Sq <= q_chunk:
        q_pos = q_offset + jnp.arange(Sq)
        return _attend_block(q, k, v, q_pos, k_pos, causal, prefix_len, kv_len,
                             scale)
    while Sq % q_chunk:  # shrink to the nearest divisor of Sq
        q_chunk -= 1
    n = Sq // q_chunk
    qr = jnp.moveaxis(q.reshape(B, n, q_chunk, H, D), 1, 0)

    def body(_, inp):
        qi, i = inp
        q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk)
        return None, _attend_block(qi, k, v, q_pos, k_pos, causal, prefix_len,
                                   kv_len, scale)

    # nested remat: recompute each chunk's scores/probs in backward instead
    # of stacking (n, B, H, qc, S) probs to HBM (flash-attention-style
    # backward; -7 TB/step on deepseek-coder train_4k, see §Perf)
    body = jax.checkpoint(body)
    _, out = jax.lax.scan(body, None, (qr, jnp.arange(n)))
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, v.shape[-1])


# ---------------------------------------------------------------------------
# GQA attention module
# ---------------------------------------------------------------------------

def padded_heads(cfg: ModelConfig) -> Tuple[int, int]:
    """(H', KV') after optional head-group padding (§Perf optimized variants).

    Padding keeps the GQA grouping: each kv group's q-head slots grow from
    G = H/KV to G' = H'/KV'; the extra slots (and extra kv heads) are
    zero-initialised so they contribute exactly nothing — the model function
    is unchanged, but the flat head dims now divide 16-way TP."""
    H = cfg.pad_heads_to or cfg.num_heads
    KV = cfg.pad_kv_to or cfg.num_kv_heads
    assert H % KV == 0, (H, KV)
    return H, KV


def _pad_masks(cfg: ModelConfig):
    """(q_head_real (H',), kv_head_real (KV',)) boolean masks."""
    Hp, KVp = padded_heads(cfg)
    H, KV = cfg.num_heads, cfg.num_kv_heads
    G, Gp = H // KV, Hp // KVp
    kv_real = jnp.arange(KVp) < KV
    grp = jnp.arange(Hp) // Gp
    slot = jnp.arange(Hp) % Gp
    q_real = (grp < KV) & (slot < G)
    return q_real, kv_real


def init_gqa(key, cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    Hp, KVp = padded_heads(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": glorot(ks[0], (d, Hp * hd)),
        "wk": glorot(ks[1], (d, KVp * hd)),
        "wv": glorot(ks[2], (d, KVp * hd)),
        "wo": glorot(ks[3], (Hp * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hp * hd,), jnp.float32)
        p["bk"] = jnp.zeros((KVp * hd,), jnp.float32)
        p["bv"] = jnp.zeros((KVp * hd,), jnp.float32)
    if Hp != cfg.num_heads or KVp != cfg.num_kv_heads:
        q_real, kv_real = _pad_masks(cfg)
        qm = jnp.repeat(q_real, hd).astype(jnp.float32)
        km = jnp.repeat(kv_real, hd).astype(jnp.float32)
        p["wq"] = p["wq"] * qm
        p["wk"] = p["wk"] * km
        p["wv"] = p["wv"] * km
        p["wo"] = p["wo"] * qm[:, None]
        if cfg.qkv_bias:
            p["bq"] = p["bq"] * qm
            p["bk"] = p["bk"] * km
            p["bv"] = p["bv"] * km
    return p


def _gqa_qkv(params, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    Hp, KVp = padded_heads(cfg)
    q = dense(x, params["wq"], params.get("bq")).reshape(B, S, Hp, hd)
    k = dense(x, params["wk"], params.get("bk")).reshape(B, S, KVp, hd)
    v = dense(x, params["wv"], params.get("bv")).reshape(B, S, KVp, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _maybe_repeat_kv(cfg: ModelConfig, t):
    """(B, S, KV', D) -> (B, S, H', D) when attn_repeat_kv (see configs)."""
    if not cfg.attn_repeat_kv:
        return t
    Hp, KVp = padded_heads(cfg)
    return jnp.repeat(t, Hp // KVp, axis=2)


def gqa_train(params, cfg: ModelConfig, x, *, prefix_len=0, q_chunk=512):
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _gqa_qkv(params, cfg, x, positions)
    out = chunked_attention(q, _maybe_repeat_kv(cfg, k),
                            _maybe_repeat_kv(cfg, v), causal=cfg.causal,
                            prefix_len=prefix_len, q_chunk=q_chunk)
    return dense(out.reshape(B, S, -1), params["wo"])


def gqa_prefill(params, cfg: ModelConfig, x, cache_size: int, *,
                prefix_len=0, q_chunk=512) -> Tuple[jax.Array, KVCache]:
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _gqa_qkv(params, cfg, x, positions)
    out = chunked_attention(q, _maybe_repeat_kv(cfg, k),
                            _maybe_repeat_kv(cfg, v), causal=cfg.causal,
                            prefix_len=prefix_len, q_chunk=q_chunk)
    hd = cfg.resolved_head_dim
    KV = padded_heads(cfg)[1]
    ck = jnp.zeros((B, cache_size, KV, hd), compute_dtype())
    cv = jnp.zeros((B, cache_size, KV, hd), compute_dtype())
    cache = KVCache(
        jax.lax.dynamic_update_slice(ck, k.astype(compute_dtype()), (0, 0, 0, 0)),
        jax.lax.dynamic_update_slice(cv, v.astype(compute_dtype()), (0, 0, 0, 0)),
    )
    return dense(out.reshape(B, S, -1), params["wo"]), cache


def _decode_positions(pos) -> Tuple[jax.Array, bool]:
    """Rope positions for one decode step: pos may be a scalar (whole batch
    at one index — the fixed-batch serving path) or a (B,) vector of
    per-request indices (continuous batching: every slot is mid-stream at
    its own depth). Returns (positions (1,)|(B, 1), is_vector)."""
    p = jnp.asarray(pos)
    if p.ndim == 0:
        return jnp.full((1,), p), False
    return p[:, None], True


def gqa_decode(params, cfg: ModelConfig, x, cache: KVCache, pos) -> Tuple[jax.Array, KVCache]:
    """x: (B, 1, d); pos: scalar index where the new token lands, or (B,)
    per-request indices."""
    B = x.shape[0]
    positions, vector = _decode_positions(pos)
    q, k, v = _gqa_qkv(params, cfg, x, positions)
    if vector:
        b = jnp.arange(B)
        ck = cache.k.at[b, pos].set(k.astype(compute_dtype())[:, 0])
        cv = cache.v.at[b, pos].set(v.astype(compute_dtype())[:, 0])
    else:
        ck = jax.lax.dynamic_update_slice(cache.k, k.astype(compute_dtype()), (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v.astype(compute_dtype()), (0, pos, 0, 0))
    out = _attend_block(q, _maybe_repeat_kv(cfg, ck), _maybe_repeat_kv(cfg, cv),
                        positions, jnp.arange(ck.shape[1]),
                        causal=True, prefix_len=0, kv_len=jnp.asarray(pos) + 1)
    return dense(out.reshape(B, 1, -1), params["wo"]), KVCache(ck, cv)


# ---------------------------------------------------------------------------
# Paged GQA: the cache is a global page pool (P, page, KV, D) per layer plus
# per-request block tables (B, nblk) — see repro.serving.kvcache. Decode and
# chunk-prefill write through the table and attend against the gathered
# contiguous view, so the math (and, in f32, the bits) matches the
# contiguous cache path token-for-token.
# ---------------------------------------------------------------------------

def _paged_kv_mod():
    from repro.serving import kvcache  # deferred: serving imports models
    return kvcache


def gqa_decode_paged(params, cfg: ModelConfig, x, cache: KVCache,
                     block_tables, pos) -> Tuple[jax.Array, KVCache]:
    """x: (B, 1, d); cache: page pools (P, page, KV, D) [or QuantKV];
    block_tables: (B, nblk) int32; pos: (B,) per-request write index."""
    KVC = _paged_kv_mod()
    B = x.shape[0]
    pos = jnp.asarray(pos)
    positions = pos[:, None]                               # (B, 1)
    q, k, v = _gqa_qkv(params, cfg, x, positions)
    valid = jnp.ones((B, 1), bool)
    ck = KVC.paged_write(cache.k, k, block_tables, positions, valid)
    cv = KVC.paged_write(cache.v, v, block_tables, positions, valid)
    kk = KVC.paged_gather(ck, block_tables)                # (B, S_max, KV, D)
    vv = KVC.paged_gather(cv, block_tables)
    out = _attend_block(q, _maybe_repeat_kv(cfg, kk), _maybe_repeat_kv(cfg, vv),
                        positions, jnp.arange(kk.shape[1]),
                        causal=True, prefix_len=0, kv_len=pos + 1)
    return dense(out.reshape(B, 1, -1), params["wo"]), KVCache(ck, cv)


def gqa_prefill_chunk(params, cfg: ModelConfig, x, cache: KVCache,
                      block_tables, start, kv_len) -> Tuple[jax.Array, KVCache]:
    """One chunk of a paged prefill. x: (B, C, d) — rows at absolute
    positions ``start + i``; rows with position >= ``kv_len`` are padding
    (their K/V land in the scratch page, their outputs are garbage the
    caller discards). ``kv_len`` is the total valid length including this
    chunk."""
    KVC = _paged_kv_mod()
    B, C, _ = x.shape
    positions = start + jnp.arange(C)                      # (C,)
    q, k, v = _gqa_qkv(params, cfg, x, positions)
    posg = jnp.broadcast_to(positions[None], (B, C))
    valid = posg < kv_len
    ck = KVC.paged_write(cache.k, k, block_tables, posg, valid)
    cv = KVC.paged_write(cache.v, v, block_tables, posg, valid)
    kk = KVC.paged_gather(ck, block_tables)
    vv = KVC.paged_gather(cv, block_tables)
    out = _attend_block(q, _maybe_repeat_kv(cfg, kk), _maybe_repeat_kv(cfg, vv),
                        positions, jnp.arange(kk.shape[1]),
                        causal=cfg.causal, prefix_len=0, kv_len=kv_len)
    return dense(out.reshape(B, C, -1), params["wo"]), KVCache(ck, cv)


def mla_decode_paged(params, cfg: ModelConfig, x, cache: KVCache,
                     block_tables, pos) -> Tuple[jax.Array, KVCache]:
    """Matrix-absorbed paged decode: cache.k pools c_kv (P, page, rank),
    cache.v pools k_rope (P, page, rope_dim)."""
    KVC = _paged_kv_mod()
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    pos = jnp.asarray(pos)
    positions = pos[:, None]
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    c_kv_new, k_rope_new = _mla_ckv(params, cfg, x, positions)
    valid = jnp.ones((B, 1), bool)
    ck = KVC.paged_write(cache.k, c_kv_new, block_tables, positions, valid)
    cv = KVC.paged_write(cache.v, k_rope_new, block_tables, positions, valid)
    cc = KVC.paged_gather(ck, block_tables)                # (B, S_max, rank)
    cr = KVC.paged_gather(cv, block_tables)
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_eff = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = mla_softmax_scale(cfg)
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_eff, cc.astype(jnp.float32))
              + jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                           cr.astype(jnp.float32))) * scale
    valid_k = jnp.arange(cc.shape[1])[None, None, None, :] <= \
        pos[:, None, None, None]
    scores = jnp.where(valid_k, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhqs,bsr->bqhr", probs, cc.astype(jnp.float32))
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bqhr,rhv->bqhv", out_lat,
                     w_uv.astype(jnp.float32)).astype(compute_dtype())
    return dense(out.reshape(B, 1, -1), params["wo"]), KVCache(ck, cv)


def mla_prefill_chunk(params, cfg: ModelConfig, x, cache: KVCache,
                      block_tables, start, kv_len) -> Tuple[jax.Array, KVCache]:
    """One chunk of a paged MLA prefill: write the chunk's latents, then
    attend with per-head K/V expanded from the gathered latent view."""
    KVC = _paged_kv_mod()
    B, C, _ = x.shape
    positions = start + jnp.arange(C)
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    c_kv, k_rope = _mla_ckv(params, cfg, x, positions)
    posg = jnp.broadcast_to(positions[None], (B, C))
    valid = posg < kv_len
    ck = KVC.paged_write(cache.k, c_kv, block_tables, posg, valid)
    cv = KVC.paged_write(cache.v, k_rope, block_tables, posg, valid)
    cc = KVC.paged_gather(ck, block_tables)
    cr = KVC.paged_gather(cv, block_tables)
    k, v = _mla_expand_kv(params, cfg, cc, cr)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = _attend_block(q, k, v, positions, jnp.arange(k.shape[1]),
                        causal=True, prefix_len=0, kv_len=kv_len,
                        scale=mla_softmax_scale(cfg))
    return dense(out.reshape(B, C, -1), params["wo"]), KVCache(ck, cv)


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_rope(cfg: ModelConfig, x, positions):
    """Rotary embedding of MLA's rope dims, YaRN-scaled where the config
    names it (``cfg.yarn``); ``mla_softmax_scale`` is its other half."""
    return apply_rope(x, positions, cfg.rope_theta, cfg.yarn)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk head dim), times YaRN's mscale(all_dim)^2 under YaRN, as
    DeepSeek-V2's attention sets ``softmax_scale``."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return scale


def init_mla(key, cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "w_dkv": glorot(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim)),
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), jnp.float32)},
        "w_uk": glorot(ks[2], (m.kv_lora_rank, H * m.qk_nope_head_dim)),
        "w_uv": glorot(ks[3], (m.kv_lora_rank, H * m.v_head_dim)),
        "wo": glorot(ks[4], (H * m.v_head_dim, d)),
    }
    if m.q_lora_rank:
        p["wq_a"] = glorot(ks[0], (d, m.q_lora_rank))
        p["q_norm"] = {"scale": jnp.ones((m.q_lora_rank,), jnp.float32)}
        p["wq_b"] = glorot(ks[5], (m.q_lora_rank, H * qk_dim))
    else:
        p["wq"] = glorot(ks[0], (d, H * qk_dim))
    return p


def _mla_q(params, cfg, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    if m.q_lora_rank:
        qa = rms_norm(dense(x, params["wq_a"]), params["q_norm"]["scale"], cfg.norm_eps)
        q = dense(qa, params["wq_b"])
    else:
        q = dense(x, params["wq"])
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    return q_nope, mla_rope(cfg, q_rope, positions)


def _mla_ckv(params, cfg, x, positions):
    m = cfg.mla
    ckv_full = dense(x, params["w_dkv"])
    c_kv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, params["kv_norm"]["scale"], cfg.norm_eps)
    k_rope = mla_rope(cfg, k_rope[:, :, None, :], positions)[:, :, 0]
    return c_kv, k_rope


def _mla_expand_kv(params, cfg, c_kv, k_rope):
    """Materialise per-head K/V from the latent cache (train/prefill path)."""
    m = cfg.mla
    B, S = c_kv.shape[:2]
    H = cfg.num_heads
    k_nope = dense(c_kv, params["w_uk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = dense(c_kv, params["w_uv"]).reshape(B, S, H, m.v_head_dim)
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                (B, S, H, m.qk_rope_head_dim))
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    return k, v


def mla_train(params, cfg: ModelConfig, x, *, q_chunk=512, prefix_len=0):
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    c_kv, k_rope = _mla_ckv(params, cfg, x, positions)
    k, v = _mla_expand_kv(params, cfg, c_kv, k_rope)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = chunked_attention(q, k, v, causal=cfg.causal, q_chunk=q_chunk,
                            prefix_len=prefix_len,
                            scale=mla_softmax_scale(cfg))
    return dense(out.reshape(B, S, -1), params["wo"])


def mla_prefill(params, cfg: ModelConfig, x, cache_size: int, *,
                q_chunk=512) -> Tuple[jax.Array, KVCache]:
    m = cfg.mla
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    c_kv, k_rope = _mla_ckv(params, cfg, x, positions)
    k, v = _mla_expand_kv(params, cfg, c_kv, k_rope)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = chunked_attention(q, k, v, causal=True, q_chunk=q_chunk,
                            scale=mla_softmax_scale(cfg))
    cc = jnp.zeros((B, cache_size, m.kv_lora_rank), compute_dtype())
    cr = jnp.zeros((B, cache_size, m.qk_rope_head_dim), compute_dtype())
    cache = KVCache(
        jax.lax.dynamic_update_slice(cc, c_kv.astype(compute_dtype()), (0, 0, 0)),
        jax.lax.dynamic_update_slice(cr, k_rope.astype(compute_dtype()), (0, 0, 0)),
    )
    return dense(out.reshape(B, S, -1), params["wo"]), cache


def mla_decode(params, cfg: ModelConfig, x, cache: KVCache, pos) -> Tuple[jax.Array, KVCache]:
    """Matrix-absorbed decode: attention runs in the kv_lora latent space.

    cache.k = c_kv (B, S, r); cache.v = k_rope (B, S, rope_dim).
    """
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    positions, vector = _decode_positions(pos)
    q_nope, q_rope = _mla_q(params, cfg, x, positions)     # (B,1,H,·)
    c_kv_new, k_rope_new = _mla_ckv(params, cfg, x, positions)
    if vector:
        b = jnp.arange(B)
        cc = cache.k.at[b, pos].set(c_kv_new.astype(compute_dtype())[:, 0])
        cr = cache.v.at[b, pos].set(k_rope_new.astype(compute_dtype())[:, 0])
    else:
        cc = jax.lax.dynamic_update_slice(cache.k, c_kv_new.astype(compute_dtype()),
                                          (0, pos, 0))
        cr = jax.lax.dynamic_update_slice(cache.v, k_rope_new.astype(compute_dtype()),
                                          (0, pos, 0))
    # Absorb W_uk into q: q_eff[b,h,r] = sum_n q_nope[b,1,h,n] * W_uk[r, h*n]
    # (f32 einsums: decode-step FLOPs are negligible; avoids CPU bf16-dot gaps)
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_eff = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = mla_softmax_scale(cfg)
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_eff, cc.astype(jnp.float32))
              + jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                           cr.astype(jnp.float32))) * scale
    p = jnp.asarray(pos)
    valid = jnp.arange(cc.shape[1])[None, None, None, :] <= (
        p[:, None, None, None] if p.ndim == 1 else p)
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhqs,bsr->bqhr", probs, cc.astype(jnp.float32))
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bqhr,rhv->bqhv", out_lat,
                     w_uv.astype(jnp.float32)).astype(compute_dtype())
    return dense(out.reshape(B, 1, -1), params["wo"]), KVCache(cc, cr)
