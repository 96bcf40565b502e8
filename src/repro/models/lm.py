"""Causal LM assembly: stages of scanned blocks + embeddings + chunked loss.

Depth is folded into ``jax.lax.scan`` over stacked layer parameters, so the
HLO is O(1) in the number of layers (critical for 88-layer configs). A model
is a list of *stages*; each stage is a homogeneous stack of blocks:

  dense family     -> [("dense", L)]
  moe family       -> [("dense", first_dense)] + [("moe", rest)]
  ssm family       -> [("mamba", L)]
  hybrid (zamba2)  -> [("hybrid", L)]  groups of `hybrid_attn_every` mamba
                       layers followed by the shared attention block

Entry points:
  init_params(cfg, key)
  train_loss(params, cfg, batch)                  -> (loss, metrics)
  prefill(params, cfg, batch, cache_size)         -> (last_logits, caches)
  decode_step(params, cfg, tokens, caches, pos)   -> (logits, caches)
  (both with ``expert_counts=True`` also return the moe layers' routed
  token-choices per held expert, (moe layers, held) int32)
  init_cache(cfg, batch, cache_size)              -> caches (zeros)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as B
from repro.models.attention import KVCache
from repro.models.layers import (compute_dtype, cross_entropy, embed,
                                 init_embedding, init_rms_norm, normal_init,
                                 rms_norm, unembed)
from repro.models.mamba2 import MambaCache, dims as mamba_dims
from repro.launch.actctx import shard_act


# ---------------------------------------------------------------------------
# Stage planning
# ---------------------------------------------------------------------------

def stage_plan(cfg: ModelConfig) -> List[Tuple[str, int]]:
    if cfg.family in ("dense", "vlm", "audio"):
        return [("dense", cfg.num_layers)]
    if cfg.family == "moe":
        plan = []
        fd = cfg.moe.first_dense_layers
        if fd:
            plan.append(("dense_first", fd))
        plan.append(("moe", cfg.num_layers - fd))
        return plan
    if cfg.family == "ssm":
        return [("mamba", cfg.num_layers)]
    if cfg.family == "hybrid":
        return [("hybrid", cfg.num_layers)]
    raise ValueError(cfg.family)


def _block_fns(kind: str):
    base = "dense" if kind == "dense_first" else kind
    return B.BLOCK_FNS[base]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_stage(key, cfg: ModelConfig, kind: str, n: int):
    if kind == "hybrid":
        k = cfg.hybrid_attn_every
        assert n % k == 0, f"layers {n} % hybrid_attn_every {k} != 0"
        g = n // k
        keys = jax.random.split(key, n)
        stacked = jax.vmap(lambda kk: B.init_mamba_block(kk, cfg))(keys)
        return jax.tree.map(lambda x: x.reshape((g, k) + x.shape[1:]), stacked)
    init_fn = _block_fns(kind)[0]
    if kind == "dense_first":
        init_fn = functools.partial(B.init_dense_block,
                                    d_ff=cfg.moe.first_dense_d_ff)
    keys = jax.random.split(key, n)
    return jax.vmap(lambda kk: init_fn(kk, cfg))(keys)


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    plan = stage_plan(cfg)
    keys = jax.random.split(key, len(plan) + 3)
    params: Dict[str, Any] = {
        "embed": init_embedding(keys[0], cfg.padded_vocab, cfg.d_model),
        "final_norm": init_rms_norm(cfg.d_model),
        "stages": [_init_stage(keys[2 + i], cfg, kind, n)
                   for i, (kind, n) in enumerate(plan)],
    }
    if not cfg.tie_embeddings:
        params["unembed"] = {
            "lm_head": normal_init(keys[1], (cfg.d_model, cfg.padded_vocab))}
    if cfg.family == "hybrid":
        params["shared_attn"] = B.init_shared_attn(keys[-1], cfg)
    return params


# ---------------------------------------------------------------------------
# Remat policy
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Embedding (+ modality stubs)
# ---------------------------------------------------------------------------

def embed_inputs(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, int]:
    """Returns (h, prefix_len). VLM prepends precomputed patch embeddings;
    audio consumes precomputed frame embeddings directly."""
    if cfg.modality == "audio":
        return batch["frame_embeds"].astype(compute_dtype()), 0
    h = embed(params["embed"], batch["tokens"])
    if cfg.modality == "vision":
        patches = batch["patch_embeds"].astype(compute_dtype())
        h = jnp.concatenate([patches, h], axis=1)
        return h, patches.shape[1]
    return h, 0


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------

def _stage_train(stage_params, kind, cfg, h, aux, prefix_len, shared=None):
    if kind == "hybrid":
        emb = h  # hybrid shared block sees the original embedding stream
        k = cfg.hybrid_attn_every

        def group_body(carry, gp):
            hh, ax = carry
            hh = shard_act(hh)

            def inner(h2, lp):
                h2, _ = B.mamba_block_train(lp, cfg, h2)
                return shard_act(h2), None

            hh, _ = jax.lax.scan(inner, hh, gp)
            hh = B.shared_attn_train(shared, cfg, hh, emb)
            return (hh, ax), None

        body = _maybe_remat(group_body, cfg)
        (h, aux), _ = jax.lax.scan(body, (h, aux), stage_params)
        return h, aux

    train_fn = _block_fns(kind)[1]

    def body(carry, lp):
        hh, ax = carry
        hh = shard_act(hh)
        hh, ax = train_fn(lp, cfg, hh, prefix_len=prefix_len, aux=ax)
        return (hh, ax), None

    body = _maybe_remat(body, cfg)
    (h, aux), _ = jax.lax.scan(body, (h, aux), stage_params)
    return h, aux


def _pick_chunk(total: int, target: int = 32_768) -> int:
    c = min(total, target)
    while total % c:
        c -= 1
    return c


def chunked_loss(params, cfg: ModelConfig, h, labels,
                 loss_mask=None) -> jax.Array:
    """Never materialises the full (T, vocab) logits tensor."""
    Bq, S, d = h.shape
    T = Bq * S
    hf = h.reshape(T, d)
    lf = labels.reshape(T)
    mf = (jnp.ones((T,), jnp.float32) if loss_mask is None
          else loss_mask.reshape(T).astype(jnp.float32))
    tie = params["embed"]["emb"] if cfg.tie_embeddings else None
    un = params.get("unembed")
    c = _pick_chunk(T)
    n = T // c

    def body(carry, xs):
        hc, lc, mc = xs
        from repro.launch.actctx import shard_as
        # gather the bf16 hidden chunk over `model` once instead of letting
        # XLA psum f32 logits (6x less collective traffic, see §Perf)
        hc = shard_as(hc, "loss_act")
        logits = unembed(un, hc, tie_to=tie, softcap=cfg.logit_softcap,
                         logical_vocab=cfg.vocab_size)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        nll = (logz - gold) * mc
        return carry + jnp.sum(nll), None

    # remat: recompute the (Tc, V) logits in backward instead of saving all
    # n chunks of them (226 GB/device at mamba2 train_4k scale — see §Perf).
    body = jax.checkpoint(body)
    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (hf.reshape(n, c, d), lf.reshape(n, c), mf.reshape(n, c)))
    return total / jnp.maximum(jnp.sum(mf), 1.0)


def train_loss(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, Dict]:
    h, prefix_len = embed_inputs(params, cfg, batch)
    aux = jnp.zeros((), jnp.float32)
    for sp, (kind, _) in zip(params["stages"], stage_plan(cfg)):
        h, aux = _stage_train(sp, kind, cfg, h, aux, prefix_len,
                              shared=params.get("shared_attn"))
    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    labels = batch["labels"]
    if cfg.modality == "vision":  # loss only over the text suffix
        h = h[:, prefix_len:]
    loss = chunked_loss(params, cfg, h, labels, batch.get("loss_mask"))
    metrics = {"ce": loss, "aux": aux}
    if cfg.family == "moe" or (cfg.moe and cfg.moe.num_experts):
        loss = loss + 0.01 * aux
    return loss, metrics


def encode(params, cfg: ModelConfig, batch):
    """Encoder-only serving (hubert): full-sequence frame logits, no cache."""
    h, prefix_len = embed_inputs(params, cfg, batch)
    aux = jnp.zeros((), jnp.float32)
    for sp, (kind, _) in zip(params["stages"], stage_plan(cfg)):
        h, aux = _stage_train(sp, kind, cfg, h, aux, prefix_len,
                              shared=params.get("shared_attn"))
    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    tie = params["embed"]["emb"] if cfg.tie_embeddings else None
    return unembed(params.get("unembed"), h, tie_to=tie,
                   softcap=cfg.logit_softcap, logical_vocab=cfg.vocab_size)


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------

def _whole_experts(stage_params, kind):
    """A serving scan reads a moe stage's expert stacks whole, so that the
    grouped matmul reads each layer's blocks in place of a slice copied out
    of the stack: (the params the scan slices, the layer indices to scan,
    a function of a layer's slice and index giving that layer's params).
    Other stages scan as they are (no indices)."""
    if kind != "moe":
        return stage_params, None, lambda lp, i: lp
    n = jax.tree.leaves(stage_params)[0].shape[0]
    stacks = {k: v for k, v in stage_params["moe"].items()
              if k.startswith("experts_")}
    rest = dict(stage_params, moe={k: v for k, v in
                                   stage_params["moe"].items()
                                   if k not in stacks})

    def layer(lp, i):
        return dict(lp, moe=dict(lp["moe"], **stacks, layer=i))

    return rest, jnp.arange(n), layer


def _stage_prefill(stage_params, kind, cfg, h, cache_size, prefix_len,
                   shared=None):
    if kind == "hybrid":
        emb = h
        k = cfg.hybrid_attn_every

        def group_body(hh, gp):
            def inner(h2, lp):
                return B.mamba_block_prefill(lp, cfg, h2, cache_size)

            hh, mcaches = jax.lax.scan(inner, hh, gp)
            hh, acache = B.shared_attn_prefill(shared, cfg, hh, emb, cache_size)
            return hh, {"mamba": mcaches, "attn": acache}

        h, caches = jax.lax.scan(group_body, h, stage_params)
        return h, caches, None

    prefill_fn = _block_fns(kind)[2]
    scanned, index, layer = _whole_experts(stage_params, kind)

    def body(hh, xs):
        lp, i = xs
        out = prefill_fn(layer(lp, i), cfg, shard_act(hh), cache_size,
                         prefix_len=prefix_len)
        return out[0], out[1:]

    h, ys = jax.lax.scan(body, h, (scanned, index))
    return (h,) + _split_counts(ys)


def _split_counts(ys):
    """A stage scan's stacked outputs -> (caches, counts or None): moe
    blocks return (cache, counts), the others (cache,)."""
    return ys[0], (ys[1] if len(ys) > 1 else None)


def _with_counts(out, counts, expert_counts: bool):
    """Append the stages' routed counts, stacked (moe layers, held)."""
    if not expert_counts:
        return out
    counts = [c for c in counts if c is not None]
    if not counts:
        raise ValueError("expert_counts: the model has no moe layers")
    return out + (jnp.concatenate(counts),)


def prefill(params, cfg: ModelConfig, batch, cache_size: int,
            expert_counts: bool = False):
    h, prefix_len = embed_inputs(params, cfg, batch)
    caches, counts = [], []
    for sp, (kind, _) in zip(params["stages"], stage_plan(cfg)):
        h, cache, c = _stage_prefill(sp, kind, cfg, h, cache_size, prefix_len,
                                     shared=params.get("shared_attn"))
        caches.append(cache)
        counts.append(c)
    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    last = h[:, -1]
    tie = params["embed"]["emb"] if cfg.tie_embeddings else None
    logits = unembed(params.get("unembed"), last, tie_to=tie,
                     softcap=cfg.logit_softcap, logical_vocab=cfg.vocab_size)
    return _with_counts((logits, caches), counts, expert_counts)


def _stage_decode(stage_params, kind, cfg, h, caches, pos, shared=None,
                  block_tables=None):
    if kind == "hybrid":
        emb = h

        def group_body(hh, xs):
            gp, gc = xs

            def inner(h2, xs2):
                lp, c = xs2
                return B.mamba_block_decode(lp, cfg, h2, c, pos)

            hh, mcaches = jax.lax.scan(inner, hh, (gp, gc["mamba"]))
            hh, acache = B.shared_attn_decode(shared, cfg, hh, emb,
                                              gc["attn"], pos)
            return hh, {"mamba": mcaches, "attn": acache}

        h, new = jax.lax.scan(group_body, h, (stage_params, caches))
        return h, new, None

    decode_fn = _block_fns(kind)[3]
    scanned, index, layer = _whole_experts(stage_params, kind)

    def body(hh, xs):
        lp, c, i = xs
        out = decode_fn(layer(lp, i), cfg, hh, c, pos,
                        block_tables=block_tables)
        return out[0], out[1:]

    h, ys = jax.lax.scan(body, h, (scanned, caches, index))
    return (h,) + _split_counts(ys)


def decode_step(params, cfg: ModelConfig, tokens, caches, pos,
                block_tables=None, expert_counts: bool = False):
    """tokens: (B, 1) int32; pos: scalar cache index shared by the batch, or
    a (B,) int32 vector of per-request indices (continuous batching — every
    slot decodes at its own depth). With ``block_tables`` ((B, nblk) int32)
    the caches are page pools (see ``init_paged_cache``) and ``pos`` must be
    the (B,) per-request write index. Returns (logits (B, V), new caches)."""
    if block_tables is not None and cfg.family == "hybrid":
        raise NotImplementedError("paged decode covers attention caches only")
    h = embed(params["embed"], tokens)
    new_caches, counts = [], []
    for sp, cache, (kind, _) in zip(params["stages"], caches, stage_plan(cfg)):
        h, nc, c = _stage_decode(sp, kind, cfg, h, cache, pos,
                                 shared=params.get("shared_attn"),
                                 block_tables=block_tables)
        new_caches.append(nc)
        counts.append(c)
    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    tie = params["embed"]["emb"] if cfg.tie_embeddings else None
    logits = unembed(params.get("unembed"), h[:, 0], tie_to=tie,
                     softcap=cfg.logit_softcap, logical_vocab=cfg.vocab_size)
    return _with_counts((logits, new_caches), counts, expert_counts)


def _stage_prefill_chunk(stage_params, kind, cfg, h, caches, block_tables,
                         start, kv_len):
    fn = (B.moe_block_prefill_chunk if kind == "moe"
          else B.dense_block_prefill_chunk)
    scanned, index, layer = _whole_experts(stage_params, kind)

    def body(hh, xs):
        lp, c, i = xs
        out = fn(layer(lp, i), cfg, shard_act(hh), c, block_tables, start,
                 kv_len)
        return out[0], out[1]          # moe counts are not kept here

    h, new = jax.lax.scan(body, h, (scanned, caches, index))
    return h, new


def prefill_chunk(params, cfg: ModelConfig, tokens, caches, block_tables,
                  start, valid):
    """One padded chunk of a paged prefill.

    tokens: (B, C) int32, columns at absolute positions ``start + i``;
    ``valid`` (traced scalar) counts the real tokens — padding columns
    write to the scratch page and are masked out of attention. Returns
    (logits of the last real token (B, V), updated pool caches)."""
    if cfg.family not in ("dense", "moe") or cfg.modality != "text":
        raise NotImplementedError(
            "chunked paged prefill covers dense/moe text models")
    h = embed(params["embed"], tokens)
    kv_len = start + valid
    new_caches = []
    for sp, cache, (kind, _) in zip(params["stages"], caches, stage_plan(cfg)):
        h, nc = _stage_prefill_chunk(sp, kind, cfg, h, cache, block_tables,
                                     start, kv_len)
        new_caches.append(nc)
    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    last = jax.lax.dynamic_slice_in_dim(h, valid - 1, 1, axis=1)[:, 0]
    tie = params["embed"]["emb"] if cfg.tie_embeddings else None
    logits = unembed(params.get("unembed"), last, tie_to=tie,
                     softcap=cfg.logit_softcap, logical_vocab=cfg.vocab_size)
    return logits, new_caches


# ---------------------------------------------------------------------------
# Cache construction (zeros — used by serving and by the dry-run specs)
# ---------------------------------------------------------------------------

def _kv_cache_zeros(cfg: ModelConfig, bsz: int, cache_size: int):
    if cfg.attn_type == "mla":
        m = cfg.mla
        return KVCache(
            jnp.zeros((bsz, cache_size, m.kv_lora_rank), compute_dtype()),
            jnp.zeros((bsz, cache_size, m.qk_rope_head_dim), compute_dtype()))
    from repro.models.attention import padded_heads
    hd = cfg.resolved_head_dim
    kv = padded_heads(cfg)[1]
    return KVCache(
        jnp.zeros((bsz, cache_size, kv, hd), compute_dtype()),
        jnp.zeros((bsz, cache_size, kv, hd), compute_dtype()))


def _mamba_cache_zeros(cfg: ModelConfig, bsz: int):
    d_inner, n_heads, bc_dim = mamba_dims(cfg)
    s = cfg.ssm
    return MambaCache(
        ssm=jnp.zeros((bsz, n_heads, s.head_dim, s.d_state), jnp.float32),
        conv_x=jnp.zeros((bsz, s.d_conv - 1, d_inner), compute_dtype()),
        conv_bc=jnp.zeros((bsz, s.d_conv - 1, bc_dim), compute_dtype()))


def _stack(tree, n: int):
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


def init_cache(cfg: ModelConfig, bsz: int, cache_size: int):
    caches = []
    for kind, n in stage_plan(cfg):
        if kind == "mamba":
            caches.append(_stack(_mamba_cache_zeros(cfg, bsz), n))
        elif kind == "hybrid":
            k = cfg.hybrid_attn_every
            g = n // k
            caches.append({
                "mamba": _stack(_stack(_mamba_cache_zeros(cfg, bsz), k), g),
                "attn": _stack(_kv_cache_zeros(cfg, bsz, cache_size), g),
            })
        else:
            caches.append(_stack(_kv_cache_zeros(cfg, bsz, cache_size), n))
    return caches


def cache_batch_axes(cfg: ModelConfig):
    """Per-stage pytrees mirroring ``init_cache``'s structure whose leaves
    are the index of that leaf's *batch* axis. Scan-stack dims sit in front
    of batch (a dense-stage KV leaf is (L, B, S, KV, D) -> axis 1; hybrid
    mamba leaves are (g, k, B, ...) -> axis 2), so lane splicing must use
    this metadata rather than inferring the axis from shapes."""
    axes = []
    for kind, _ in stage_plan(cfg):
        if kind == "mamba":
            axes.append(jax.tree.map(lambda _: 1, _mamba_cache_zeros(cfg, 1)))
        elif kind == "hybrid":
            axes.append({
                "mamba": jax.tree.map(lambda _: 2,
                                      _mamba_cache_zeros(cfg, 1)),
                "attn": jax.tree.map(lambda _: 1,
                                     _kv_cache_zeros(cfg, 1, 1)),
            })
        else:
            axes.append(jax.tree.map(lambda _: 1, _kv_cache_zeros(cfg, 1, 1)))
    return axes


# ---------------------------------------------------------------------------
# Paged cache construction: one page pool per stage, stacked over layers.
# Leaves are (L, num_pages, page_size, *tail); block tables are shared by
# every layer, so one (B, nblk) table drives the whole stack.
# ---------------------------------------------------------------------------

def _kv_pool_zeros(cfg: ModelConfig, num_pages: int, page_size: int,
                   quant: bool):
    from repro.serving.kvcache import pool_zeros
    if cfg.attn_type == "mla":
        m = cfg.mla
        return KVCache(
            pool_zeros(num_pages, page_size, (m.kv_lora_rank,),
                       compute_dtype(), quant),
            pool_zeros(num_pages, page_size, (m.qk_rope_head_dim,),
                       compute_dtype(), quant))
    from repro.models.attention import padded_heads
    hd = cfg.resolved_head_dim
    kv = padded_heads(cfg)[1]
    return KVCache(
        pool_zeros(num_pages, page_size, (kv, hd), compute_dtype(), quant),
        pool_zeros(num_pages, page_size, (kv, hd), compute_dtype(), quant))


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     quant: bool = False):
    if cfg.family not in ("dense", "moe") or cfg.modality != "text":
        raise NotImplementedError(
            "paged KV covers dense/moe text models; ssm/hybrid state is O(1) "
            "per request and vlm prefixes are not token-addressed")
    return [_stack(_kv_pool_zeros(cfg, num_pages, page_size, quant), n)
            for _, n in stage_plan(cfg)]
