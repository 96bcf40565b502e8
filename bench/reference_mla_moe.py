"""Plain float32 reference of the MLA + MoE block (DeepSeek-V2).

Written from the published description (arXiv:2405.04434 and the
configuration's ``config.json``) in straightforward ``jax.numpy``; it
imports nothing of the program and takes nothing the program made.
Weights and packs are made again from the run's seed by
``weights_mla_moe.py``, one layer at a time, once the program's state is
freed.

Block (pre-norm RMSNorm, eps ``rms_norm_eps``):
  h += MLA(RMSNorm(h)); h += FFN(RMSNorm(h))
  MLA: q = x Wq, per head nope (``qk_nope_head_dim``) and rope
       (``qk_rope_head_dim``) parts; [c, k_rope] = x W_dkv, c RMS-normed;
       K_nope = c W_uk and V = c W_uv expanded per head (not absorbed);
       k_rope one head shared by all; rotate-half rotary on the rope
       parts with YaRN's frequencies (``rope_scaling``); scores times
       mscale(factor, mscale_all_dim)^2 / sqrt(nope + rope); causal.
  FFN: layers before ``first_k_dense_replace``: gated SiLU of
       ``intermediate_size``. Then MoE: softmax over the ``router_experts``
       router outputs, top ``num_experts_per_tok``, renormalised only with
       ``norm_topk_prob``, times ``routed_scaling_factor``; the held experts
       (``first_held_expert`` on, ``n_routed_experts`` of them) each add
       their gated SiLU times their weight, the others add nothing (they
       lie on other chips); the shared experts (one SwiGLU of
       ``n_shared_experts`` x ``moe_intermediate_size``) are added once.

Matmuls run at ``Precision.HIGHEST``. ``control=True`` computes the same
model with every matmul operand rounded to float8, as ``reference.py``'s
control does.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import harness as H
import reference as Rd
import weights as W
import weights_mla_moe as WM

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 512


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """YaRN's inverse frequencies of the rope dims, as the published
    ``DeepseekV2YarnRotaryEmbedding`` builds them."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    y = cfg["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return extra / y["factor"] * ramp + extra * (1 - ramp)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, pos, cfg):
    """x (B, S, heads, d) rotate-half."""
    y = cfg["rope_scaling"]
    mult = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"],
                                                      y["mscale_all_dim"])
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(yarn_inv_freq(cfg),
                                                         jnp.float32)
    cos, sin = jnp.cos(ang)[:, None] * mult, jnp.sin(ang)[:, None] * mult
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, up, gate, down, control):
    return Rd._mm(jax.nn.silu(Rd._mm(x, gate, control))
                  * Rd._mm(x, up, control), down, control)


def _attn(cfg, control, x, w):
    B, S, _ = x.shape
    H = cfg["num_attention_heads"]
    nope, rope, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["kv_lora_rank"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(S)
    q = Rd._mm(x, w["attn/wq"], control).reshape(B, S, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, cfg)], -1)
    ckv = Rd._mm(x, w["attn/w_dkv"], control)
    c = Rd._rms(ckv[..., :r], w["attn/kv_norm/scale"], eps)
    k_rope = _rope(ckv[..., None, r:], pos, cfg)              # (B, S, 1, rope)
    k_nope = Rd._mm(c, w["attn/w_uk"], control).reshape(B, S, H, nope)
    v = Rd._mm(c, w["attn/w_uv"], control).reshape(B, S, H, -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))],
                        -1)
    y = cfg["rope_scaling"]
    scale = (nope + rope) ** -0.5 * _mscale(y["factor"],
                                            y["mscale_all_dim"]) ** 2
    outs = []
    for c0 in range(0, S, Q_CHUNK):
        qc = q[:, c0:c0 + Q_CHUNK]
        kk, qq = (Rd._q8(k, -1), Rd._q8(qc, -1)) if control else (k, qc)
        s = jnp.einsum("bqhd,bshd->bhqs", qq, kk, precision=HI) * scale
        causal = (c0 + jnp.arange(qc.shape[1]))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        vv, pp = (Rd._q8(v, 1), Rd._q8(p, -1)) if control else (v, p)
        outs.append(jnp.einsum("bhqs,bshd->bqhd", pp, vv, precision=HI))
    a = jnp.concatenate(outs, axis=1).reshape(B, S, -1)
    return Rd._mm(a, w["attn/wo"], control)


def _experts(x, w, control):
    """x (E, T, n) @ w (E, n, m): every held expert on every token."""
    if control:
        x, w = Rd._q8(x, -1), Rd._q8(w, -2)
    return jnp.einsum("etn,enm->etm", x, w, precision=HI)


def _moe(cfg, control, x, w):
    """x (T, d): the held experts' routed part plus the shared experts."""
    probs = jax.nn.softmax(Rd._mm(x, w["moe/w_router"], control), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * cfg["routed_scaling_factor"]
    y = _swiglu(x, w["moe/shared/w_up"], w["moe/shared/w_gate"],
                w["moe/shared/w_down"], control)
    E = cfg["n_routed_experts"]
    held = cfg["first_held_expert"] + jnp.arange(E)
    gate = jnp.sum(jnp.where(top_i[None] == held[:, None, None],
                             top_p[None], 0.0), axis=-1)           # (E, T)
    xe = jnp.broadcast_to(x[None], (E,) + x.shape)
    f = jax.nn.silu(_experts(xe, w["moe/experts_w_gate"], control)) \
        * _experts(xe, w["moe/experts_w_up"], control)
    f = _experts(f, w["moe/experts_w_down"], control)
    return y + jnp.sum(gate[..., None] * f, axis=0)


def _block(cfg, control, h, w, dense):
    B, S, d = h.shape
    eps = cfg["rms_norm_eps"]
    h = h + _attn(cfg, control, Rd._rms(h, w["attn_norm/scale"], eps), w)
    x = Rd._rms(h, w["mlp_norm/scale"], eps)
    if dense:
        return h + _swiglu(x, w["mlp/w_up"], w["mlp/w_gate"],
                           w["mlp/w_down"], control)
    return h + _moe(cfg, control, x.reshape(B * S, d), w).reshape(B, S, d)


def _with_adapter(w, pack):
    out = dict(w)
    for name, (idx, vals) in pack.items():
        *lead, n, m = w[name].shape
        flat = w[name].reshape(-1, n * m)
        flat = jax.vmap(lambda row, i, v: row.at[i].add(v))(
            flat, idx.reshape(flat.shape[0], -1),
            vals.reshape(flat.shape[0], -1))
        out[name] = flat.reshape(w[name].shape)
    return out


def _head(cfg, control, h, g, start, count):
    x = jax.lax.dynamic_slice_in_dim(h, start, count, axis=1)
    x = Rd._rms(x, g["final_norm/scale"], cfg["rms_norm_eps"])
    return Rd._mm(x, g["unembed/lm_head"], control)[..., :cfg["vocab_size"]]


_JIT: dict = {}


def _jit(name, cfg, control, fn, **static):
    key = (name, W._cfg_key(cfg), control, tuple(sorted(static.items())))
    if key not in _JIT:
        _JIT[key] = jax.jit(functools.partial(fn, cfg, control),
                            static_argnames=tuple(static))
    return _JIT[key]


def logits(cfg: dict, seed: int, adapter: int, mix: dict, tokens,
           start: int, count: int, control: bool = False):
    """Logits (B, count, vocab) f32 at positions [start, start + count) of
    ``tokens`` (B, S) int32, under pack ``adapter``. Positions after
    ``start + count`` may hold padding: attention is causal."""
    g = WM.global_weights(cfg, seed)
    h = jnp.take(g["embed/emb"], jnp.asarray(tokens), axis=0)
    add = _jit("adapter", cfg, False, lambda cfg, c, w, p: _with_adapter(w, p))
    for prefix, layer in WM.layer_kinds(cfg):
        dense = prefix == WM.DENSE
        block = _jit("block", cfg, control, _block, dense=dense)
        w = add(WM.layer_weights(cfg, seed, prefix, layer),
                WM.pack_for_layer(cfg, seed, adapter, mix["density"],
                                  mix["std"], mix["targets"], prefix, layer))
        h = block(h, w, dense=dense)
    head = _jit("head", cfg, control, _head, count=count)
    return head(h, g, jnp.int32(start), count=count)


def logit_gaps(cfg: dict, mix: dict, seed: int, served: List[H.Served],
               control: bool = False) -> Dict[str, float]:
    """``harness.logit_gaps`` for this block: the widest gap by which a
    served token's logit lies below the reference's best, each request
    run through the reference on its own at one padded width (the longest
    bucket plus the longest output), so that one shape compiles. NaN where
    a request served nothing."""
    count = int(mix["output"]["max"])
    widest, widest_ctrl, n = 0.0, 0.0, 0
    for s in served:
        if not len(s.tokens):
            return {"logit_gap": float("nan"), "control_gap": float("nan"),
                    "tokens": 0, "requests": len(served)}
        start = len(s.prompt) - 1
        seq = np.concatenate([s.prompt, s.tokens[:-1]])
        toks = np.zeros((1, H._pad_to(max(mix["buckets"]) + count, 256)),
                        np.int32)
        toks[0, :len(seq)] = seq
        tlen = len(s.tokens)
        ref = np.asarray(logits(cfg, seed, s.adapter, mix, toks, start,
                                count))[:, :tlen]
        widest = max(widest, float(Rd.gaps(ref, s.tokens[None]).max()))
        n += tlen
        if control:
            ctl = np.asarray(logits(cfg, seed, s.adapter, mix, toks, start,
                                    count, control=True))[:, :tlen]
            widest_ctrl = max(widest_ctrl,
                              float(Rd.gaps(ref, ctl.argmax(-1)).max()))
    out = {"logit_gap": widest if n else float("nan"), "tokens": n,
           "requests": len(served)}
    if control:
        out["control_gap"] = widest_ctrl
    return out


def weights_err(cfg: dict, mix: dict, seed: int, params, adapter: int,
                control: bool = False) -> float:
    """Worst adapted matrix after the last switch, every expert matrix
    included: max |program - ref| over the layer's entries, as a share of
    the reference's RMS, where ref is the base plus the active adapter's
    nonzeros, made again from the seed in float32. ``control=True`` reads
    the same for the reference computed in bfloat16."""
    key_w = W.root_key(seed, W.WEIGHTS_SALT)
    key_p = W.root_key(seed, W.PACKS_SALT)
    density, std = mix["density"], mix["std"]
    worst = 0.0
    for prefix in (WM.DENSE, WM.MOE):
        lv = [leaf for leaf in WM.pack_leaves(cfg, mix["targets"])
              if leaf.path.startswith(prefix)]

        def err(stacks, kw, kp, layer, adapter, lv=lv):
            """The worst of the stage's adapted matrices in one layer."""
            worst = jnp.float32(0.0)
            for leaf in lv:
                prog = stacks[leaf.path][layer]
                base = W.leaf_value(kw, leaf, layer)
                idx, vals = WM.pack_layer(kp, adapter, leaf, layer, density,
                                          std)
                ref = _with_adapter({"w": base}, {"w": (idx, vals)})["w"]
                if control:
                    prog = _with_adapter(
                        {"w": base.astype(jnp.bfloat16)},
                        {"w": (idx, vals.astype(jnp.bfloat16))})["w"]
                diff = jnp.max(jnp.abs(prog.astype(jnp.float32) - ref))
                rms = jnp.sqrt(jnp.mean(ref * ref))
                worst = jnp.maximum(worst, diff / rms)
            return worst

        fn = W._jitted(("mla_moe_err", W._cfg_key(cfg), prefix, density, std,
                        tuple(mix["targets"]), control),
                       lambda err=err: jax.jit(err))
        stacks = {leaf.path: H._at_path(params, leaf.path) for leaf in lv}
        for layer in range(WM.stage_layers(cfg, prefix)):
            worst = max(worst, float(fn(stacks, key_w, key_p, layer,
                                        adapter)))
    return worst
