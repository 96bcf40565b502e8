"""Plain float32 reference of the DeepSeek-V2 block, for the CPU tests.

Written from the published description (arXiv:2405.04434 and the
DeepSeek-V2-Lite ``config.json``) in straightforward ``jax.numpy``; it reads
the program's parameter tree for its weights and nothing else of the
program. Every matmul runs at ``Precision.HIGHEST``.

  embed -> [dense layer]*first_dense -> [moe layer]* -> RMSNorm -> head
  layer: h += MLA(RMSNorm(h)); h += FFN(RMSNorm(h))
  MLA:   q = x Wq (heads of nope 128 + rope 64, rotate-half rotary on the
         rope part); [c, k_rope] = x W_dkv, c RMS-normed, k_rope rotary and
         shared by the heads; K_nope = c W_uk, V = c W_uv per head (expanded,
         not absorbed); scores scaled by mscale^2 / sqrt(192) under YaRN.
  FFN:   gated SiLU (dense layers, shared experts); routed experts: softmax
         over all router outputs, top-k, renormalised only where the config
         says, only the held experts contributing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_inv_freq(dim, base, yarn):
    """HF ``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies, in numpy."""
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return extra

    def corr(rot):
        return (dim * math.log(yarn.original_max_position
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return extra / yarn.factor * (1 - keep) + extra * keep


def get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _rope(x, pos, inv, mult):
    """x (B, S, heads, d) rotate-half; pos (S,)."""
    ang = pos[:, None].astype(np.float64) * inv[None]
    cos = jnp.asarray(np.cos(ang) * mult, jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(ang) * mult, jnp.float32)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mla(p, cfg, x):
    m, H = cfg.mla, cfg.num_heads
    B, S, _ = x.shape
    pos = np.arange(S)
    y = cfg.yarn
    inv = yarn_inv_freq(m.qk_rope_head_dim, cfg.rope_theta, y)
    mult = 1.0 if y is None else (get_mscale(y.factor, y.mscale)
                                  / get_mscale(y.factor, y.mscale_all_dim))
    q = _mm(x, p["wq"]).reshape(B, S, H, -1)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = _rope(q_rope, pos, inv, mult)
    ckv = _mm(x, p["w_dkv"])
    c = _rms(ckv[..., :m.kv_lora_rank], p["kv_norm"]["scale"], cfg.norm_eps)
    k_rope = _rope(ckv[..., None, m.kv_lora_rank:], pos, inv, mult)
    k_nope = _mm(c, p["w_uk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = _mm(c, p["w_uv"]).reshape(B, S, H, m.v_head_dim)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= get_mscale(y.factor, y.mscale_all_dim) ** 2
    s = (jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope, precision=HI)
         + jnp.einsum("bqhd,bsxd->bhqs", q_rope, k_rope, precision=HI)) * scale
    causal = pos[:, None] >= pos[None, :]
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqs,bshd->bqhd", a, v, precision=HI).reshape(B, S, -1)
    return _mm(o, p["wo"])


def swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"])) * _mm(x, p["w_up"]),
               p["w_down"])


def routed(p, cfg, x):
    """The held experts' part of the routed sum. x (T, d)."""
    mo = cfg.moe
    probs = jax.nn.softmax(_mm(x, p["w_router"]), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, mo.top_k)
    if mo.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(p["experts_w_up"].shape[0]):
        e = mo.first_held + j
        w = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)       # (T,)
        f = swiglu({"w_gate": p["experts_w_gate"][j],
                    "w_up": p["experts_w_up"][j],
                    "w_down": p["experts_w_down"][j]}, x)
        y = y + w[:, None] * f
    return y


def moe_layer(p, cfg, x):
    """x (T, d): routed part plus the shared experts, once."""
    y = routed(p, cfg, x)
    return y + swiglu(p["shared"], x) if cfg.moe.num_shared else y


def forward(params, cfg, tokens):
    """Logits (B, S, vocab) f32 of the whole sequence."""
    h = jnp.take(params["embed"]["emb"], jnp.asarray(tokens), axis=0)
    B, S, d = h.shape
    kinds = ["dense"] * cfg.moe.first_dense_layers + ["moe"] * (
        cfg.num_layers - cfg.moe.first_dense_layers)
    stages = [params["stages"][0]] * cfg.moe.first_dense_layers + [
        params["stages"][-1]] * (cfg.num_layers - cfg.moe.first_dense_layers)
    index = {"dense": 0, "moe": 0}
    for kind, stage in zip(kinds, stages):
        lp = jax.tree.map(lambda a: a[index[kind]], stage)
        index[kind] += 1
        x = _rms(h, lp["attn_norm"]["scale"], cfg.norm_eps)
        h = h + _mla(lp["attn"], cfg, x)
        x = _rms(h, lp["mlp_norm"]["scale"], cfg.norm_eps)
        if kind == "dense":
            h = h + swiglu(lp["mlp"], x)
        else:
            h = h + moe_layer(lp["moe"], cfg, x.reshape(B * S, d)).reshape(
                B, S, d)
    h = _rms(h, params["final_norm"]["scale"], cfg.norm_eps)
    return _mm(h, params["unembed"]["lm_head"])[..., :cfg.vocab_size]
