"""Plain float32 reference of the dense decoder the configurations run.

Written from the published block description in straightforward
``jax.numpy``; it imports nothing of the program and takes nothing the
program made. Weights and adapter packs are made again from the run's
seed by ``weights.py``, one layer at a time, so that it fits beside
nothing else on the chip once the program's state is freed.

Block (pre-norm): h += Attn(RMSNorm(h)); h += MLP(RMSNorm(h)).
  Attn: grouped-query attention, rotary embeddings on q and k in the
        rotate-half layout, causal, optional q/k/v biases.
  MLP:  GELU (tanh form) for ``act = "gelu"``; gated SiLU for "silu".
The norm kind, epsilon and rotary base are the configuration's
``assumed`` values.

Matmuls run at ``Precision.HIGHEST``: a float32 matmul on a TPU otherwise
runs in bfloat16. ``control=True`` computes the same model with every
matmul operand rounded to float8 (e4m3, one scale per row or column over
the contraction), the step below the program's bfloat16 compute.
"""
from __future__ import annotations

import functools
import math
import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
Q_CHUNK = 512


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, control):
    """x (..., n) @ w (n, m)."""
    if control:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (B, S, heads, hd), rotate-half layout."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv           # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(cfg, control, h, w):
    B, S, d = h.shape
    H, KV, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["assumed"]["norm_eps"], cfg["assumed"]["rope_theta"]
    pos = jnp.arange(S)
    x = _rms(h, w["attn_norm/scale"], eps)

    def proj(name, bias):
        y = _mm(x, w[name], control)
        return y + w[bias] if cfg["qkv_bias"] else y

    q = _rope(proj("attn/wq", "attn/bq").reshape(B, S, H, hd), pos, theta)
    k = _rope(proj("attn/wk", "attn/bk").reshape(B, S, KV, hd), pos, theta)
    v = proj("attn/wv", "attn/bv").reshape(B, S, KV, hd)
    G = H // KV
    k = jnp.repeat(k, G, axis=2)                            # (B, S, H, hd)
    v = jnp.repeat(v, G, axis=2)
    outs = []
    for c0 in range(0, S, Q_CHUNK):                         # query chunks
        qc = q[:, c0:c0 + Q_CHUNK]
        kk, qq = (_q8(k, -1), _q8(qc, -1)) if control else (k, qc)
        s = jnp.einsum("bqhd,bshd->bhqs", qq, kk, precision=HI)
        s = s / math.sqrt(hd)
        causal = (c0 + jnp.arange(qc.shape[1]))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        vv, pp = (_q8(v, 1), _q8(p, -1)) if control else (v, p)
        outs.append(jnp.einsum("bhqs,bshd->bqhd", pp, vv, precision=HI))
    a = jnp.concatenate(outs, axis=1).reshape(B, S, H * hd)
    h = h + _mm(a, w["attn/wo"], control)
    x = _rms(h, w["mlp_norm/scale"], eps)
    if cfg["act"] == "silu":
        f = jax.nn.silu(_mm(x, w["mlp/w_gate"], control)) \
            * _mm(x, w["mlp/w_up"], control)
    else:
        f = _gelu(_mm(x, w["mlp/w_up"], control))
    return h + _mm(f, w["mlp/w_down"], control)


def _with_adapter(w, pack):
    out = dict(w)
    for name, (idx, vals) in pack.items():
        n, m = w[name].shape
        out[name] = w[name].reshape(-1).at[idx].add(vals).reshape(n, m)
    return out


def _head(cfg, control, h, g, start, count):
    x = jax.lax.dynamic_slice_in_dim(h, start, count, axis=1)
    x = _rms(x, g["final_norm/scale"], cfg["assumed"]["norm_eps"])
    return _mm(x, g["unembed/lm_head"], control)[..., :cfg["vocab_size"]]


_JIT: dict = {}


def _jit(name, cfg, control, fn, **static):
    key = (name, W._cfg_key(cfg), control, tuple(sorted(static.items())))
    if key not in _JIT:
        _JIT[key] = jax.jit(functools.partial(fn, cfg, control),
                            static_argnames=tuple(static))
    return _JIT[key]


def logits(cfg: dict, seed: int, adapter: int, mix: dict,
           tokens, start: int, count: int, control: bool = False):
    """Logits (B, count, vocab) f32 at positions [start, start + count) of
    ``tokens`` (B, S) int32; ``count`` is a compiled size, ``start`` is
    not. ``adapter`` is the pack index, made as the mix's ``density``,
    ``std`` and ``targets`` say.
    Positions after ``start + count`` may hold padding: attention is
    causal, so they change nothing that is read."""
    g = W.global_weights(cfg, seed)
    h = jnp.take(g["embed/emb"], jnp.asarray(tokens), axis=0)
    block = _jit("block", cfg, control, _block)
    add = _jit("adapter", cfg, False, lambda cfg, c, w, p: _with_adapter(w, p))
    for layer in range(cfg["num_layers"]):
        w = add(W.layer_weights(cfg, seed, layer),
                W.pack_for_layer(cfg, seed, adapter, mix["density"],
                                 mix["std"], mix["targets"], layer))
        h = block(h, w)
    head = _jit("head", cfg, control, _head, count=count)
    return head(h, g, jnp.int32(start), count=count)


def gaps(ref_logits, chosen) -> np.ndarray:
    """How far below the reference's best each chosen token's logit lies:
    ref_logits (..., V), chosen (...) int -> (...) f32, 0 where the chosen
    token is the reference's first choice."""
    ref = np.asarray(ref_logits, np.float32)
    c = np.asarray(chosen, np.int64)
    return ref.max(-1) - np.take_along_axis(ref, c[..., None], -1)[..., 0]
