"""Pure-jnp oracles for every kernel — the ground truth the Pallas kernels
are validated against (tests sweep shapes/dtypes in interpret mode)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sidedelta_ref(x: jax.Array, rows: jax.Array, cols: jax.Array,
                  vals: jax.Array, ids: jax.Array, m: int) -> jax.Array:
    """x: (B, S, n); rows/cols/vals: (A, K); ids: (B,) with -1 = no adapter.
    Returns (B, S, m) f32: delta[b] = x[b] @ dW_{ids[b]} with dW the sparse
    matrix scattered from the packed (row, col, val) triples (duplicate
    pairs sum). dW is never built: each request gathers its inputs at its
    adapter's rows, scales them by the values and scatter-adds them into
    its columns, in O(B * S * K) memory. Plain jnp, so differentiable."""
    S = x.shape[1]
    slot = jnp.maximum(ids, 0)

    def one_request(xb, r, c, v):
        xs = xb.astype(jnp.float32)[:, r] * v.astype(jnp.float32)
        return jnp.zeros((S, m), jnp.float32).at[:, c].add(xs)

    delta = jax.vmap(one_request)(x, rows[slot].astype(jnp.int32),
                                  cols[slot].astype(jnp.int32), vals[slot])
    return jnp.where((ids >= 0)[:, None, None], delta, 0.0)


def sidedelta_int8_ref(x: jax.Array, rows: jax.Array, cols: jax.Array,
                       vals_q: jax.Array, scale: jax.Array, ids: jax.Array,
                       m: int) -> jax.Array:
    """int8-table oracle: vals_q (A, K) int8 with per-adapter scale (A,)
    f32 dequantized exactly as the kernel does (q * scale in f32) before
    the reference contraction."""
    vals = vals_q.astype(jnp.float32) * scale[:, None].astype(jnp.float32)
    return sidedelta_ref(x, rows.astype(jnp.int32), cols.astype(jnp.int32),
                         vals, ids, m)


def masked_update_ref(w: jax.Array, mask: jax.Array, vals: jax.Array,
                      alpha: float = 1.0) -> jax.Array:
    out = w.astype(jnp.float32) + alpha * mask.astype(jnp.float32) \
        * vals.astype(jnp.float32)
    return out.astype(w.dtype)


def sparse_adamw_ref(values, grads, mu, nu, *, lr, b1, b2, eps, wd, step):
    g = grads.astype(jnp.float32)
    v = values.astype(jnp.float32)
    m = b1 * mu + (1 - b1) * g
    u = b2 * nu + (1 - b2) * g * g
    c1 = 1.0 - b1 ** step
    c2 = 1.0 - b2 ** step
    delta = (m / c1) / (jnp.sqrt(u / c2) + eps) + wd * v
    return (v - lr * delta).astype(values.dtype), m, u


def flash_prefill_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Skv, KV, D) -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Skv)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: int) -> jax.Array:
    """q: (B, KV, G, D); k/v: (B, S, KV, D). Masked softmax attention."""
    B, KV, G, D = q.shape
    S = k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, None, None, :] < kv_len
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
