"""jit'd wrappers: host-side prep + pallas_call dispatch for every kernel.

``bucket_updates`` is the host pre-pass for scatter_apply: it converts a
packed (flat_idx, values) adapter into per-VMEM-tile buckets. It runs once
per adapter at registration time (numpy), not per switch.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_decode import flash_decode_blocks
from repro.kernels.masked_update import masked_update_tiles
from repro.kernels.scatter_apply import scatter_apply_tiles
from repro.kernels.sidedelta import (sidedelta_rows,  # noqa: F401
                                     sidedelta_table)
from repro.kernels.sparse_adamw import sparse_adamw_blocks, sparse_adamw_rows


# ---------------------------------------------------------------------------
# scatter_apply
# ---------------------------------------------------------------------------

def bucket_updates(flat_idx: np.ndarray, vals: np.ndarray, n: int, m: int,
                   bn: int = 256, bm: int = 256
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Bucket packed updates by (bn, bm) tile.

    Returns (counts (nt_i, nt_j), rows, cols, vals) with rows/cols tile-local
    and padded to the max bucket size (zero-padded entries are masked out by
    the per-tile count in the kernel)."""
    flat_idx = np.asarray(flat_idx, np.int64)
    vals = np.asarray(vals, np.float32)
    r = flat_idx // m
    c = flat_idx % m
    ti = r // bn
    tj = c // bm
    nt_i, nt_j = n // bn, m // bm
    tile_id = ti * nt_j + tj
    order = np.argsort(tile_id, kind="stable")
    tile_id_s = tile_id[order]
    counts = np.bincount(tile_id_s, minlength=nt_i * nt_j)
    u = max(int(counts.max()), 1)
    rows = np.zeros((nt_i * nt_j, u), np.int32)
    cols = np.zeros((nt_i * nt_j, u), np.int32)
    vbuf = np.zeros((nt_i * nt_j, u), np.float32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    r_s, c_s, v_s = r[order], c[order], vals[order]
    for t in range(nt_i * nt_j):
        s, e = starts[t], starts[t + 1]
        k = e - s
        if k:
            rows[t, :k] = (r_s[s:e] % bn).astype(np.int32)
            cols[t, :k] = (c_s[s:e] % bm).astype(np.int32)
            vbuf[t, :k] = v_s[s:e]
    return (counts.reshape(nt_i, nt_j).astype(np.int32),
            rows.reshape(nt_i, nt_j, u), cols.reshape(nt_i, nt_j, u),
            vbuf.reshape(nt_i, nt_j, u))


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def scatter_apply(w, counts, rows, cols, vals, alpha, *, bn=256, bm=256,
                  interpret=False):
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1)
    return scatter_apply_tiles(w, counts, rows, cols, vals, alpha,
                               bn=bn, bm=bm, interpret=interpret)


# ---------------------------------------------------------------------------
# sidedelta (multi-tenant per-request adapters)
# ---------------------------------------------------------------------------

def quantize_table(vals: np.ndarray) -> Tuple[np.ndarray, float]:
    """Symmetric int8 quantization of one adapter's (K,) value table.
    Returns (q int8, scale) with q * scale ~= vals; scale 1.0 for an
    all-zero table so padded slots dequantize to exact zeros."""
    vals = np.asarray(vals, np.float32)
    amax = float(np.max(np.abs(vals))) if vals.size else 0.0
    scale = amax / 127.0 if amax > 0 else 1.0
    q = np.clip(np.rint(vals / scale), -127, 127).astype(np.int8)
    return q, scale


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def sidedelta(x, rows, cols, vals, ids, *, m, blocks=None, scale=None,
              interpret=False):
    """Batched per-request sparse delta: (B, S, m) f32 with
    delta[b] = x[b] @ dW_{ids[b]} (ids[b] < 0 -> zeros). ``vals`` may be
    int8 with per-adapter ``scale`` (dequantised inside the kernel);
    ``blocks`` comes with tables laid out by ``sidedelta_table``."""
    return sidedelta_rows(x, rows, cols, vals, ids, m, blocks=blocks,
                          scale=scale, interpret=interpret)


# ---------------------------------------------------------------------------
# masked_update
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def masked_update(w, mask, vals, alpha, *, bn=256, bm=256, interpret=False):
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1)
    return masked_update_tiles(w, mask, vals, alpha, bn=bn, bm=bm,
                               interpret=interpret)


# ---------------------------------------------------------------------------
# sparse_adamw
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("lr", "b1", "b2", "eps", "wd", "block",
                                    "interpret"))
def sparse_adamw(values, grads, mu, nu, step, *, lr=1e-3, b1=0.9, b2=0.999,
                 eps=1e-8, wd=0.0, block=2048, interpret=False):
    k = values.shape[0]
    pad = (-k) % block
    if pad:
        z = lambda x: jnp.pad(x, (0, pad))
        values, grads, mu, nu = z(values), z(grads), z(mu), z(nu)
    stepf = step.astype(jnp.float32)
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(b1, jnp.float32),
        jnp.asarray(b2, jnp.float32), jnp.asarray(eps, jnp.float32),
        jnp.asarray(wd, jnp.float32),
        1.0 - jnp.asarray(b1, jnp.float32) ** stepf,
        1.0 - jnp.asarray(b2, jnp.float32) ** stepf,
        jnp.zeros((), jnp.float32)])
    v, m, u = sparse_adamw_blocks(values, grads, mu, nu, scalars,
                                  block=block, interpret=interpret)
    if pad:
        v, m, u = v[:k], m[:k], u[:k]
    return v, m, u


def _adamw_scalars(step, lr, b1, b2, eps, wd):
    stepf = step.astype(jnp.float32)
    return jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(b1, jnp.float32),
        jnp.asarray(b2, jnp.float32), jnp.asarray(eps, jnp.float32),
        jnp.asarray(wd, jnp.float32),
        1.0 - jnp.asarray(b1, jnp.float32) ** stepf,
        1.0 - jnp.asarray(b2, jnp.float32) ** stepf,
        jnp.zeros((), jnp.float32)])


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "wd", "block",
                                    "interpret"))
def sparse_adamw_batched(values, grads, mu, nu, step, *, lr, b1=0.9,
                         b2=0.999, eps=1e-8, wd=0.0, mu_scale=None,
                         nu_scale=None, block=2048, interpret=False):
    """Batched fused AdamW over (R, K) row-stacked packed values.

    Rows are independent (adapter, leaf) vectors, so A adapters update in
    one kernel launch. ``mu``/``nu`` may be stored f32, bf16, or int8 with
    per-row ``mu_scale``/``nu_scale`` (see ``sparse_adamw_rows`` for the
    int8 encoding); updated moments are always returned f32 — the caller
    re-encodes. ``lr`` is traced (it follows a schedule); ``step`` is the
    1-based optimizer step used for bias correction."""
    r, k = values.shape
    pad = (-k) % block
    if pad:
        z = lambda x: jnp.pad(x, ((0, 0), (0, pad)))
        values, grads, mu, nu = z(values), z(grads), z(mu), z(nu)
    scalars = _adamw_scalars(step, lr, b1, b2, eps, wd)
    v, m, u = sparse_adamw_rows(values, grads, mu, nu, mu_scale, nu_scale,
                                scalars, block=block, interpret=interpret)
    if pad:
        v, m, u = v[:, :k], m[:, :k], u[:, :k]
    return v, m, u


# ---------------------------------------------------------------------------
# flash_decode
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sb", "interpret"))
def flash_decode(q, k, v, kv_len, *, sb=512, interpret=False):
    """q: (B, KV, G, D); k/v: (B, S, KV, D); kv_len scalar int32."""
    S = k.shape[1]
    pad = (-S) % sb
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)
    return flash_decode_blocks(q, k, v, kv_len, sb=sb, interpret=interpret)


# ---------------------------------------------------------------------------
# flash_prefill
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("bq", "bkv", "causal", "interpret"))
def flash_prefill(q, k, v, *, bq=512, bkv=512, causal=True, interpret=False):
    """q: (B, Sq, H, D); k/v: (B, Skv, KV, D). Returns (B, Sq, H, D).

    Handles GQA layout conversion + padding; Sq/Skv padded to the block
    sizes (padded kv masked by causality when causal; for bidirectional use
    only with already-aligned Skv)."""
    from repro.kernels.flash_prefill import flash_prefill_blocks
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    pq = (-Sq) % bq
    pk = (-k.shape[1]) % bkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qg = jnp.moveaxis(q.reshape(B, Sq + pq, KV, G, D), 1, 3)  # (B,KV,G,Sq,D)
    out = flash_prefill_blocks(qg, k, v, bq=bq, bkv=bkv, causal=causal,
                               interpret=interpret)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq + pq, H, D)
    return out[:, :Sq]
