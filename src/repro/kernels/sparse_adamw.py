"""sparse_adamw — fused packed-AdamW update (paper App. D, kernelised).

Packed SHiRA training keeps optimizer state only for the K nonzero values
per matrix. This kernel fuses the whole moment + parameter update over the
packed (…, K) vectors in one pass: 4 reads + 3 writes per element and zero
intermediate HBM traffic (vs ~7 separate elementwise HLO ops).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _adamw_kernel(scal_ref, v_ref, g_ref, m_ref, u_ref,
                  v_out, m_out, u_out):
    lr = scal_ref[0]
    b1 = scal_ref[1]
    b2 = scal_ref[2]
    eps = scal_ref[3]
    wd = scal_ref[4]
    c1 = scal_ref[5]   # 1 - b1**t
    c2 = scal_ref[6]   # 1 - b2**t
    g = g_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    u = b2 * u_ref[...] + (1.0 - b2) * g * g
    mh = m / c1
    uh = u / c2
    delta = mh / (jnp.sqrt(uh) + eps) + wd * v
    v_out[...] = (v - lr * delta).astype(v_out.dtype)
    m_out[...] = m
    u_out[...] = u


def sparse_adamw_blocks(values: jax.Array, grads: jax.Array, mu: jax.Array,
                        nu: jax.Array, scalars: jax.Array, *,
                        block: int = 2048,
                        interpret: bool = False):
    """values/grads/mu/nu: (K,) — pre-padded to a multiple of ``block``.
    scalars: (8,) f32 = [lr, b1, b2, eps, wd, c1, c2, pad]."""
    k = values.shape[0]
    assert k % block == 0, (k, block)
    grid = (k // block,)
    vec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _adamw_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((8,), lambda i: (0,)), vec, vec, vec, vec],
        out_specs=(vec, vec, vec),
        out_shape=(jax.ShapeDtypeStruct((k,), values.dtype),
                   jax.ShapeDtypeStruct((k,), jnp.float32),
                   jax.ShapeDtypeStruct((k,), jnp.float32)),
        interpret=interpret,
    )(scalars, values, grads, mu, nu)


def _adamw_rows_kernel(scal_ref, v_ref, g_ref, m_ref, u_ref,
                       ms_ref, us_ref, v_out, m_out, u_out, *, qmode):
    """One (row-block, K-block) tile of the batched update.

    ``qmode`` selects how the incoming moment refs decode:
      - "f32"/"bf16": plain cast (the per-row scale refs are ignored —
        bf16's exponent range covers AdamW moments directly).
      - "int8": symmetric per-row dequant. ``mu`` decodes as ``q * scale``;
        ``nu`` is stored in the *sqrt domain* (``q = sqrt(nu) / scale``) so
        8 bits cover nu's squared dynamic range — decode squares it back.
    The per-row scales arrive as a (rows, 1) column that broadcasts along
    K. Updated moments always leave in f32; re-encoding happens outside
    the kernel so one kernel serves every storage dtype.
    """
    lr = scal_ref[0]
    b1 = scal_ref[1]
    b2 = scal_ref[2]
    eps = scal_ref[3]
    wd = scal_ref[4]
    c1 = scal_ref[5]   # 1 - b1**t
    c2 = scal_ref[6]   # 1 - b2**t
    g = g_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    if qmode == "int8":
        m_prev = m_ref[...].astype(jnp.float32) * ms_ref[...]
        ru = u_ref[...].astype(jnp.float32) * us_ref[...]
        u_prev = ru * ru
    else:
        m_prev = m_ref[...].astype(jnp.float32)
        u_prev = u_ref[...].astype(jnp.float32)
    m = b1 * m_prev + (1.0 - b1) * g
    u = b2 * u_prev + (1.0 - b2) * g * g
    mh = m / c1
    uh = u / c2
    delta = mh / (jnp.sqrt(uh) + eps) + wd * v
    v_out[...] = (v - lr * delta).astype(v_out.dtype)
    m_out[...] = m
    u_out[...] = u


# Rows per program: a multiple of every dtype's sublane tile (f32 8, bf16
# 16, int8 32), so row blocks of the moments are whole tiles.
_ROW_BLOCK = 32


def sparse_adamw_rows(values: jax.Array, grads: jax.Array, mu: jax.Array,
                      nu: jax.Array, mu_scale, nu_scale,
                      scalars: jax.Array, *, block: int = 2048,
                      interpret: bool = False):
    """Batched fused AdamW over row-stacked packed vectors.

    values/grads: (R, K) with K a multiple of ``block``; R is the flattened
    (adapter, leaf-lead) axis so A adapters update in one launch. mu/nu:
    (R, K) in their storage dtype (f32, bf16, or int8). mu_scale/nu_scale:
    (R,) f32 per-row dequant scales, or None when the storage dtype carries
    values directly. scalars: (8,) as in ``sparse_adamw_blocks``; they ride
    in SMEM as scalar-prefetch operands. Returns (new_values (R, K), mu
    (R, K) f32, nu (R, K) f32).

    Programs take (rows, block) tiles with rows = R when R is small, else
    ``_ROW_BLOCK``; a ragged last row block is padded by Pallas on read
    and clipped on write, which is exact for this elementwise update.
    """
    r, k = values.shape
    assert k % block == 0, (k, block)
    qmode = {jnp.int8: "int8"}.get(jnp.dtype(mu.dtype).type, "f32")
    if mu_scale is None:
        mu_scale = jnp.ones((r,), jnp.float32)
    if nu_scale is None:
        nu_scale = jnp.ones((r,), jnp.float32)
    br = r if r <= _ROW_BLOCK else _ROW_BLOCK
    vec = pl.BlockSpec((br, block), lambda i, j, s: (i, j))
    row = pl.BlockSpec((br, 1), lambda i, j, s: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(r, br), k // block),
        in_specs=[vec, vec, vec, vec, row, row],
        out_specs=(vec, vec, vec),
    )
    return pl.pallas_call(
        functools.partial(_adamw_rows_kernel, qmode=qmode),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((r, k), values.dtype),
                   jax.ShapeDtypeStruct((r, k), jnp.float32),
                   jax.ShapeDtypeStruct((r, k), jnp.float32)),
        interpret=interpret,
    )(scalars, values, grads, mu, nu, mu_scale.reshape(r, 1),
      nu_scale.reshape(r, 1))
