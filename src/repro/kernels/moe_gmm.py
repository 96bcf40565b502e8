"""Grouped matmul over the experts a chip holds (dropless MoE).

The expert layer (``models/moe.py``) lays the token-choices routed to its
held experts out in row tiles of ``tm``: choices sorted by expert, each
expert's run starting on a tile boundary, so every tile belongs to one
expert (``tile_expert``). Only the first ``n_tiles`` tiles hold choices;
the grid visits those alone (a dynamic grid extent), so no work is spent
on the room kept for the worst case, and rows past ``n_tiles`` tiles are
left unwritten. Consecutive tiles of one expert keep its weight block in
VMEM: each held expert's weights are read once per output column block.

``gated=True`` (two weight stacks, gate and up) computes the SwiGLU
hidden ``silu(x @ w_gate) * (x @ w_up)``; one stack computes ``x @ w``.
Weights may be float32: blocks are cast to the compute dtype in VMEM, so
the f32 master weights are read once and never converted in HBM.

A weight stack may also be the whole (L, E, K, N) stack of a layer scan,
with ``layer`` naming the layer: the kernel then reads that layer's
blocks in place, where a slice taken in the scan would be copied out of
the stack before the call (XLA cannot fuse a slice into a custom call).

The Pallas call is named ``moe_gmm``, so its device events can be found
in a profiler trace. Its gradient runs the same grouped layout through
the ``megablox`` grouped matmuls that ship with JAX (``gmm`` for the rows,
``tgmm`` for the weights), so training pays for the rows each expert
holds, not for every row once per expert.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as mb_gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as mb_tgmm


def _col_tile(n: int) -> int:
    for tn in (512, 256, 128):
        if n % tn == 0:
            return tn
    return n


def _silu(g):
    return g / (1.0 + jnp.exp(-g))


def _kernel(ly_ref, te_ref, nt_ref, x_ref, *refs, gated, cd, precision):
    del ly_ref, te_ref
    o_ref = refs[-1]

    def mm(w_ref):
        return jnp.dot(x_ref[...].astype(cd), w_ref[...].astype(cd),
                       preferred_element_type=jnp.float32,
                       precision=precision)

    @pl.when(pl.program_id(1) < nt_ref[0])
    def _():
        y = _silu(mm(refs[0])) * mm(refs[1]) if gated else mm(refs[0])
        o_ref[...] = y.astype(o_ref.dtype)


def _pallas(x, ws, layer, tile_expert, n_tiles, tm, out_dtype, cd,
            interpret):
    M, K = x.shape
    N = ws[0].shape[-1]
    tn = _col_tile(N)
    # on the chip the grid's tile extent is the live count; the interpreter
    # walks every tile and the kernel skips the dead ones
    tiles = M // tm if interpret else n_tiles
    x_spec = pl.BlockSpec((tm, K), lambda n, t, ly, te, nt: (t, 0))
    w_spec = pl.BlockSpec((None, None, K, tn),
                          lambda n, t, ly, te, nt: (ly[0], te[t], 0, n))
    precision = (jax.lax.Precision.HIGHEST if cd == jnp.float32 else None)
    return pl.pallas_call(
        functools.partial(_kernel, gated=len(ws) == 2, cd=cd,
                          precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, tiles),
            in_specs=[x_spec] + [w_spec] * len(ws),
            out_specs=pl.BlockSpec((tm, tn), lambda n, t, ly, te, nt: (t, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
    )(layer.reshape(1), tile_expert, n_tiles.reshape(1), x, *ws)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _gmm(x, ws, layer, tile_expert, n_tiles, tm, out_dtype, cd, interpret):
    return _pallas(x, ws, layer, tile_expert, n_tiles, tm, out_dtype, cd,
                   interpret)


def _gmm_fwd(x, ws, layer, tile_expert, n_tiles, tm, out_dtype, cd,
             interpret):
    out = _pallas(x, ws, layer, tile_expert, n_tiles, tm, out_dtype, cd,
                  interpret)
    return out, (x, ws, layer, tile_expert, n_tiles)


def _gmm_bwd(tm, out_dtype, cd, interpret, res, g):
    x, ws, layer, tile_expert, n_tiles = res
    M, K = x.shape
    E, N = ws[0].shape[1], ws[0].shape[-1]
    live = jnp.arange(M // tm) < n_tiles
    # the tile layout is megablox's grouped layout: experts in order, each
    # run a whole number of tiles; rows past the live tiles are no group's
    sizes = (jnp.zeros((E,), jnp.int32).at[tile_expert].add(
        live.astype(jnp.int32)) * tm)
    tiling = (tm, 128 if K % 128 == 0 else K, 128 if N % 128 == 0 else N)
    back = (tm, tiling[2], tiling[1])           # (m, k, n) of the row grads
    w = [jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False).astype(cd)
         for v in ws]
    xc = x.astype(cd)

    def rows(lhs, rhs, til, **kw):
        return mb_gmm(lhs, rhs, sizes, jnp.float32, til,
                      interpret=interpret, **kw)

    g = g.astype(jnp.float32)
    if len(ws) == 2:
        a, b = rows(xc, w[0], tiling), rows(xc, w[1], tiling)
        sig = jax.nn.sigmoid(a)
        grads = [g * b * sig * (1 + a * (1 - sig)), g * a * sig]
    else:
        grads = [g]
    dx = jnp.zeros((M, K), jnp.float32)
    dws = []
    for gr, wi, v in zip(grads, w, ws):
        gr = gr.astype(cd)
        dx = dx + rows(gr, wi, back, transpose_rhs=True)
        dw = mb_tgmm(xc.T, gr, sizes, jnp.float32, tiling,
                     num_actual_groups=E, interpret=interpret)
        dws.append(jax.lax.dynamic_update_index_in_dim(
            jnp.zeros(v.shape, v.dtype), dw.astype(v.dtype), layer, 0))
    # rows past the live tiles were never written
    dx = jnp.where(jnp.repeat(live, tm)[:, None], dx, 0.0)
    return dx.astype(x.dtype), tuple(dws), None, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def moe_gmm(x, ws: Sequence[jax.Array], tile_expert, n_tiles, *, tm: int,
            layer=None, out_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
            interpret=None):
    """x (M, K) rows in ``tm``-row tiles, M a multiple of ``tm``; ``ws`` one
    or two (gate, up) stacks (E, K, N), or (L, E, K, N) read at ``layer``;
    ``tile_expert`` (M // tm,) int32 expert of each tile; ``n_tiles`` ()
    int32 live tiles. Returns (M, N): tile t's rows times expert
    ``tile_expert[t]``'s weights. ``interpret=None`` interprets off the
    TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if layer is None:
        ws, layer = [w[None] for w in ws], 0
    return _gmm(x, tuple(ws), jnp.asarray(layer, jnp.int32),
                tile_expert.astype(jnp.int32),
                jnp.asarray(n_tiles, jnp.int32), tm, out_dtype,
                compute_dtype, interpret)
