"""scatter_apply — the paper's `torch.Tensor.scatter_` re-thought for TPU.

SHiRA rapid switching overwrites 1-2% of a weight matrix in place. Scalar
scatter is hostile to the TPU memory system, so we adapt the *insight*
(move only the adapter bytes, touch the weight once) to the hierarchy:

  1. host pre-pass (ops.py): bucket the packed (flat_idx, value) updates by
     VMEM tile, producing per-tile padded (row, col, val) buffers + counts;
  2. kernel: grid = weight tiles; each program DMAs its (bn, bm) tile into
     VMEM, applies its bucket with a bounded fori_loop of dynamic stores,
     and writes the tile back. Tiles with empty buckets skip the update
     (input/output aliasing keeps them untouched) — with SHiRA-Struct masks
     whole tile rows short-circuit, so only dirty tiles cost stores.

W_out = W + alpha * scatter(vals)  (delta form: load = +alpha, unload = -alpha)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _scatter_kernel(counts_ref, alpha_ref, rows_ref, cols_ref, vals_ref,
                    w_ref, out_ref, *, max_updates: int):
    cnt = counts_ref[0, 0]
    out_ref[...] = w_ref[...]

    @pl.when(cnt > 0)
    def _():
        alpha = alpha_ref[0]

        def body(u, _):
            @pl.when(u < cnt)
            def _():
                r = rows_ref[0, 0, u]
                c = cols_ref[0, 0, u]
                v = vals_ref[0, 0, u]
                at = (pl.ds(r, 1), pl.ds(c, 1))
                out_ref[at] = out_ref[at] + (alpha * v).astype(out_ref.dtype)
            return ()

        jax.lax.fori_loop(0, max_updates, body, ())


def scatter_apply_tiles(w: jax.Array, counts: jax.Array, rows: jax.Array,
                        cols: jax.Array, vals: jax.Array, alpha: jax.Array,
                        *, bn: int = 256, bm: int = 256,
                        interpret: bool = False) -> jax.Array:
    """w: (n, m); counts: (nt_i, nt_j) int32; rows/cols: (nt_i, nt_j, U)
    int32 tile-local coordinates; vals: (nt_i, nt_j, U) f32; alpha: (1,) f32.
    """
    n, m = w.shape
    assert n % bn == 0 and m % bm == 0, (n, m, bn, bm)
    nt_i, nt_j = n // bn, m // bm
    max_updates = rows.shape[-1]
    kernel = functools.partial(_scatter_kernel, max_updates=max_updates)
    return pl.pallas_call(
        kernel,
        grid=(nt_i, nt_j),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
            pl.BlockSpec((1,), lambda i, j: (0,)),
            pl.BlockSpec((1, 1, max_updates), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, max_updates), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, max_updates), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), w.dtype),
        input_output_aliases={5: 0},
        interpret=interpret,
    )(counts, alpha, rows, cols, vals, w)
