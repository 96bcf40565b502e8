"""Test helper: the side-delta op over raw (A, K) tables, as tests draw
them, laid out for the kernel first (``ops.sidedelta_table``)."""
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def sidedelta(x, rows, cols, vals, ids, *, m, scale=None, interpret=False,
              kc=None):
    rows, cols, vals = (np.asarray(t) for t in (rows, cols, vals))
    flat = [r.astype(np.int64) * m + c for r, c in zip(rows, cols)]
    t = ops.sidedelta_table(flat, vals, x.shape[-1], m, kc=kc,
                            idx_dtype=rows.dtype)
    return ops.sidedelta(x, jnp.asarray(t.rows), jnp.asarray(t.cols),
                         jnp.asarray(t.vals), ids, m=m,
                         blocks=jnp.asarray(t.blocks), scale=scale,
                         interpret=interpret)
