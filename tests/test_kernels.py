"""Pallas kernels vs pure-jnp oracles, swept over shapes/dtypes
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import masks as M
from repro.kernels import ops, ref
from sidedelta_tables import sidedelta as raw_sidedelta


def _updates(rng, shape, k, dup=True):
    """(idx, vals) of k entries per matrix of a (..., n, m) leaf, in random
    order; with ``dup`` drawn with replacement (duplicates must sum)."""
    *lead, n, m = shape
    rows = int(np.prod(lead, dtype=np.int64))
    if dup:
        idx = rng.integers(0, n * m, (rows, k))
    else:
        idx = np.stack([rng.permutation(n * m)[:k] for _ in range(rows)])
    vals = rng.standard_normal((rows, k)).astype(np.float32)
    return (jnp.asarray(idx.reshape(tuple(lead) + (k,)), jnp.int32),
            jnp.asarray(vals.reshape(tuple(lead) + (k,))))


# shape, entries per matrix, duplicates, streams, weight dtype
SCATTER_CASES = {
    "square": ((256, 256), 1300, False, 1, jnp.float32),
    "edges_200x576": ((200, 576), 2300, True, 1, jnp.float32),
    "dense_duplicates": ((64, 200), 6000, True, 1, jnp.float32),
    "layers": ((3, 136, 300), 800, True, 1, jnp.float32),
    "experts": ((2, 3, 130, 260), 700, True, 1, jnp.float32),
    "switch_unload_load": ((2, 256, 1100), 5000, True, 2, jnp.float32),
    "bf16_weights": ((128, 384), 900, True, 1, jnp.bfloat16),
}


@pytest.mark.parametrize("alpha", [1.0, -1.0, 0.5])
@pytest.mark.parametrize("case", list(SCATTER_CASES))
def test_scatter_apply(case, alpha):
    """The one-hot tile kernel (interpret mode) against XLA's scatter
    (``scatter_packed_add``), stream by stream: unsorted indices,
    duplicates, partial edge blocks, (L, n, m) and (L, E, n, m) leaves,
    both signs, and an unload fused with a load against the two in turn,
    to f32 rounding."""
    shape, k, dup, streams, dtype = SCATTER_CASES[case]
    rng = np.random.default_rng(abs(hash((case, alpha))) % 2**31)
    w = jnp.asarray(rng.standard_normal(shape), dtype)
    ups = [_updates(rng, shape, k, dup) for _ in range(streams)]
    scales = [alpha * (-1) ** (streams - 1 - s) for s in range(streams)]
    out = ops.scatter_apply(w, ups, scales, interpret=True)
    want = w.astype(jnp.float32)
    for (idx, vals), a in zip(ups, scales):
        want = M.scatter_packed_add(want, idx, vals, alpha=a)
    if dtype == jnp.bfloat16:
        want = want.astype(dtype)
    tol = (8 * np.finfo(np.float32).eps * float(jnp.max(jnp.abs(want)))
           if dtype == jnp.float32 else 3e-2)
    assert out.shape == w.shape and out.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)


def test_scatter_apply_empty_blocks_untouched():
    """Blocks no entry falls in come back bit-identical, and a load then
    an unload of the same entries restores the weight to f32 rounding."""
    rng = np.random.default_rng(0)
    n, m = 512, 640
    w = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
    # entries only in the block at (128, 256), in random order
    r, c = rng.integers(0, 128, 300) + 128, rng.integers(0, 128, 300) + 256
    upd = (jnp.asarray(r * m + c, jnp.int32),
           jnp.asarray(rng.standard_normal(300), jnp.float32))
    out = np.asarray(ops.scatter_apply(w, [upd], [1.0], interpret=True))
    hit = np.zeros((n, m), bool)
    hit[128:256, 256:384] = True
    np.testing.assert_array_equal(out[~hit], np.asarray(w)[~hit])
    assert np.any(out[hit] != np.asarray(w)[hit])
    back = ops.scatter_apply(jnp.asarray(out), [upd], [-1.0], interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(w), atol=1e-5)


def test_scatter_apply_bucket_layout():
    """``bucket``: the rows hold every entry once, sorted by block, and
    each tile's bounds bracket exactly its blocks' entries."""
    from repro.kernels.scatter_apply import bucket, grid_of
    rng = np.random.default_rng(3)
    shape = (2, 300, 700)
    idx, vals_in = _updates(rng, shape, 1500)
    lay = bucket(idx, vals_in, shape)
    B, n, m, q, nt = grid_of(shape)
    nbj = -(-m // 128)
    flat_i = np.asarray(idx).reshape(B, -1)
    for b in range(B):
        r, c = np.divmod(flat_i[b], m)
        want_blocks = np.sort((r // 128) * nbj + c // 128)
        want_place = (r % 128) * 128 + c % 128
        got = np.asarray(lay.entries[0]).reshape(-1)[b * 1500:(b + 1) * 1500]
        assert sorted(got) == sorted(want_place)
        bounds = np.asarray(lay.bounds).reshape(B, -1, nt, q + 1)[b]
        for i in range(bounds.shape[0]):
            for t in range(nt):
                for j in range(q):
                    blk = i * nbj + min(t * q + j, nbj)
                    s = bounds[i, t, j] - b * 1500
                    e = bounds[i, t, j + 1] - b * 1500
                    if t * q + j < nbj:
                        assert np.sum(want_blocks == blk) == e - s
    vals = np.asarray(lay.entries[1]).view(np.float32).reshape(-1)
    assert sorted(vals[:B * 1500]) == sorted(np.asarray(vals_in).reshape(-1))
    assert not vals[B * 1500:].any()


def test_scatter_apply_large_tile_overflows_window():
    """A tile with many more entries than its window (a dense block among
    sparse ones) is walked window after window."""
    rng = np.random.default_rng(5)
    n, m = 1024, 1024
    w = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
    sparse = rng.integers(0, n * m, 400)
    dense = rng.integers(0, 128, 9000) * m + rng.integers(0, 128, 9000)
    idx = np.concatenate([sparse, dense])
    rng.shuffle(idx)
    upd = (jnp.asarray(idx, jnp.int32),
           jnp.asarray(rng.standard_normal(idx.size), jnp.float32))
    from repro.kernels.scatter_apply import bucket, window_rows
    assert window_rows((n, m), idx.size) * 128 < 9000 / 2
    out = ops.scatter_apply(w, [upd], [0.25], interpret=True)
    want = M.scatter_packed_add(w, *upd, alpha=0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=8 * np.finfo(np.float32).eps
                               * float(jnp.max(jnp.abs(want))), rtol=0)


@pytest.mark.parametrize("B,S,n,m,A,K", [
    (4, 1, 64, 64, 3, 33),       # decode-step shape
    (3, 16, 128, 64, 4, 129),    # prefill shape, non-square
    (2, 8, 96, 160, 1, 7),       # single adapter
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sidedelta_parity(B, S, n, m, A, K, dtype):
    rng = np.random.RandomState(hash((B, S, n, m, A, K)) % 2**31)
    x = jnp.asarray(rng.randn(B, S, n), dtype)
    rows = jnp.asarray(rng.randint(0, n, (A, K)), jnp.int32)
    cols = jnp.asarray(rng.randint(0, m, (A, K)), jnp.int32)
    vals = jnp.asarray(rng.randn(A, K), jnp.float32)
    ids = jnp.asarray(rng.randint(-1, A, (B,)), jnp.int32)
    out = raw_sidedelta(x, rows, cols, vals, ids, m=m)
    want = ref.sidedelta_ref(x, rows, cols, vals, ids, m)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_sidedelta_base_requests_untouched():
    """ids = -1 must yield an exactly-zero delta row."""
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(3, 4, 32), jnp.float32)
    rows = jnp.asarray(rng.randint(0, 32, (2, 11)), jnp.int32)
    cols = jnp.asarray(rng.randint(0, 16, (2, 11)), jnp.int32)
    vals = jnp.asarray(rng.randn(2, 11), jnp.float32)
    ids = jnp.asarray([-1, 0, -1], jnp.int32)
    out = np.asarray(raw_sidedelta(x, rows, cols, vals, ids, m=16))
    assert np.all(out[0] == 0) and np.all(out[2] == 0)
    assert np.any(out[1] != 0)


@pytest.mark.parametrize("B,S,n,m,A,K,kc", [
    (3, 8, 128, 512, 3, 65, 128),     # dense MLP-ish: 4 column blocks
    (2, 4, 96, 384, 2, 33, 128),      # MoE expert-ish: odd n, 3 blocks
    (2, 2, 160, 576, 2, 47, 256),     # MLA-ish: non-pow2 dims, ragged block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sidedelta_tiled_parity(B, S, n, m, A, K, kc, dtype):
    """Multi-block tables must match the oracle for f32 activations and
    for the bf16 ones serving feeds; the oracle reads the same
    bf16-rounded x in f32, and the interpreter's matmuls are exact at
    both, so one tolerance holds."""
    rng = np.random.RandomState(hash((B, S, n, m, A, K)) % 2**31)
    x = jnp.asarray(rng.randn(B, S, n), dtype)
    rows = jnp.asarray(rng.randint(0, n, (A, K)), jnp.int32)
    cols = jnp.asarray(rng.randint(0, m, (A, K)), jnp.int32)
    vals = jnp.asarray(rng.randn(A, K), jnp.float32)
    ids = jnp.asarray(rng.randint(-1, A, (B,)), jnp.int32)
    out = raw_sidedelta(x, rows, cols, vals, ids, m=m, kc=kc)
    want = ref.sidedelta_ref(x.astype(jnp.float32), rows, cols, vals, ids,
                             m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want, np.float32),
                               atol=1e-5, rtol=1e-5)


def test_sidedelta_big_dff():
    """The acceptance shape: m=8192. The plan must be one of the weighed
    sub-chunk lengths and fit the VMEM budget at this S, and the kernel
    must match the oracle to fp32 accuracy."""
    from repro.kernels.sidedelta import (_KCS, DEFAULT_VMEM_BUDGET,
                                         plan_tiles, vmem_estimate)
    B, S, n, m, A, K = 2, 8, 256, 8192, 2, 1024
    kc = plan_tiles(n, m, K)
    assert kc in _KCS, kc
    assert vmem_estimate(S, n, m, kc) <= DEFAULT_VMEM_BUDGET
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S, n), jnp.float32)
    rows = jnp.asarray(rng.randint(0, n, (A, K)), jnp.int32)
    cols = jnp.asarray(rng.randint(0, m, (A, K)), jnp.int32)
    vals = jnp.asarray(rng.randn(A, K), jnp.float32)
    ids = jnp.asarray([1, -1], jnp.int32)
    out = raw_sidedelta(x, rows, cols, vals, ids, m=m)
    want = ref.sidedelta_ref(x, rows, cols, vals, ids, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want, np.float32),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sidedelta_tile_straddle(dtype):
    """Nonzeros ON the block boundaries (last col of block j, first col of
    block j+1) and duplicate (row, col) pairs must land exactly once each —
    the block's one-hot masks, it must not double-count or drop."""
    n, m = 32, 256
    rows = jnp.asarray([[0, 1, 2, 2, 3]], jnp.int32)
    cols = jnp.asarray([[127, 128, 255, 255, 0]], jnp.int32)  # edges + dup
    vals = jnp.asarray([[1.0, 2.0, 3.0, 4.0, 5.0]], jnp.float32)
    x = jnp.asarray(np.random.RandomState(3).randn(1, 4, n), dtype)
    xf = x.astype(jnp.float32)
    ids = jnp.asarray([0], jnp.int32)
    out = raw_sidedelta(x, rows, cols, vals, ids, m=m, kc=128)
    want = ref.sidedelta_ref(xf, rows, cols, vals, ids, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # the duplicate (2, 255) really accumulated 3 + 4
    np.testing.assert_allclose(np.asarray(out)[0, :, 255],
                               np.asarray(xf)[0, :, 2] * 7.0, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sidedelta_int8_tables(dtype):
    """int8 vals + per-adapter scale + int16 indices: exact against the
    int8 oracle (dequant is the same f32 math), and within dequant
    tolerance (<1e-2) of the unquantized f32 reference at SHiRA-realistic
    value magnitudes."""
    rng = np.random.RandomState(5)
    B, S, n, m, A, K = 3, 8, 128, 512, 3, 200
    x = jnp.asarray(rng.randn(B, S, n), dtype)
    xf = x.astype(jnp.float32)
    rows = jnp.asarray(rng.randint(0, n, (A, K)), jnp.int16)
    cols = jnp.asarray(rng.randint(0, m, (A, K)), jnp.int16)
    vf = (0.05 * rng.randn(A, K)).astype(np.float32)   # adapter-scale values
    qs = [ops.quantize_table(vf[a]) for a in range(A)]
    vq = jnp.asarray(np.stack([q for q, _ in qs]))
    scale = jnp.asarray(np.array([s for _, s in qs], np.float32))
    assert vq.dtype == jnp.int8
    ids = jnp.asarray([0, -1, 2], jnp.int32)
    out = raw_sidedelta(x, rows, cols, vq, ids, m=m, scale=scale, kc=128)
    want_q = ref.sidedelta_int8_ref(xf, rows, cols, vq, scale, ids, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_q),
                               atol=1e-5, rtol=1e-5)
    want_f = ref.sidedelta_ref(xf, rows.astype(jnp.int32),
                               cols.astype(jnp.int32), jnp.asarray(vf),
                               ids, m)
    assert float(np.max(np.abs(np.asarray(out) - np.asarray(want_f)))) < 1e-2
    assert np.all(np.asarray(out)[1] == 0)             # ids = -1 stays zero


def _dense_delta(x, rows, cols, vals, ids, m):
    """x[b] @ dW_{ids[b]} with each adapter's dW built dense (duplicate
    (row, col) pairs sum), 0 where ids[b] < 0."""
    A, n = rows.shape[0], x.shape[-1]
    dw = jnp.zeros((A, n, m), jnp.float32).at[
        jnp.arange(A)[:, None], rows, cols].add(vals)
    out = jnp.einsum("bsn,bnm->bsm", x, dw[jnp.maximum(ids, 0)],
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.where((ids >= 0)[:, None, None], out, 0.0)


def _raw_tables(rng, B, n, m, A, K, dups=True):
    """(rows, cols, vals, ids) drawn with duplicate (row, col) pairs."""
    rows = rng.randint(0, n, (A, K))
    cols = rng.randint(0, m, (A, K))
    if dups and K > 1:
        rows[:, 1], cols[:, 1] = rows[:, 0], cols[:, 0]
    return (jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
            jnp.asarray(rng.randn(A, K), jnp.float32),
            jnp.asarray(np.r_[-1, rng.randint(0, A, B - 1)], jnp.int32))


@pytest.mark.parametrize("B,S,n,m,A,K,int8", [
    (3, 8, 128, 512, 3, 65, False),
    (2, 4, 96, 384, 2, 33, False),
    (2, 2, 160, 576, 2, 47, False),
    (3, 8, 128, 512, 3, 200, True),    # int8 values, int16 indices
    (2, 4, 64, 256, 2, 0, False),      # empty tables
])
def test_sidedelta_ref_matches_dense(B, S, n, m, A, K, int8):
    """The reference (gather, scale, scatter-add; dW never built) equals
    x @ dW with dW built dense, duplicates summed, base rows zero."""
    rng = np.random.RandomState(hash((B, S, n, m, A, K)) % 2**31)
    x = jnp.asarray(rng.randn(B, S, n), jnp.float32)
    rows, cols, vals, ids = _raw_tables(rng, B, n, m, A, K)
    if int8:
        q = [ops.quantize_table(np.asarray(v)) for v in vals]
        vq = jnp.asarray(np.stack([a for a, _ in q]))
        scale = jnp.asarray(np.array([s for _, s in q], np.float32))
        got = ref.sidedelta_int8_ref(x, rows.astype(jnp.int16),
                                     cols.astype(jnp.int16), vq, scale, ids,
                                     m)
        vals = vq.astype(jnp.float32) * scale[:, None]
    else:
        got = ref.sidedelta_ref(x, rows, cols, vals, ids, m)
    want = _dense_delta(x, rows, cols, vals, ids, m)
    assert got.shape == (B, S, m) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert not np.asarray(got)[0].any()                  # ids = -1


def test_sidedelta_ref_grad_matches_dense():
    """The trainer differentiates the reference w.r.t. the value tables:
    its gradient equals the dense form's, duplicates and base rows
    included."""
    rng = np.random.RandomState(11)
    B, S, n, m, A, K = 4, 6, 96, 200, 3, 57
    x = jnp.asarray(rng.randn(B, S, n), jnp.float32)
    rows, cols, vals, ids = _raw_tables(rng, B, n, m, A, K)
    g = jnp.asarray(rng.randn(B, S, m), jnp.float32)

    def loss(f):
        return lambda v: jnp.sum(f(x, rows, cols, v, ids, m) * g)

    got = jax.grad(loss(ref.sidedelta_ref))(vals)
    want = jax.grad(loss(_dense_delta))(vals)
    assert np.asarray(got).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def _mask_flat(kind, n, m, K, rng):
    """Flat nonzero indices of one (n, m) adapter: ``rand`` spreads K
    evenly; ``struct`` is a SHiRA-struct mask (whole rows and columns plus
    the diagonal), whose blocks hold from 0 to 128 * 128 nonzeros."""
    if kind == "rand":
        return np.sort(rng.choice(n * m, K, replace=False))
    from repro.configs.base import AdapterConfig
    from repro.core.masks import _struct_indices
    return _struct_indices(n, m, AdapterConfig(struct_rows=2, struct_cols=3))


@pytest.mark.parametrize("mask,B,S,n,m,A,K,int8,budget", [
    ("rand", 3, 5, 300, 700, 3, 2000, False, None),    # ragged n, m, K
    ("rand", 4, 1, 256, 384, 2, 1500, True, None),     # int8, int16 indices
    ("struct", 2, 3, 200, 520, 2, 0, False, None),     # skewed block counts
    ("struct", 3, 2, 260, 140, 3, 0, True, None),      # struct, int8
    ("rand", 2, 20, 256, 256, 2, 900, False, 64 << 10),  # 3 S tiles of 8
])
def test_sidedelta_bucketed_kernel_parity(mask, B, S, n, m, A, K, int8,
                                          budget):
    """The Pallas kernel (interpret mode) over block-bucketed tables
    matches ``sidedelta_ref`` / ``sidedelta_int8_ref``: per adapter a mask
    of its own, ``ids = -1`` rows left at zero, dims and K off the tile
    grid, and (with a tight VMEM budget) more than one S tile."""
    from repro.kernels.sidedelta import DEFAULT_VMEM_BUDGET, sidedelta_rows
    rng = np.random.default_rng(hash((mask, B, S, n, m, A)) % 2**31)
    flats = [_mask_flat(mask, n, m, K, rng) for _ in range(A)]
    vf = [(0.05 * rng.standard_normal(len(f))).astype(np.float32)
          for f in flats]
    if int8:
        q = [ops.quantize_table(v) for v in vf]
        vq, scale = [a for a, _ in q], np.array([s for _, s in q], np.float32)
    t = ops.sidedelta_table(flats, vq if int8 else vf, n, m,
                            idx_dtype=np.int16 if int8 else np.int32)
    assert t.rows.dtype == (np.int16 if int8 else np.int32)
    x = jnp.asarray(rng.standard_normal((B, S, n)), jnp.float32)
    ids = jnp.asarray(np.r_[-1, rng.integers(0, A, B - 1)], jnp.int32)
    out = sidedelta_rows(
        x, jnp.asarray(t.rows), jnp.asarray(t.cols), jnp.asarray(t.vals),
        ids, m, blocks=jnp.asarray(t.blocks),
        scale=jnp.asarray(scale) if int8 else None,
        vmem_budget=budget or DEFAULT_VMEM_BUDGET)
    kmax = max(len(f) for f in flats)
    pad = lambda arrs, dt: np.stack([np.pad(a, (0, kmax - len(a))).astype(dt)
                                     for a in arrs])
    rows = jnp.asarray(pad([f // m for f in flats], np.int32))
    cols = jnp.asarray(pad([f % m for f in flats], np.int32))
    if int8:
        want = ref.sidedelta_int8_ref(x, rows, cols,
                                      jnp.asarray(pad(vq, np.int8)),
                                      jnp.asarray(scale), ids, m)
    else:
        want = ref.sidedelta_ref(x, rows, cols,
                                 jnp.asarray(pad(vf, np.float32)), ids, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert not np.asarray(out)[0].any()                  # ids = -1


@pytest.mark.parametrize("mask,n,m,K,kc", [
    ("rand", 300, 700, 3000, None),
    ("rand", 520, 260, 700, 256),
    ("struct", 384, 640, 0, None),
    ("struct", 130, 1000, 0, 512),
])
def test_sidedelta_bucketing_properties(mask, n, m, K, kc):
    """The layout itself: every nonzero appears exactly once, each
    sub-chunk lies in the one block its origin names, padding holds val 0
    (and -1 sub-chunks only padding), and ``pad_share`` counts the padded
    entries of the sub-chunks that run over the real nonzeros."""
    rng = np.random.default_rng(hash((mask, n, m, K)) % 2**31)
    flats = [_mask_flat(mask, n, m, K, rng) for _ in range(3)]
    flats[1] = flats[1][: len(flats[1]) // 3]            # a shorter table
    vals = [rng.uniform(0.5, 1.5, len(f)).astype(np.float32) for f in flats]
    t = ops.sidedelta_table(flats, vals, n, m, kc=kc)
    C = t.blocks.shape[1]
    kc = t.rows.shape[1] // C
    assert kc * C == t.rows.shape[1] and kc % 128 == 0
    used = 0
    for i, (f, v) in enumerate(zip(flats, vals)):
        real = t.vals[i] != 0
        got = t.rows[i][real].astype(np.int64) * m + t.cols[i][real]
        order = np.argsort(got)
        np.testing.assert_array_equal(got[order], np.sort(f))
        np.testing.assert_array_equal(t.vals[i][real][order],
                                      v[np.argsort(f)])
        origin = np.repeat(t.blocks[i], kc)
        run = origin >= 0
        n0, m0 = origin[run] >> 16, origin[run] & 0xFFFF
        assert not (n0 % 128).any() and not (m0 % 128).any()
        r, c = t.rows[i][run], t.cols[i][run]
        assert ((r >= n0) & (r < n0 + 128) & (c >= m0) & (c < m0 + 128)).all()
        assert not t.vals[i][~run].any()
        # the sub-chunks that run come first, then only -1
        k = int(run.sum()) // kc
        assert (t.blocks[i, :k] >= 0).all() and (t.blocks[i, k:] == -1).all()
        used += k * kc
    nnz = sum(len(f) for f in flats)
    assert (t.nnz, t.slots) == (nnz, used)
    assert t.pad_share == pytest.approx((used - nnz) / nnz)


def test_sidedelta_plan_tiles_budget():
    """The plan must respect its budget knob: a tighter budget never yields
    a longer sub-chunk, the plan fits the budget at every S tile, and a
    budget no plan fits raises instead of returning an over-budget one."""
    from repro.kernels.sidedelta import _ANY_S, plan_tiles, vmem_estimate
    n, m, K = 512, 16384, 2048
    big = plan_tiles(n, m, K, vmem_budget=8 << 20)
    small = plan_tiles(n, m, K, vmem_budget=2 << 20)
    assert small <= big
    assert vmem_estimate(_ANY_S, n, m, small, vmem_budget=2 << 20) <= 2 << 20
    for kc in (big, small):
        assert kc % 128 == 0
    with pytest.raises(ValueError, match="no tile plan fits"):
        plan_tiles(n, m, K, vmem_budget=256 << 10)


def test_sidedelta_table_roundtrip():
    """Host prep: packed flat indices -> block-bucketed (rows, cols, vals,
    blocks): each block's run is padded to a whole sub-chunk at the
    block's origin, and the triples read back exactly."""
    flat = np.asarray([5, 17, 33, 200 * 256 + 130], np.int64)
    vals = np.asarray([1.0, -2.0, 3.0, 4.0], np.float32)
    t = ops.sidedelta_table([flat], [vals], n=256, m=256, kc=128)
    assert t.rows.shape == (1, 256) and t.blocks.shape == (1, 2)
    # block (0, 0) first, then block (128, 128)
    np.testing.assert_array_equal(t.blocks[0], [0, 128 << 16 | 128])
    np.testing.assert_array_equal(t.rows[0, :4], [0, 0, 0, 0])
    np.testing.assert_array_equal(t.cols[0, :4], [5, 17, 33, 0])
    np.testing.assert_array_equal(t.vals[0, :4], [1.0, -2.0, 3.0, 0.0])
    assert t.rows[0, 128] == 200 and t.cols[0, 128] == 130
    assert t.vals[0, 128] == 4.0
    # padding sits at the block's origin with value 0
    assert set(t.rows[0, 129:]) == {128} and set(t.cols[0, 129:]) == {128}
    assert not t.vals[0, 129:].any()
    assert (t.nnz, t.slots) == (4, 256)
    assert t.pad_share == pytest.approx(252 / 4)


@pytest.mark.parametrize("shape", [(256, 256), (512, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_update(shape, dtype):
    rng = np.random.RandomState(2)
    n, m = shape
    w = jnp.asarray(rng.randn(n, m), dtype)
    mask = jnp.asarray(rng.rand(n, m) < 0.02, jnp.float32)
    vals = jnp.asarray(rng.randn(n, m), jnp.float32)
    out = ops.masked_update(w, mask, vals, 1.5, interpret=True)
    want = ref.masked_update_ref(w, mask, vals, 1.5)
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("k", [100, 2048, 5000])
@pytest.mark.parametrize("step", [1, 7])
def test_sparse_adamw(k, step):
    rng = np.random.RandomState(3)
    v = jnp.asarray(rng.randn(k), jnp.float32)
    g = jnp.asarray(rng.randn(k), jnp.float32)
    mu = jnp.asarray(rng.rand(k), jnp.float32)
    nu = jnp.asarray(rng.rand(k), jnp.float32)
    out = ops.sparse_adamw(v, g, mu, nu, jnp.asarray(step), lr=1e-2, wd=0.01,
                           interpret=True)
    want = ref.sparse_adamw_ref(v, g, mu, nu, lr=1e-2, b1=0.9, b2=0.999,
                                eps=1e-8, wd=0.01, step=step)
    for a, b in zip(out, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("B,KV,G,D,S,sb", [
    (1, 1, 1, 64, 512, 256),
    (2, 2, 4, 64, 1024, 512),
    (2, 1, 8, 128, 768, 256),   # MQA w/ padding (768 % 256 == 0)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(B, KV, G, D, S, sb, dtype):
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(B, KV, G, D), dtype)
    k = jnp.asarray(rng.randn(B, S, KV, D), dtype)
    v = jnp.asarray(rng.randn(B, S, KV, D), dtype)
    kv_len = S - 100
    out = ops.flash_decode(q, k, v, kv_len, sb=sb, interpret=True)
    want = ref.flash_decode_ref(q, k, v, kv_len)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_decode_matches_model_attention():
    """Cross-check the kernel against the model's decode attention path."""
    from repro.models.attention import _attend_block
    rng = np.random.RandomState(5)
    B, KV, G, D, S = 2, 2, 2, 64, 512
    H = KV * G
    q4 = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    k4 = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    v4 = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    kv_len = 300
    model_out = _attend_block(q4, k4, v4, jnp.array([kv_len - 1]),
                              jnp.arange(S), causal=True, prefix_len=0,
                              kv_len=kv_len)  # (B, 1, H, D)
    qk = q4[:, 0].reshape(B, KV, G, D)
    kern = ops.flash_decode(qk, k4, v4, kv_len, sb=256, interpret=True)
    np.testing.assert_allclose(
        np.asarray(kern.reshape(B, H, D), np.float32),
        np.asarray(model_out[:, 0], np.float32), atol=2e-2, rtol=2e-2)
