"""Pallas kernels vs pure-jnp oracles, swept over shapes/dtypes
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from sidedelta_tables import sidedelta as raw_sidedelta


@pytest.mark.parametrize("shape,bn,bm", [
    ((256, 256), 128, 128),
    ((512, 768), 256, 256),
    ((384, 512), 128, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("alpha", [1.0, -1.0, 0.5])
def test_scatter_apply(shape, bn, bm, dtype, alpha):
    rng = np.random.RandomState(hash((shape, str(dtype))) % 2**31)
    n, m = shape
    k = max(int(0.02 * n * m), 4)
    w = jnp.asarray(rng.randn(n, m), dtype)
    idx = np.unique(rng.randint(0, n * m, 2 * k))[:k]
    vals = rng.randn(len(idx)).astype(np.float32)
    counts, rows, cols, vbuf = ops.bucket_updates(idx, vals, n, m, bn=bn, bm=bm)
    out = ops.scatter_apply(w, jnp.asarray(counts), jnp.asarray(rows),
                            jnp.asarray(cols), jnp.asarray(vbuf), alpha,
                            bn=bn, bm=bm, interpret=True)
    want = ref.scatter_apply_ref(w, jnp.asarray(idx), jnp.asarray(vals), alpha)
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_scatter_apply_empty_tiles_untouched():
    """Struct-style masks: tiles without updates must be bit-identical."""
    rng = np.random.RandomState(0)
    n, m, bn, bm = 512, 512, 256, 256
    w = jnp.asarray(rng.randn(n, m), jnp.float32)
    # updates only in the top-left tile
    idx = (rng.randint(0, bn, 50) * m + rng.randint(0, bm, 50)).astype(np.int64)
    idx = np.unique(idx)
    vals = rng.randn(len(idx)).astype(np.float32)
    counts, rows, cols, vbuf = ops.bucket_updates(idx, vals, n, m, bn=bn, bm=bm)
    assert counts[0, 0] == len(idx) and counts[1, 1] == 0
    out = ops.scatter_apply(w, jnp.asarray(counts), jnp.asarray(rows),
                            jnp.asarray(cols), jnp.asarray(vbuf), 1.0,
                            bn=bn, bm=bm, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[bn:, bm:]),
                                  np.asarray(w[bn:, bm:]))


def test_scatter_load_unload_roundtrip():
    rng = np.random.RandomState(1)
    n = m = 512
    w = jnp.asarray(rng.randn(n, m), jnp.float32)
    idx = np.unique(rng.randint(0, n * m, 4000))
    vals = rng.randn(len(idx)).astype(np.float32)
    args = [jnp.asarray(a) for a in ops.bucket_updates(idx, vals, n, m)]
    loaded = ops.scatter_apply(w, *args, 1.0, interpret=True)
    restored = ops.scatter_apply(loaded, *args, -1.0, interpret=True)
    np.testing.assert_allclose(np.asarray(restored), np.asarray(w), atol=1e-5)


def test_scatter_apply_vs_dense_np_reference():
    """Interpret-mode kernel vs a plain numpy dense scatter-add."""
    rng = np.random.RandomState(7)
    n, m = 512, 512
    w = rng.randn(n, m).astype(np.float32)
    idx = np.unique(rng.randint(0, n * m, 3000))
    vals = rng.randn(len(idx)).astype(np.float32)
    alpha = 0.75
    args = [jnp.asarray(a) for a in ops.bucket_updates(idx, vals, n, m)]
    out = ops.scatter_apply(jnp.asarray(w), *args, alpha, interpret=True)
    want = w.copy()
    want.reshape(-1)[idx] += alpha * vals
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-6)


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
    out = raw_sidedelta(x, rows, cols, vals, ids, m=m, interpret=True)
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
    out = np.asarray(raw_sidedelta(x, rows, cols, vals, ids, m=16,
                                   interpret=True))
    assert np.all(out[0] == 0) and np.all(out[2] == 0)
    assert np.any(out[1] != 0)


@pytest.mark.parametrize("B,S,n,m,A,K,kc", [
    (3, 8, 128, 512, 3, 65, 128),     # dense MLP-ish: 4 column blocks
    (2, 4, 96, 384, 2, 33, 128),      # MoE expert-ish: odd n, 3 blocks
    (2, 2, 160, 576, 2, 47, 256),     # MLA-ish: non-pow2 dims, ragged block
])
@pytest.mark.parametrize("interpret", [True, False])
def test_sidedelta_tiled_parity(B, S, n, m, A, K, kc, interpret):
    """Multi-block tables must match the oracle in BOTH the Pallas
    interpreter and compiled mode (on CPU the latter dispatches the XLA
    twin, which reads the same tables in any order)."""
    rng = np.random.RandomState(hash((B, S, n, m, A, K)) % 2**31)
    x = jnp.asarray(rng.randn(B, S, n), jnp.float32)
    rows = jnp.asarray(rng.randint(0, n, (A, K)), jnp.int32)
    cols = jnp.asarray(rng.randint(0, m, (A, K)), jnp.int32)
    vals = jnp.asarray(rng.randn(A, K), jnp.float32)
    ids = jnp.asarray(rng.randint(-1, A, (B,)), jnp.int32)
    out = raw_sidedelta(x, rows, cols, vals, ids, m=m, interpret=interpret,
                        kc=kc)
    want = ref.sidedelta_ref(x, rows, cols, vals, ids, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want, np.float32),
                               atol=1e-5, rtol=1e-5)


def test_sidedelta_compiled_big_dff():
    """The acceptance shape: m=8192 with interpret=False on CPU. The plan
    must be one of the weighed sub-chunk lengths and fit the VMEM budget
    at this S, and the compiled result must match the oracle to fp32
    accuracy."""
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
    out = raw_sidedelta(x, rows, cols, vals, ids, m=m, interpret=False)
    want = ref.sidedelta_ref(x, rows, cols, vals, ids, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want, np.float32),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("interpret", [True, False])
def test_sidedelta_tile_straddle(interpret):
    """Nonzeros ON the block boundaries (last col of block j, first col of
    block j+1) and duplicate (row, col) pairs must land exactly once each —
    the block's one-hot masks, it must not double-count or drop."""
    n, m = 32, 256
    rows = jnp.asarray([[0, 1, 2, 2, 3]], jnp.int32)
    cols = jnp.asarray([[127, 128, 255, 255, 0]], jnp.int32)  # edges + dup
    vals = jnp.asarray([[1.0, 2.0, 3.0, 4.0, 5.0]], jnp.float32)
    x = jnp.asarray(np.random.RandomState(3).randn(1, 4, n), jnp.float32)
    ids = jnp.asarray([0], jnp.int32)
    out = raw_sidedelta(x, rows, cols, vals, ids, m=m, interpret=interpret,
                        kc=128)
    want = ref.sidedelta_ref(x, rows, cols, vals, ids, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # the duplicate (2, 255) really accumulated 3 + 4
    np.testing.assert_allclose(np.asarray(out)[0, :, 255],
                               np.asarray(x)[0, :, 2] * 7.0, rtol=1e-5)


@pytest.mark.parametrize("interpret", [True, False])
def test_sidedelta_int8_tables(interpret):
    """int8 vals + per-adapter scale + int16 indices: exact against the
    int8 oracle (dequant is the same f32 math), and within dequant
    tolerance (<1e-2) of the unquantized f32 reference at SHiRA-realistic
    value magnitudes."""
    rng = np.random.RandomState(5)
    B, S, n, m, A, K = 3, 8, 128, 512, 3, 200
    x = jnp.asarray(rng.randn(B, S, n), jnp.float32)
    rows = jnp.asarray(rng.randint(0, n, (A, K)), jnp.int16)
    cols = jnp.asarray(rng.randint(0, m, (A, K)), jnp.int16)
    vf = (0.05 * rng.randn(A, K)).astype(np.float32)   # adapter-scale values
    qs = [ops.quantize_table(vf[a]) for a in range(A)]
    vq = jnp.asarray(np.stack([q for q, _ in qs]))
    scale = jnp.asarray(np.array([s for _, s in qs], np.float32))
    assert vq.dtype == jnp.int8
    ids = jnp.asarray([0, -1, 2], jnp.int32)
    out = raw_sidedelta(x, rows, cols, vq, ids, m=m, scale=scale,
                        interpret=interpret, kc=128)
    want_q = ref.sidedelta_int8_ref(x, rows, cols, vq, scale, ids, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_q),
                               atol=1e-5, rtol=1e-5)
    want_f = ref.sidedelta_ref(x, rows.astype(jnp.int32),
                               cols.astype(jnp.int32), jnp.asarray(vf),
                               ids, m)
    assert float(np.max(np.abs(np.asarray(out) - np.asarray(want_f)))) < 1e-2
    assert np.all(np.asarray(out)[1] == 0)             # ids = -1 stays zero


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
        scale=jnp.asarray(scale) if int8 else None, interpret=True,
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
