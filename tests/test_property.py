"""Property-based tests (hypothesis) on the system's invariants.

``hypothesis`` is an optional test dependency: when it is absent the whole
module is skipped at collection time instead of erroring the tier-1 run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import masks as M
from repro.kernels import ops, ref
from sidedelta_tables import sidedelta as raw_sidedelta

SETTINGS = dict(max_examples=20, deadline=None)


@given(n=st.integers(2, 12).map(lambda x: x * 32),
       m=st.integers(2, 12).map(lambda x: x * 32),
       frac=st.floats(0.005, 0.05),
       seed=st.integers(0, 2 ** 16))
@settings(**SETTINGS)
def test_scatter_gather_roundtrip(n, m, frac, seed):
    """gather(scatter_add(w, idx, v)) - gather(w) == v at idx."""
    rng = np.random.RandomState(seed)
    k = max(1, int(frac * n * m))
    w = jnp.asarray(rng.randn(1, n, m), jnp.float32)
    idx = jnp.asarray(np.sort(rng.choice(n * m, k, replace=False))[None],
                      jnp.int32)
    v = jnp.asarray(rng.randn(1, k), jnp.float32)
    w2 = M.scatter_packed_add(w, idx, v)
    got = M.gather_packed(w2, idx) - M.gather_packed(w, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(v), atol=1e-5)


@given(n=st.integers(1, 8).map(lambda x: x * 64),
       m=st.integers(1, 8).map(lambda x: x * 64),
       seed=st.integers(0, 2 ** 16),
       alpha=st.floats(-2.0, 2.0))
@settings(**SETTINGS)
def test_scatter_set_then_add_inverse(n, m, seed, alpha):
    """W + aS - aS == W exactly (load/unload invariant of rapid switching)."""
    rng = np.random.RandomState(seed)
    k = max(1, (n * m) // 100)
    w = jnp.asarray(rng.randn(1, n, m), jnp.float32)
    idx = jnp.asarray(rng.choice(n * m, k, replace=False)[None], jnp.int32)
    v = jnp.asarray(rng.randn(1, k), jnp.float32)
    w2 = M.scatter_packed_add(M.scatter_packed_add(w, idx, v, alpha),
                              idx, v, -alpha)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(w), atol=1e-5)


@given(B=st.integers(1, 4), S=st.integers(1, 8),
       n=st.integers(3, 160), m=st.integers(3, 520),
       K=st.integers(0, 300), seed=st.integers(0, 2 ** 16),
       int8=st.booleans())
@settings(max_examples=25, deadline=None)
def test_sidedelta_tiled_matches_ref(B, S, n, m, K, seed, int8):
    """The tiled+vectorised sidedelta kernel must match ``sidedelta_ref``
    for arbitrary (B, S, n, m, K): K=0, all-base batches (ids=-1),
    nonzeros straddling block boundaries (the blocks are 128 wide, so any
    m > 128 has several), and int8 tables within dequant tolerance."""
    rng = np.random.RandomState(seed)
    A = rng.randint(1, 4)
    x = jnp.asarray(rng.randn(B, S, n), jnp.float32)
    rows = jnp.asarray(rng.randint(0, n, (A, K)), jnp.int32)
    # bias columns toward tile edges so boundary straddling is common
    cols_np = rng.randint(0, m, (A, K))
    if K and m > 130:
        edge = rng.randint(0, K, max(K // 4, 1))
        cols_np[:, edge] = rng.choice([127, 128, m - 1], edge.shape[0])
    cols = jnp.asarray(cols_np, jnp.int32)
    vf = (0.05 * rng.randn(A, K)).astype(np.float32)
    ids = jnp.asarray(rng.randint(-1, A, (B,)), jnp.int32)
    if int8:
        pairs = [ops.quantize_table(vf[a]) for a in range(A)]
        vals = jnp.asarray(np.stack([q for q, _ in pairs]))
        scale = jnp.asarray(np.array([s for _, s in pairs], np.float32))
        want = ref.sidedelta_int8_ref(x, rows, cols, vals, scale, ids, m)
        tol = 1e-5   # vs the int8 oracle: same dequant math, exact
    else:
        vals, scale = jnp.asarray(vf), None
        want = ref.sidedelta_ref(x, rows, cols, vals, ids, m)
        tol = 1e-5
    out = raw_sidedelta(x, rows, cols, vals, ids, m=m, scale=scale, kc=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    if int8:   # and the dequant stays within the serving tolerance
        want_f = ref.sidedelta_ref(x, rows, cols, jnp.asarray(vf), ids, m)
        assert float(np.max(np.abs(
            np.asarray(out) - np.asarray(want_f, np.float32)))) < 1e-2


@given(n=st.integers(64, 256), m=st.integers(64, 256),
       sparsity=st.floats(0.9, 0.995), seed=st.integers(0, 2 ** 16))
@settings(**SETTINGS)
def test_mask_budget_exact(n, m, sparsity, seed):
    k = M.budget(n, m, sparsity)
    assert 1 <= k <= n * m
    assert abs(k - (1 - sparsity) * n * m) <= 1


@given(seed=st.integers(0, 2 ** 16), steps=st.integers(1, 5))
@settings(**SETTINGS)
def test_adamw_zero_grad_only_decays(seed, steps):
    """With zero grads and wd=0 the packed AdamW must be an exact no-op."""
    rng = np.random.RandomState(seed)
    k = 256
    v = jnp.asarray(rng.randn(k), jnp.float32)
    mu = jnp.zeros((k,), jnp.float32)
    nu = jnp.zeros((k,), jnp.float32)
    for s in range(1, steps + 1):
        v2, mu, nu = ops.sparse_adamw(v, jnp.zeros((k,)), mu, nu,
                                      jnp.asarray(s), lr=1e-2, wd=0.0,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v), atol=1e-7)


@given(seed=st.integers(0, 2 ** 16),
       s=st.integers(2, 6).map(lambda x: x * 16),
       chunk=st.sampled_from([8, 16, 32]))
@settings(max_examples=10, deadline=None)
def test_ssd_chunk_size_invariance(seed, s, chunk):
    """SSD output must not depend on the chunk size (pure reformulation)."""
    from repro.models import mamba2
    rng = np.random.RandomState(seed)
    b, h, p, g, n = 1, 2, 4, 1, 8
    x = jnp.asarray(rng.randn(b, s, h, p), jnp.float32)
    dt = jnp.asarray(rng.rand(b, s, h) * 0.3 + 0.01, jnp.float32)
    Ah = -jnp.asarray(rng.rand(h) + 0.1, jnp.float32)
    B = jnp.asarray(rng.randn(b, s, g, n), jnp.float32)
    C = jnp.asarray(rng.randn(b, s, g, n), jnp.float32)
    y1, f1 = mamba2.ssd_chunked(x, dt, Ah, B, C, chunk)
    y2, f2 = mamba2.ssd_chunked(x, dt, Ah, B, C, s)  # one chunk
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                               rtol=1e-3, atol=1e-3)


@given(seed=st.integers(0, 2 ** 16), kv_len=st.integers(1, 512))
@settings(max_examples=15, deadline=None)
def test_flash_decode_kv_len_property(seed, kv_len):
    """Tokens beyond kv_len must not influence the output."""
    rng = np.random.RandomState(seed)
    B, KV, G, D, S = 1, 1, 2, 32, 512
    q = jnp.asarray(rng.randn(B, KV, G, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    out1 = ops.flash_decode(q, k, v, kv_len, sb=256, interpret=True)
    k2 = k.at[:, kv_len:].set(99.0)
    v2 = v.at[:, kv_len:].set(-99.0)
    out2 = ops.flash_decode(q, k2, v2, kv_len, sb=256, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


@given(seed=st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_checkpoint_roundtrip_identity(seed):
    import tempfile
    from repro.checkpoint import restore_tree, save_tree
    rng = np.random.RandomState(seed)
    tree = {"a": jnp.asarray(rng.randn(4, 8), jnp.float32),
            "b": [{"c": jnp.asarray(rng.randn(3), jnp.bfloat16)},
                  jnp.asarray(rng.randint(0, 5, (2, 2)), jnp.int32)]}
    with tempfile.TemporaryDirectory() as d:
        save_tree(tree, d)
        out = restore_tree(tree, d)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
