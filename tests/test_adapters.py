"""SHiRA core: masks, adapters, switching, fusion (paper §3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs import AdapterConfig, get_smoke_config
from repro.core import masks as M
from repro.models import lm


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("starcoder2-7b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 32), jnp.int32),
             "labels": jnp.ones((2, 32), jnp.int32)}
    return cfg, params, batch


MASKS = ["struct", "rand", "wm"]


@pytest.mark.parametrize("mask", MASKS)
def test_mask_sparsity_and_exact_budget(setup, mask):
    cfg, params, _ = setup
    acfg = AdapterConfig(kind="shira", mask=mask, sparsity=0.95)
    idx = core.make_packed_indices(params, acfg, jax.random.PRNGKey(1))
    for p, leaf in jax.tree_util.tree_flatten_with_path(
            idx, is_leaf=lambda x: x is None)[0]:
        if leaf is None:
            continue
        *lead, k = leaf.shape
        flat = np.asarray(leaf).reshape(-1, k)
        for row in flat:
            assert len(np.unique(row)) == k, "duplicate indices in mask"
        if mask != "struct":
            # exact per-matrix budget
            w = None
            assert k >= 1


def test_grad_and_snip_masks_need_grads(setup):
    cfg, params, batch = setup
    acfg = AdapterConfig(kind="shira", mask="snip", sparsity=0.95)
    with pytest.raises(ValueError):
        core.make_packed_indices(params, acfg, jax.random.PRNGKey(0))
    grads = jax.grad(lambda p: lm.train_loss(p, cfg, batch)[0])(params)
    idx = core.make_packed_indices(params, acfg, jax.random.PRNGKey(0),
                                   grads)
    assert any(l is not None for l in jax.tree.leaves(
        idx, is_leaf=lambda x: x is None))


def test_dense_mask_matches_packed(setup):
    cfg, params, _ = setup
    acfg = AdapterConfig(kind="shira", mask="wm", sparsity=0.9)
    key = jax.random.PRNGKey(3)
    idx = core.make_packed_indices(params, acfg, key)
    dm = core.make_dense_masks(params, acfg, key)
    for (pi, i), (pm, m) in zip(
            jax.tree_util.tree_flatten_with_path(idx, is_leaf=lambda x: x is None)[0],
            jax.tree_util.tree_flatten_with_path(dm, is_leaf=lambda x: x is None)[0]):
        if i is None:
            assert m is None
            continue
        *lead, k = i.shape
        assert float(jnp.sum(m)) == np.prod(lead or [1]) * k


def test_zero_init_is_identity_and_alpha_scales(setup):
    cfg, params, _ = setup
    acfg = AdapterConfig(kind="shira", mask="rand", sparsity=0.97)
    values, aux = core.init_adapter(jax.random.PRNGKey(0), params, acfg)
    eff0 = core.materialize(params, values, aux, acfg)
    for a, b in zip(jax.tree.leaves(eff0), jax.tree.leaves(params)):
        assert jnp.array_equal(a, b)
    vals = jax.tree.map(lambda v: v + 1.0, values)
    e1 = core.materialize(params, vals, aux, acfg, alpha=1.0)
    e2 = core.materialize(params, vals, aux, acfg, alpha=2.0)
    # alpha=2 delta is exactly twice alpha=1 delta (paper App. G)
    for w, a, b in zip(jax.tree.leaves(params), jax.tree.leaves(e1),
                       jax.tree.leaves(e2)):
        np.testing.assert_allclose(np.asarray(b - w), 2 * np.asarray(a - w),
                                   rtol=1e-5, atol=1e-6)


def test_pack_load_unload_roundtrip(setup):
    cfg, params, _ = setup
    acfg = AdapterConfig(kind="shira", mask="wm", sparsity=0.95)
    values, aux = core.init_adapter(jax.random.PRNGKey(0), params, acfg)
    values = jax.tree.map(lambda v: v + 0.05, values)
    pack = core.pack_from_shira("t", values, aux)
    eng = core.SwitchEngine(params)
    eng.load(pack)
    ch = core.switching.changed_fraction(params, eng.params)
    assert 0 < ch < 0.2, f"%C should be small, got {ch}"
    eng.unload()
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_multi_adapter_fusion_equals_sequential(setup):
    cfg, params, _ = setup
    acfg = AdapterConfig(kind="shira", mask="rand", sparsity=0.97)
    packs = []
    for i in range(3):
        v, aux = core.init_adapter(jax.random.fold_in(jax.random.PRNGKey(5), i),
                                   params, acfg)
        v = jax.tree.map(lambda x: x + 0.01 * (i + 1), v)
        packs.append(core.pack_from_shira(f"p{i}", v, aux))
    seq = core.SwitchEngine(params)
    seq.load_fused(packs)
    fused = core.fuse_packs(packs)
    one = core.SwitchEngine(params)
    one.load(fused)
    for a, b in zip(jax.tree.leaves(seq.params), jax.tree.leaves(one.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_switch_engine_roundtrip_changed_fraction(setup):
    """load -> unload restores params within fp32 tolerance AND the paper's
    %C metric returns to ~0 (the rapid-switch invariant)."""
    cfg, params, _ = setup
    acfg = AdapterConfig(kind="shira", mask="rand", sparsity=0.98)
    values, aux = core.init_adapter(jax.random.PRNGKey(11), params, acfg)
    values = jax.tree.map(
        lambda v: None if v is None
        else 0.02 * jax.random.normal(jax.random.PRNGKey(12), v.shape),
        values, is_leaf=lambda x: x is None)
    pack = core.pack_from_shira("rt", values, aux)
    eng = core.SwitchEngine(params)
    eng.load(pack)
    ch_loaded = core.switching.changed_fraction(params, eng.params)
    assert ch_loaded > 0.001
    eng.unload()
    # %C back to ~0: only last-ulp residue of the float add/sub roundtrip
    # may remain (bitwise-differing but value-identical to 1e-6)
    ch_unloaded = core.switching.changed_fraction(params, eng.params)
    assert ch_unloaded < 0.2 * ch_loaded and ch_unloaded < 5e-3
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _random_packs(params, n=2, seed=30, std=0.05):
    acfg = AdapterConfig(kind="shira", mask="rand", sparsity=0.97)
    packs = []
    for i in range(n):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        v, aux = core.init_adapter(key, params, acfg)
        v = jax.tree.map(lambda x: std * jax.random.normal(key, x.shape), v)
        packs.append(core.pack_from_shira(f"r{i}", v, aux))
    return packs


def test_switch_engine_donates_only_its_own_leaves(setup):
    """The caller's tree survives switching untouched; a leaf the engine
    wrote is donated to (updated in place by) its next switch; and
    A -> B -> A -> unload restores the base to f32 tolerance."""
    cfg, params, _ = setup
    base = [np.asarray(x) for x in jax.tree.leaves(params)]
    a, b = _random_packs(params)
    eng = core.SwitchEngine(params)
    eng.switch(a)
    first = eng.params
    eng.switch(b)
    for x, want in zip(jax.tree.leaves(params), base):
        assert not x.is_deleted()
        np.testing.assert_array_equal(np.asarray(x), want)
    for p, x in jax.tree_util.tree_flatten_with_path(first)[0]:
        assert x.is_deleted() == (M.path_str(p) in a.entries)
    eng.switch(a)
    eng.unload()
    assert not eng.active
    for x, want in zip(jax.tree.leaves(eng.params), base):
        np.testing.assert_allclose(np.asarray(x), want, atol=1e-6)


def test_switch_engine_entry_points_equal_apply_pack(setup):
    """``load_fused`` and a non-blocking switch give what the plain
    reference (``apply_pack``, XLA's scatter) gives."""
    cfg, params, _ = setup
    a, b = _random_packs(params, seed=31)
    fused = core.SwitchEngine(params)
    fused.load_fused([a, b], weights=[0.5, -2.0])
    want = core.apply_pack(core.apply_pack(params, a, alpha=0.5),
                           b, alpha=-2.0)
    for x, y in zip(jax.tree.leaves(fused.params), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)
    eager = core.SwitchEngine(params, blocking=False)
    eager.switch(a)
    eager.switch(b)
    want = core.apply_pack(params, b)
    for x, y in zip(jax.tree.leaves(eager.params), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


def test_switch_engine_counts_kernel_and_fallback_leaves(setup):
    """Leaves held whole by one device take the tile kernel; others (here
    a host array) keep XLA's scatter. The tracer counts both."""
    from repro.analysis import trace
    cfg, params, _ = setup
    (a,) = _random_packs(params, n=1, seed=32)
    host = sorted(a.entries)[0]
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    mixed = jax.tree_util.tree_unflatten(treedef, [
        np.asarray(x) if M.path_str(p) == host else x for p, x in flat])
    eng = core.SwitchEngine(mixed)
    tr = trace.install()
    try:
        eng.load(a)
    finally:
        trace.uninstall()
    counts = {e["name"]: e["args"]["value"] for e in tr.events()
              if e["ph"] == "C"}
    assert counts == {"switch.kernel_leaves": len(a.entries) - 1,
                      "switch.fallback_leaves": 1}
    want = core.apply_pack(params, a)
    for x, y in zip(jax.tree.leaves(eng.params), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


def test_fuse_packs_merges_duplicate_coordinates(setup):
    """Two packs sharing coordinates (same mask) must merge by ADDITION in
    the fused pack: loading it == loading both sequentially."""
    cfg, params, _ = setup
    acfg = AdapterConfig(kind="shira", mask="wm", sparsity=0.97)
    v1, aux = core.init_adapter(jax.random.PRNGKey(6), params, acfg)
    # identical index sets (wm mask is deterministic), different values —
    # every coordinate is a duplicate between the two packs
    p1 = core.pack_from_shira("x", jax.tree.map(lambda v: v + 0.03, v1), aux,
                              alpha=1.0)
    p2 = core.pack_from_shira("y", jax.tree.map(lambda v: v - 0.01, v1), aux,
                              alpha=0.5)
    seq = core.SwitchEngine(params)
    seq.load(p1)
    seq.load(p2)
    fused = core.fuse_packs([p1, p2])
    # duplicate merging really happened: fused K == single-pack K
    for path, (idx, _) in fused.entries.items():
        assert idx.shape[-1] == p1.entries[path][0].shape[-1]
    one = core.SwitchEngine(params)
    one.load(fused)
    for a, b in zip(jax.tree.leaves(seq.params), jax.tree.leaves(one.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_fuse_packs_keeps_paths_unique_to_later_packs(setup):
    """A path covered only by the SECOND pack must survive fusion (diff
    packs for multi-tenant fused serving rely on this)."""
    cfg, params, _ = setup
    a_wq = AdapterConfig(kind="shira", mask="wm", sparsity=0.97,
                         target_modules=("wq",))
    a_wo = AdapterConfig(kind="shira", mask="wm", sparsity=0.97,
                         target_modules=("wo",))
    v1, x1 = core.init_adapter(jax.random.PRNGKey(21), params, a_wq)
    v2, x2 = core.init_adapter(jax.random.PRNGKey(22), params, a_wo)
    p1 = core.pack_from_shira("wq-only", jax.tree.map(lambda v: v + 0.1, v1),
                              x1)
    p2 = core.pack_from_shira("wo-only", jax.tree.map(lambda v: v - 0.2, v2),
                              x2)
    fused = core.fuse_packs([p1, p2], weights=[1.0, -1.0])
    assert set(fused.entries) == set(p1.entries) | set(p2.entries)
    seq = core.SwitchEngine(params)
    seq.load(p1)
    seq.load(core.adapters.AdapterPack(p2.name, p2.entries, alpha=-p2.alpha))
    one = core.SwitchEngine(params)
    one.load(fused)
    for a, b in zip(jax.tree.leaves(seq.params), jax.tree.leaves(one.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_overlap_lower_for_independent_rand_masks(setup):
    """§3.2: sparse masks ⇒ low interference. Random independent masks
    overlap ~(1-sparsity); LoRA-equivalent dense deltas overlap 100%."""
    cfg, params, _ = setup
    acfg = AdapterConfig(kind="shira", mask="rand", sparsity=0.97)
    v1, a1 = core.init_adapter(jax.random.PRNGKey(1), params, acfg)
    v2, a2 = core.init_adapter(jax.random.PRNGKey(2), params, acfg)
    p1 = core.pack_from_shira("a", jax.tree.map(lambda x: x + 1, v1), a1)
    p2 = core.pack_from_shira("b", jax.tree.map(lambda x: x + 1, v2), a2)
    ov = core.index_overlap(p1, p2)
    mean_ov = np.mean(list(ov.values()))
    assert mean_ov < 0.15, f"random 3% masks should barely overlap: {mean_ov}"


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["lora", "dora", "shira-dora"])
def test_baseline_adapters_train_signal(setup, kind):
    cfg, params, batch = setup
    acfg = AdapterConfig(kind=kind, mask="wm", sparsity=0.95, rank=4)
    t, aux = core.init_adapter(jax.random.PRNGKey(0), params, acfg)

    def loss_fn(t):
        eff = core.materialize(params, t, aux, acfg)
        return lm.train_loss(eff, cfg, batch)[0]

    g = jax.grad(loss_fn)(t)
    gn = sum(float(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(g))
    assert gn > 0, f"{kind}: no gradient signal"
    assert all(jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(g))


def test_shira_dora_changes_only_masked_entries(setup):
    cfg, params, _ = setup
    acfg = AdapterConfig(kind="shira-dora", mask="wm", sparsity=0.95, rank=4)
    t, aux = core.init_adapter(jax.random.PRNGKey(0), params, acfg)
    t = jax.tree.map(
        lambda x: x + 0.1 if isinstance(x, jnp.ndarray) else x, t)
    eff = core.materialize(params, t, aux, acfg)
    ch = core.switching.changed_fraction(params, eff)
    assert ch < 0.2, f"shira-dora must stay sparse in fused mode: %C={ch}"


def test_lora_engine_fuse_preserves_tuple_structure():
    """Regression: LoraEngine.fuse's tree walk returned a list for BOTH
    list and tuple nodes, corrupting the pytree structure of tuple-bearing
    param trees (jit caches and tree_maps then mismatch)."""
    params = {"stages": ({"wq": jnp.ones((4, 4))},
                         {"wq": jnp.ones((4, 4))}),
              "aux": [jnp.zeros((2, 2))]}
    lora = {"stages/0/wq": {"A": jnp.ones((4, 2)), "B": jnp.ones((2, 4))}}
    eng = core.LoraEngine(params)
    eng.fuse(lora, scale=0.5)
    assert (jax.tree_util.tree_structure(eng.params)
            == jax.tree_util.tree_structure(params))
    assert isinstance(eng.params["stages"], tuple)
    assert isinstance(eng.params["aux"], list)
    np.testing.assert_allclose(np.asarray(eng.params["stages"][0]["wq"]),
                               1.0 + 0.5 * 2.0)
    np.testing.assert_allclose(np.asarray(eng.params["stages"][1]["wq"]), 1.0)
    eng.unfuse()
    np.testing.assert_allclose(np.asarray(eng.params["stages"][0]["wq"]), 1.0)


def test_changed_fraction_single_jitted_reduction():
    """changed_fraction must stay correct after being batched into one
    jitted reduction (incl. mixed dtypes and tuple-bearing trees)."""
    base = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": (jnp.zeros((5,), jnp.int32), jnp.ones((2, 2), jnp.bfloat16))}
    switched = jax.tree.map(lambda x: x, base)
    assert core.switching.changed_fraction(base, switched) == 0.0
    switched = {"a": base["a"].at[0, 0].set(99.0),
                "b": (base["b"][0].at[2].set(7), base["b"][1])}
    got = core.switching.changed_fraction(base, switched)
    assert got == pytest.approx(2 / (12 + 5 + 4))
