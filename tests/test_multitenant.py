"""Multi-tenant serving: per-request batched SHiRA deltas in one batch.

Parity contract: a mixed-adapter batch served by MultiTenantEngine (one
forward pass, per-request side-deltas) must match serving each request
alone after SwitchEngine-switching to its adapter. Run in f32 compute —
the two paths evaluate the delta in different orders, so bf16 would bury
the comparison in rounding noise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs import AdapterConfig, get_smoke_config
from repro.core.adapters import AdapterPack
from repro.core.switching import FusedLRU
from repro.models import layers, lm
from repro.serving import MultiTenantEngine
from repro.serving.multitenant import switch_per_request_reference

TARGETS = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "out_proj")


def make_packs(cfg, params, n, seed=7, scale=0.05):
    acfg = AdapterConfig(kind="shira", mask="rand", sparsity=0.98,
                         target_modules=TARGETS)
    packs = []
    for i in range(n):
        sub = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        values, aux = core.init_adapter(sub, params, acfg)
        values = jax.tree.map(
            lambda v: None if v is None
            else scale * jax.random.normal(sub, v.shape), values,
            is_leaf=lambda x: x is None)
        packs.append(core.pack_from_shira(f"a{i}", values, aux))
    return packs


def sequential_reference(cfg, params, packs, toks, names, tokens):
    out, logits, _ = switch_per_request_reference(cfg, params, packs, toks,
                                                  names, tokens)
    return out, logits


@pytest.fixture(scope="module")
def dense_setup():
    with layers.compute_precision(jnp.float32):
        cfg = get_smoke_config("starcoder2-7b")
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        packs = make_packs(cfg, params, 3)
        yield cfg, params, packs


def test_batched_matches_sequential_switching(dense_setup):
    """≥3 distinct adapters + base traffic in ONE batch must reproduce the
    sequential switch-per-request outputs (greedy tokens + fp32 logits)."""
    with layers.compute_precision(jnp.float32):
        cfg, params, packs = dense_setup
        engine = MultiTenantEngine(cfg, params)
        for p in packs:
            engine.register(p)
        B, S, T = 5, 8, 4
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                  cfg.vocab_size)
        names = ["a0", "a2", None, "a1", "a0"]
        assert len({n for n in names if n}) >= 3
        out_mt, _ = engine.generate({"tokens": toks}, names, T)
        out_seq, logits_seq = sequential_reference(
            cfg, params, packs, np.asarray(toks), names, T)
        np.testing.assert_array_equal(np.asarray(out_mt), out_seq)

        # logits parity at the last step, batched path
        from repro.serving.multitenant import greedy_decode
        ids = engine.ids_for(names)
        p = engine.wrapped_params(ids)
        _, logits = greedy_decode(
            cfg, {"tokens": toks}, T,
            lambda b: engine._prefill(p, b, S + T + 8),
            lambda t, c, pos: engine._decode(p, t, c, pos))
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   logits_seq, atol=1e-3)


def test_multitenant_mamba_arch():
    """The scan-sliced side-delta bundle must also work for ssm stacks
    (out_proj adapters ride inside the mamba mixer)."""
    with layers.compute_precision(jnp.float32):
        cfg = get_smoke_config("mamba2-780m")
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        packs = make_packs(cfg, params, 3)
        engine = MultiTenantEngine(cfg, params)
        for p in packs:
            engine.register(p)
        B, S, T = 4, 8, 3
        toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                  cfg.vocab_size)
        names = ["a0", "a1", "a2", None]
        out_mt, _ = engine.generate({"tokens": toks}, names, T)
        out_seq, _ = sequential_reference(cfg, params, packs,
                                          np.asarray(toks), names, T)
        np.testing.assert_array_equal(np.asarray(out_mt), out_seq)


def test_multitenant_moe_mla_arch():
    """MoE shared experts consume flattened (B*S, d) tokens — the side-delta
    path must recover the request axis; MLA's w_dkv/wq projections ride the
    normal 3D path (w_uk/w_uv stay excluded)."""
    with layers.compute_precision(jnp.float32):
        cfg = get_smoke_config("deepseek-v2-lite-16b")
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        targets = ("wq", "wq_a", "wq_b", "wo", "w_up", "w_gate", "w_down",
                   "w_dkv")
        acfg = AdapterConfig(kind="shira", mask="rand", sparsity=0.98,
                             target_modules=targets)
        packs = []
        for i in range(3):
            sub = jax.random.fold_in(jax.random.PRNGKey(9), i)
            values, aux = core.init_adapter(sub, params, acfg)
            values = jax.tree.map(
                lambda v: None if v is None
                else 0.05 * jax.random.normal(sub, v.shape), values,
                is_leaf=lambda x: x is None)
            packs.append(core.pack_from_shira(f"a{i}", values, aux))
        engine = MultiTenantEngine(cfg, params)
        for p in packs:
            engine.register(p)
        B, S, T = 4, 8, 3
        toks = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0,
                                  cfg.vocab_size)
        names = ["a0", "a1", "a2", None]
        out_mt, _ = engine.generate({"tokens": toks}, names, T)
        out_seq, _ = sequential_reference(cfg, params, packs,
                                          np.asarray(toks), names, T)
        np.testing.assert_array_equal(np.asarray(out_mt), out_seq)


def test_scheduler_promotion_preserves_outputs(dense_setup):
    """Fusing the hot adapter into the shared base (and serving the others
    with diff packs) must not change any tenant's output."""
    with layers.compute_precision(jnp.float32):
        cfg, params, packs = dense_setup
        plain = MultiTenantEngine(cfg, params)
        sched = MultiTenantEngine(
            cfg, params, scheduler=FusedLRU(promote_at=0.5, decay=0.0))
        for p in packs:
            plain.register(p)
            sched.register(p)
        B, S, T = 4, 8, 3
        toks = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0,
                                  cfg.vocab_size)
        names = ["a1", "a1", "a1", None]       # a1-dominated traffic
        want, _ = plain.generate({"tokens": toks}, names, T)
        got, _ = sched.generate({"tokens": toks}, names, T)
        assert sched.fused == "a1"
        assert sched.fuse_transitions == 1
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

        # traffic spreads out (nobody reaches promote_at) -> demotion
        # restores the un-fused base
        names2 = ["a0", "a2", None, None]
        want2, _ = plain.generate({"tokens": toks}, names2, T)
        got2, _ = sched.generate({"tokens": toks}, names2, T)
        assert sched.fused is None
        np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))
        for a, b in zip(jax.tree.leaves(sched.shared),
                        jax.tree.leaves(params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


def test_reregister_fused_adapter_demotes_first(dense_setup):
    """Replacing the pack of the currently-fused adapter must un-fuse the
    OLD delta first, or the base is corrupted forever."""
    with layers.compute_precision(jnp.float32):
        cfg, params, packs = dense_setup
        engine = MultiTenantEngine(
            cfg, params, scheduler=FusedLRU(promote_at=0.5, decay=0.0))
        for p in packs:
            engine.register(p)
        B, S, T = 2, 8, 2
        toks = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0,
                                  cfg.vocab_size)
        engine.generate({"tokens": toks}, ["a0", "a0"], T)
        assert engine.fused == "a0"
        v2 = make_packs(cfg, params, 1, seed=33, scale=0.07)[0]  # new "a0"
        engine.register(v2)
        assert engine.fused is None            # old delta scattered back out
        for a, b in zip(jax.tree.leaves(engine.shared),
                        jax.tree.leaves(params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        # and the new pack really serves (parity vs sequential)
        out, _ = engine.generate({"tokens": toks}, ["a0", None], T)
        want, _ = sequential_reference(cfg, params, [v2] + packs[1:],
                                       np.asarray(toks), ["a0", None], T)
        np.testing.assert_array_equal(np.asarray(out), want)


def test_fused_lru_policy():
    s = FusedLRU(promote_at=0.5, demote_at=0.2, decay=0.0, max_idle=2)
    d = s.observe(["a", "a", "b", None])        # a at 50% -> promote
    assert d.promote == "a" and s.fused == "a"
    d = s.observe(["a", "a", "a", "a"])
    assert d.promote is None and d.demote is None and s.fused == "a"
    d = s.observe(["b", "b", "b", "b"])         # b hot -> swap fused state
    assert d.promote == "b" and d.demote == "a" and s.fused == "b"
    d = s.observe([None, None, None, None])     # b share crashes -> demote
    assert d.demote == "b" and s.fused is None
    # LRU/idle demotion: promote c, then starve it below demote_at=0 share
    s2 = FusedLRU(promote_at=0.5, demote_at=0.0, decay=1.0, max_idle=2)
    s2.share["c"] = 1.0
    s2.observe(["c", "c"])
    assert s2.fused == "c"
    s2.observe(["d"])
    d = s2.observe(["d"])                       # idle for max_idle steps
    assert d.demote == "c" and s2.fused is None


def test_fused_lru_tie_break_deterministic():
    """Equal shares must promote the lexicographically-first adapter, not
    whichever dict insertion order happens to yield (regression: promotion
    used to depend on the order tenants were first observed)."""
    for first_seen in (["b", "b", "a", "a"], ["a", "a", "b", "b"]):
        s = FusedLRU(promote_at=0.5, demote_at=0.2, decay=0.0)
        d = s.observe(first_seen)           # both at exactly 50%
        assert d.promote == "a", first_seen
        assert s.fused == "a"


def test_fused_lru_capacity_groups():
    """capacity=2 promotes a hot adapter *stack* as a group; capacity=1
    never does, even when the stack dominates traffic."""
    hot = [("a0", "a1")] * 3 + ["a2"]
    s1 = FusedLRU(promote_at=0.5, decay=0.0, capacity=1)
    d = s1.observe(hot)
    assert d.promote is None and s1.fused is None
    s2 = FusedLRU(promote_at=0.5, decay=0.0, capacity=2)
    d = s2.observe(hot)
    assert d.promote == ("a0", "a1") and s2.fused == ("a0", "a1")
    # normalization: member order within a stack does not split traffic
    s3 = FusedLRU(promote_at=0.5, decay=0.0, capacity=2)
    d = s3.observe([("a1", "a0"), ("a0", "a1"), ("a1", "a0"), "a2"])
    assert d.promote == ("a0", "a1")
    # demotion restores the un-fused state
    d = s2.observe(["a2", "a2", "a2", "a2"])
    assert d.demote == ("a0", "a1")


def test_group_fusion_preserves_outputs(dense_setup):
    """Fusing a hot STACK into the shared base (capacity=2) must not change
    any tenant's output: stack members, other adapters, and base traffic
    are all served off group-aware diff packs."""
    with layers.compute_precision(jnp.float32):
        cfg, params, packs = dense_setup
        plain = MultiTenantEngine(cfg, params)
        sched = MultiTenantEngine(
            cfg, params,
            scheduler=FusedLRU(promote_at=0.5, decay=0.0, capacity=2))
        for p in packs:
            plain.register(p)
            sched.register(p)
        B, S, T = 4, 8, 3
        toks = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0,
                                  cfg.vocab_size)
        names = [("a0", "a1"), ("a1", "a0"), "a2", None]  # stack-dominated
        want, _ = plain.generate({"tokens": toks}, names, T)
        got, _ = sched.generate({"tokens": toks}, names, T)
        assert sched.fused == ("a0", "a1")
        assert sched.fuse_transitions == 1
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

        # spread traffic -> demote; the base must return to pristine
        names2 = ["a0", "a2", None, None]
        want2, _ = plain.generate({"tokens": toks}, names2, T)
        got2, _ = sched.generate({"tokens": toks}, names2, T)
        assert sched.fused is None
        np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))
        for a, b in zip(jax.tree.leaves(sched.shared),
                        jax.tree.leaves(params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


def test_int8_device_tables_serving(dense_setup):
    """int8 device-side tables (store QuantPack -> engine tables with no
    f32 round trip) must reproduce the sequential reference served with
    the dequantized packs: same greedy tokens, logits within 1e-2. The
    int8 ``vals`` tables must be >=3x smaller than the f32 ones (int8 vs
    f32 values) and the whole table set >=2x smaller (int16 indices)."""
    import tempfile

    from repro.hub import AdapterStore
    with layers.compute_precision(jnp.float32):
        cfg, params, packs = dense_setup
        store = AdapterStore(tempfile.mkdtemp(prefix="mt-int8-"))
        for p in packs:
            store.add(p, values="int8")
        eng8 = MultiTenantEngine(cfg, params, store=store,
                                 table_dtype="int8")
        engf = MultiTenantEngine(cfg, params, store=store,
                                 table_dtype="f32")
        for p in packs:
            eng8.register(p.name)
            engf.register(p.name)
        # the quantized resident form reached the engine un-dequantized
        assert set(eng8._qpacks) == {p.name for p in packs}
        B, S, T = 5, 8, 4
        toks = jax.random.randint(jax.random.PRNGKey(8), (B, S), 0,
                                  cfg.vocab_size)
        names = ["a0", "a2", None, "a1", "a0"]
        out8, _ = eng8.generate({"tokens": toks}, names, T)
        dq = [store.get(p.name) for p in packs]   # what int8 really serves
        out_seq, logits_seq = sequential_reference(
            cfg, params, dq, np.asarray(toks), names, T)
        np.testing.assert_array_equal(np.asarray(out8), out_seq)

        from repro.serving.multitenant import greedy_decode
        ids = eng8.ids_for(names)
        p8 = eng8.wrapped_params(ids)
        _, logits8 = greedy_decode(
            cfg, {"tokens": toks}, T,
            lambda b: eng8._prefill(p8, b, S + T + 8),
            lambda t, c, pos: eng8._decode(p8, t, c, pos))
        np.testing.assert_allclose(np.asarray(logits8, np.float32),
                                   logits_seq, atol=1e-2)

        nb8, nbf = eng8.table_nbytes(), engf.table_nbytes()
        assert nbf["vals"] >= 3 * nb8["vals"], (nbf, nb8)
        assert nbf["total"] >= 2 * nb8["total"], (nbf, nb8)
        # int8 tables really are int8/int16 on device
        t = next(iter(eng8._tables.values()))
        assert t["vals"].dtype == jnp.int8
        assert t["rows"].dtype == jnp.int16
        assert "scale" in t


def test_int8_tables_skip_f32_roundtrip(dense_setup):
    """An adapter registered from an int8 store must land in the device
    tables with its ORIGINAL quantized values — one rounding at pack time,
    not a second quantization of the dequantized f32 form."""
    import tempfile

    from repro.hub import AdapterStore
    cfg, params, packs = dense_setup
    store = AdapterStore(tempfile.mkdtemp(prefix="mt-rt-"))
    store.add(packs[0], values="int8")
    engine = MultiTenantEngine(cfg, params, store=store, table_dtype="int8")
    engine.register(packs[0].name)
    engine._rebuild()
    qp = store.get_raw(packs[0].name)
    qtables = qp.int8_tables()
    path = next(iter(qtables))
    idx, vq, scale = qtables[path]
    t = engine._tables[path]
    k = idx.shape[-1]
    vals_dev = np.asarray(t["vals"]).reshape(-1, 1, t["vals"].shape[-1])
    np.testing.assert_array_equal(vals_dev[:, 0, :k],
                                  np.asarray(vq).reshape(vals_dev.shape[0],
                                                         -1))
    np.testing.assert_allclose(
        np.asarray(t["scale"]).reshape(-1)[0], scale * qp.alpha)


@pytest.mark.parametrize("table_dtype", ["f32", "int8"])
def test_sidedelta_dispatch(dense_setup, table_dtype):
    """The tables pick the side-delta formulation: every bundle the engine
    serves carries ``sd.blocks``, so serving always runs the kernel, and
    ``pdot`` over the same bundle without them (the trainer's plain jnp
    path) gives the same output."""
    from repro.hub import AdapterStore
    with layers.compute_precision(jnp.float32):
        cfg, params, packs = dense_setup
        store = AdapterStore()
        for p in packs:
            store.add(p, values=table_dtype)
        engine = MultiTenantEngine(cfg, params, store=store,
                                   table_dtype=table_dtype)
        for p in packs:
            engine.register(p.name)
        ids = engine.ids_for(["a0", None, "a2", "a1"])
        bundles = [w for w in jax.tree.leaves(engine.wrapped_params(ids),
                                              is_leaf=layers.is_sidedelta)
                   if layers.is_sidedelta(w)]
        assert bundles and all("sd.blocks" in w for w in bundles)
        w = bundles[0]
        while w[layers.SIDEDELTA_KEY].ndim > 2:   # one layer of a stack
            w = {k: v[0] for k, v in w.items()}
        assert ("sd.scale" in w) == (table_dtype == "int8")
        plain = {k: v for k, v in w.items() if k != "sd.blocks"}
        x = jax.random.normal(jax.random.PRNGKey(3),
                              (4, 5, w[layers.SIDEDELTA_KEY].shape[0]))
        kernel = layers.pdot(x, w)
        np.testing.assert_allclose(np.asarray(layers.pdot(x, plain)),
                                   np.asarray(kernel), atol=1e-5, rtol=1e-5)
        assert float(jnp.max(jnp.abs(kernel - layers.pdot(
            x, w[layers.SIDEDELTA_KEY])))) > 0    # the deltas are not zero


def test_unsupported_target_rejected():
    cfg = get_smoke_config("starcoder2-7b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    engine = MultiTenantEngine(cfg, params)
    bad = AdapterPack("bad", {"stages/0/attn/w_uk": (
        jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 4), jnp.float32))})
    with pytest.raises(ValueError, match="w_uk"):
        engine.register(bad)
    unknown = AdapterPack("unknown", {"no/such/wq": (
        jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 4), jnp.float32))})
    with pytest.raises(KeyError):
        engine.register(unknown)


def test_routed_expert_targets_rejected():
    """A side-delta bundle on routed experts would never reach pdot (the
    grouped expert matmul reads the base experts), so it is refused."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    engine = MultiTenantEngine(cfg, params)
    E = cfg.moe.num_experts
    for leaf in ("experts_w_up", "experts_w_gate", "experts_w_down"):
        bad = AdapterPack("bad", {f"stages/1/moe/{leaf}": (
            jnp.zeros((2, E, 4), jnp.int32),
            jnp.zeros((2, E, 4), jnp.float32))})
        with pytest.raises(ValueError, match="R2"):
            engine.register(bad)
