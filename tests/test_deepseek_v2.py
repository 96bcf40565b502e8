"""DeepSeek-V2(-Lite) block against a plain f32 reference, at the smoke size.

The serving path (``lm.prefill`` then ``lm.decode_step`` at per-row
positions, MLA latent cache, dropless held-expert layer with the
``moe_gmm`` kernel interpreted) is compared with ``ref_deepseek_v2``'s full
forward; the expert share, YaRN, dropless routing, SHiRA packs on stacked
expert leaves and the kernel each get a test of their own.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ref_deepseek_v2 as ref
from repro import core
from repro.configs import get_config, get_smoke_config
from repro.configs.base import AdapterConfig
from repro.core import masks as M
from repro.kernels.moe_gmm import moe_gmm
from repro.models import layers, lm, moe
from repro.models.attention import mla_softmax_scale

# f32 reassociation only: the program sums the same f32 products in another
# order (absorbed decode, grouped expert rows, chunked softmax); smoke-size
# logits are O(1), so reordering moves them by ~1e-6. bf16 compute moves
# them by ~1e-2 and must fail this.
LOGIT_TOL = 2e-4


def _share_cfg():
    """The smoke block with a chip's share: experts 1-2 of the router's 4."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    return cfg.replace(moe=dataclasses.replace(cfg.moe, held_experts=2,
                                               first_held=1))


def _serve(cfg, params, prompts, steps):
    """Prefill each row group at its own length into one latent cache, then
    decode ``steps`` tokens for every row at its own position. Returns the
    served tokens per row and the logits of every served position."""
    cache = max(p.shape[1] for p in prompts) + steps + 4
    prefill = jax.jit(lambda p, t: lm.prefill(p, cfg, {"tokens": t}, cache))
    decode = jax.jit(lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos))
    firsts, caches, lens = [], [], []
    for t in prompts:
        logits, c = prefill(params, t)
        firsts.append(logits)
        caches.append(c)
        lens += [t.shape[1]] * t.shape[0]
    caches = jax.tree.map(lambda *a: jnp.concatenate(a, axis=1), *caches)
    logits = jnp.concatenate(firsts)
    pos = jnp.asarray(lens, jnp.int32)
    outs, served = [logits], []
    for i in range(steps):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        served.append(tok)
        logits, caches = decode(params, tok[:, None], caches, pos + i)
        outs.append(logits)
    return np.stack([np.asarray(t) for t in served], 1), outs, lens


def _prefill_decode_gap(dtype):
    cfg = _share_cfg()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    prompts = [jax.random.randint(k[0], (2, 12), 0, cfg.vocab_size),
               jax.random.randint(k[1], (2, 7), 0, cfg.vocab_size)]
    steps = 4
    with layers.compute_precision(dtype):
        served, outs, lens = _serve(cfg, params, prompts, steps)
    worst = 0.0
    rows = [np.asarray(r) for p in prompts for r in p]
    for b, (row, n) in enumerate(zip(rows, lens)):
        seq = np.concatenate([row, served[b]])
        full = np.asarray(ref.forward(params, cfg, seq[None]))[0]
        for i in range(steps + 1):
            got = np.asarray(outs[i][b], np.float32)[:cfg.vocab_size]
            worst = max(worst, float(np.max(np.abs(got - full[n - 1 + i]))))
    return worst


def test_prefill_decode_logits_match_reference():
    assert _prefill_decode_gap(jnp.float32) < LOGIT_TOL


def test_prefill_decode_tolerance_rejects_bf16():
    """The tolerance is tight enough: bf16 compute fails it."""
    assert _prefill_decode_gap(jnp.bfloat16) > LOGIT_TOL


def _moe_params(cfg, seed=3):
    return moe.init_moe(jax.random.PRNGKey(seed), cfg)


def _held(p, first, n):
    out = dict(p)
    for name in ("experts_w_up", "experts_w_gate", "experts_w_down"):
        out[name] = p[name][first:first + n]
    return out


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of one expert each: their routed parts, plus the shared
    experts counted once, give the uncut reference layer."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.d_model))
    with layers.compute_precision(jnp.float32):
        top_p, top_i, _ = moe.route(p, cfg, x)
        total = sum(moe.routed_local(_held(p, e, 1), cfg, x, e, top_p,
                                     top_i)[0]
                    for e in range(cfg.moe.num_experts))
        total = total + layers.mlp(p["shared"], x, act="silu")
    want = ref.moe_layer(p, cfg, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_routing_is_dropless():
    """A router that sends every token to expert 0 (and, by the tie, 1):
    the serving and the training expert layer still match the reference.
    The capacity buffer they replaced held max(1.25 T k / E, min(T, 512))
    = 800 choices an expert, and would have dropped 480 of each 1280."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    p = _moe_params(cfg)
    p["w_router"] = jnp.zeros_like(p["w_router"]).at[0, 0].set(50.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 640, cfg.d_model))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    want = np.asarray(ref.moe_layer(p, cfg, x.reshape(-1, cfg.d_model)))
    with layers.compute_precision(jnp.float32):
        y, counts = moe.moe_serve(p, cfg, x)
        trained, _ = moe.moe_ffn(p, cfg, x)
    T, k, E = 1280, cfg.moe.top_k, cfg.moe.num_experts
    assert [int(c) for c in counts] == [T, T, 0, 0]
    assert max(round(1.25 * T * k / E), min(T, 512)) == 800
    for got in (y, trained):
        np.testing.assert_allclose(np.asarray(got).reshape(want.shape), want,
                                   atol=1e-4, rtol=1e-4)


def test_yarn_frequencies_and_mscale():
    cfg = get_config("deepseek-v2-lite-16b")
    inv = np.asarray(layers.rope_freqs(64, 10000.0, cfg.yarn))
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # correction range of HF's yarn: floor(10.47) = 10 .. ceil(22.51) = 23
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    r = (16 - 10) / 13
    np.testing.assert_allclose(inv[16], base[16] / 40 * r + base[16] * (1 - r),
                               rtol=1e-6)
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(64, 10000.0, cfg.yarn),
                               rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1          # 1.2608...
    assert abs(mla_softmax_scale(cfg) - mscale ** 2 / math.sqrt(192)) < 1e-12
    assert abs(mla_softmax_scale(cfg) - 0.114722) < 1e-5
    plain = get_config("granite-moe-1b-a400m")
    assert plain.yarn is None and plain.moe.norm_topk_prob


def test_expert_packs_load_and_unload_through_switch_engine():
    """Packs on (L, E, n, m) expert leaves: a 2% budget per (layer, expert)
    matrix; SwitchEngine scatters them in and back out to the base."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    targets = ("experts_w_up", "experts_w_gate", "experts_w_down", "wq")
    acfg = AdapterConfig(kind="shira", mask="rand", sparsity=0.98,
                         target_modules=targets)
    idx = M.make_packed_indices(params, acfg, jax.random.PRNGKey(1))
    up = idx["stages"][1]["moe"]["experts_w_up"]
    L, E = cfg.num_layers - 1, cfg.moe.num_experts
    assert up.shape == (L, E, M.budget(cfg.d_model, cfg.moe.d_ff, 0.98))
    values, aux = core.init_adapter(jax.random.PRNGKey(2), params, acfg)
    values = jax.tree.map(lambda v: None if v is None else
                          0.1 + jnp.zeros(v.shape), values,
                          is_leaf=lambda x: x is None)
    pack = core.pack_from_shira("e", values, aux)
    assert "stages/1/moe/experts_w_down" in pack.entries
    engine = core.SwitchEngine(params)
    engine.switch(pack)
    w = engine.params["stages"][1]["moe"]["experts_w_up"]
    base = params["stages"][1]["moe"]["experts_w_up"]
    changed = np.asarray(w != base).reshape(L, E, -1).sum(-1)
    assert np.all(changed == up.shape[-1])
    engine.unload()
    for a, b in zip(jax.tree.leaves(engine.params), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


TILE_EXPERT = [1, 1, 3, 3, 3]        # tiles 0-2 live, 3-4 room


def _gmm_case(gated, stacked):
    """Empty and uneven groups: experts 0 and 2 hold no rows, expert 1
    two tiles, expert 3 one; the last two tiles are room nobody uses.
    ``stacked``: the weights are layer 1 of a (2, E, K, N) stack."""
    tm, K, N, E = 16, 64, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    x = jax.random.normal(ks[0], (5 * tm, K))
    ws = tuple(jax.random.normal(k, (2, E, K, N)) / 8 for k in ks[1:3])
    ws = ws if gated else ws[:1]
    if not stacked:
        ws = tuple(w[1] for w in ws)
    return x, ws, tm


def _gmm_jnp(x, ws, tm, layer):
    """The live tiles' rows times their expert's weights, row by row."""
    out = []
    for t, e in enumerate(TILE_EXPERT[:3]):
        rows = x[t * tm:(t + 1) * tm]
        w = [v[e] if layer is None else v[layer, e] for v in ws]
        out.append(jax.nn.silu(rows @ w[0]) * (rows @ w[1]) if len(w) == 2
                   else rows @ w[0])
    return jnp.concatenate(out)


def _gmm(x, ws, tm, layer):
    return moe_gmm(x, ws, jnp.asarray(TILE_EXPERT, jnp.int32), 3, tm=tm,
                   layer=layer, compute_dtype=jnp.float32, interpret=True)


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("gated", [True, False])
def test_moe_gmm_interpret_matches_jnp(gated, stacked):
    x, ws, tm = _gmm_case(gated, stacked)
    layer = 1 if stacked else None
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_gmm_jnp(x, ws, tm, layer))
    got = np.asarray(_gmm(x, ws, tm, layer))[:3 * tm]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("gated", [True, False])
def test_moe_gmm_gradient_matches_jnp(gated, stacked):
    """The grouped backward (megablox gmm and tgmm, interpreted) gives the
    rows' and the weights' gradients of the plain computation; rows past
    the live tiles and experts with no rows get none."""
    x, ws, tm = _gmm_case(gated, stacked)
    layer = 1 if stacked else None
    r = jax.random.normal(jax.random.PRNGKey(7), (3 * tm, ws[0].shape[-1]))

    def loss(fn):
        return lambda x, ws: jnp.sum(fn(x, ws, tm, layer)[:3 * tm] * r)

    got = jax.grad(loss(_gmm), argnums=(0, 1))(x, ws)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(_gmm_jnp), argnums=(0, 1))(x, ws)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
    assert not np.any(np.asarray(got[0])[3 * tm:])
