"""Compile the main-path kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
topology that is described, not attached, so what Mosaic would refuse on
the chip (misaligned blocks, VMEM over-use) fails here at no chip time.
The widths are starcoder2-7b's published ones (d_model 4608, d_ff 18432),
plus deepseek-coder-33b's gated FFN (7168 x 19200), with SHiRA packs at 2%
density, as ``launch/serve.py`` makes them. The side-delta tables have the
shapes ``sidedelta_table`` gives a random mask of that density.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.
"""
import os
import re
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.sidedelta import sidedelta_rows, sidedelta_table

CFG = get_config("starcoder2-7b")
D, F = CFG.d_model, CFG.d_ff                     # 4608, 18432
DSC = get_config("deepseek-coder-33b")
D33, F33 = DSC.d_model, DSC.d_ff                 # 7168, 19200
DSV2 = get_config("deepseek-v2-lite-16b")        # d 2048, experts 1408 wide
DENSITY = 0.02
ADAPTERS = 3                                     # table slots
BATCH = 4
PREFILL = 128                                    # chip_smoke.py's prompt


def _nnz(n, m):
    return int(n * m * DENSITY)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can only be read back on a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_for_tpu(fn, *args):
    """Lower ``fn`` as it would trace on a TPU host: kernels that pick
    their path from ``jax.default_backend()`` take the Mosaic branch."""
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 10 ** 9, used             # v5e HBM
    return compiled


def _table_shape(n, m):
    """(K, C) of the bucketed table of one random 2% mask: K entries in C
    sub-chunks."""
    flat = np.random.default_rng(0).choice(n * m, _nnz(n, m), replace=False)
    t = sidedelta_table([flat], [np.ones(flat.shape, np.float32)], n, m)
    return t.rows.shape[1], t.blocks.shape[1]


@pytest.mark.parametrize("n,m,S,x_dtype,table", [
    (D, D, 1, jnp.bfloat16, "f32"),              # wq decode
    (D, F, 1, jnp.bfloat16, "f32"),              # w_up decode
    (D, D, PREFILL, jnp.bfloat16, "f32"),        # wq prefill
    (F, D, PREFILL, jnp.bfloat16, "f32"),        # w_down prefill
    (D, F, 1, jnp.bfloat16, "int8"),             # w_up decode, int8 tables
    (D, D, 1, jnp.float32, "f32"),               # f32 parity decode
    (F, D, 1, jnp.bfloat16, "f32"),              # w_down decode
    (D33, F33, 1, jnp.bfloat16, "f32"),          # deepseek-coder-33b w_up
])
def test_sidedelta_compiles_for_v5e(one_chip, n, m, S, x_dtype, table):
    K, C = _table_shape(n, m)
    int8 = table == "int8"
    idx = jnp.int16 if int8 else jnp.int32

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [spec((BATCH, S, n), x_dtype), spec((ADAPTERS, K), idx),
            spec((ADAPTERS, K), idx),
            spec((ADAPTERS, K), jnp.int8 if int8 else jnp.float32),
            spec((BATCH,), jnp.int32), spec((ADAPTERS,), jnp.float32),
            spec((ADAPTERS, C), jnp.int32)]

    def fn(x, rows, cols, vals, ids, scale, blocks):
        return sidedelta_rows(x, rows, cols, vals, ids, m, blocks=blocks,
                              scale=scale if int8 else None)

    hlo = _compile_for_tpu(fn, *args).as_text()
    # the benchmark's kernel time and roofline find the kernel by this name
    assert re.search(r"%sidedelta(\.\d+)* = [^\n]*custom-call\(", hlo)


@pytest.mark.parametrize("moments", [jnp.float32, jnp.int8])
def test_sparse_adamw_rows_compiles_for_v5e(one_chip, moments):
    # one row per (adapter, layer) of a 4-layer w_up pack
    R, K = ADAPTERS * 4, _nnz(D, F)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    int8 = moments == jnp.int8
    args = [spec((R, K), jnp.float32), spec((R, K), jnp.float32),
            spec((R, K), moments), spec((R, K), moments)]
    if int8:
        args += [spec((R,), jnp.float32), spec((R,), jnp.float32)]

    def fn(v, g, mu, nu, *scales):
        ms, us = scales if scales else (None, None)
        return ops.sparse_adamw_batched(v, g, mu, nu, 3, lr=1e-3,
                                        mu_scale=ms, nu_scale=us)

    _compile_for_tpu(fn, *args)


def _held_experts(one_chip, layers=None):
    import dataclasses
    cfg = DSV2.replace(moe=dataclasses.replace(DSV2.moe, held_experts=8))
    d, ff, E = cfg.d_model, cfg.moe.d_ff, 8
    lead = () if layers is None else (layers,)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"w_router": spec((d, cfg.moe.num_experts), jnp.float32),
              "experts_w_up": spec(lead + (E, d, ff), jnp.float32),
              "experts_w_gate": spec(lead + (E, d, ff), jnp.float32),
              "experts_w_down": spec(lead + (E, ff, d), jnp.float32)}
    return cfg, params, spec


@pytest.mark.parametrize("tokens", [64, 8 * 1024])     # decode, prefill
def test_moe_gmm_compiles_for_v5e(one_chip, tokens):
    """The held-expert layer at deepseek-v2-lite widths, 8 of 64 experts
    held, as a serving scan reads it: the 6 moe layers' (L, E, K, N) stacks
    whole, at a traced layer. Routing, tile layout and both grouped
    matmuls, for a decode step of 64 rows and a prefill of 8 x 1024 tokens
    (6 choices a token); no slice of the stacks is copied out."""
    from repro.models import moe
    cfg, params, spec = _held_experts(one_chip, layers=6)

    def fn(p, x, layer):
        top_p, top_i, _ = moe.route(p, cfg, x)
        return moe.routed_local(dict(p, layer=layer), cfg, x, 0, top_p,
                                top_i)

    hlo = _compile_for_tpu(fn, params, spec((tokens, cfg.d_model),
                                            jnp.bfloat16),
                           spec((), jnp.int32)).as_text()
    assert len(re.findall(r"%moe_gmm(\.\d+)* = [^\n]*custom-call\(",
                          hlo)) == 2
    assert not re.search(r"= f32\[8,(2048,1408|1408,2048)\][^\n]* "
                         r"(fusion|dynamic-slice|copy)\(", hlo)


def test_moe_gmm_gradient_compiles_for_v5e(one_chip):
    """The training expert layer's backward (megablox gmm and tgmm over the
    same tiles) at the same widths, for 2 x 1024 tokens."""
    from repro.models import moe
    cfg, params, spec = _held_experts(one_chip)

    def fn(p, x):
        def loss(p, x):
            top_p, top_i, _ = moe.route(p, cfg, x)
            y, _ = moe.routed_local(p, cfg, x, 0, top_p, top_i)
            return jnp.sum(y)
        return jax.grad(loss, argnums=(0, 1))(p, x)

    _compile_for_tpu(fn, params, spec((2 * 1024, cfg.d_model), jnp.bfloat16))
