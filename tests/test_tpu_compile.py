"""Compile the main-path kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
topology that is described, not attached, so what Mosaic would refuse on
the chip (misaligned blocks, VMEM over-use) fails here at no chip time.
The widths are starcoder2-7b's published ones (d_model 4608, d_ff 18432)
with SHiRA packs at 2% density, as ``launch/serve.py`` makes them.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.
"""
import os
import re
from unittest import mock

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.sidedelta import sidedelta_rows

CFG = get_config("starcoder2-7b")
D, F = CFG.d_model, CFG.d_ff                     # 4608, 18432
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


@pytest.mark.parametrize("n,m,S,x_dtype,table", [
    (D, D, 1, jnp.bfloat16, "f32"),              # wq decode
    (D, F, 1, jnp.bfloat16, "f32"),              # w_up decode
    (D, D, PREFILL, jnp.bfloat16, "f32"),        # wq prefill
    (F, D, PREFILL, jnp.bfloat16, "f32"),        # w_down prefill
    (D, F, 1, jnp.bfloat16, "int8"),             # w_up decode, int8 tables
    (D, D, 1, jnp.float32, "f32"),               # f32 parity decode
])
def test_sidedelta_compiles_for_v5e(one_chip, n, m, S, x_dtype, table):
    K = _nnz(n, m)
    int8 = table == "int8"
    idx = jnp.int16 if int8 else jnp.int32

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [spec((BATCH, S, n), x_dtype), spec((ADAPTERS, K), idx),
            spec((ADAPTERS, K), idx),
            spec((ADAPTERS, K), jnp.int8 if int8 else jnp.float32),
            spec((BATCH,), jnp.int32), spec((ADAPTERS,), jnp.float32)]

    def fn(x, rows, cols, vals, ids, scale):
        return sidedelta_rows(x, rows, cols, vals, ids, m,
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
