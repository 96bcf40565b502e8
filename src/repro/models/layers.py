"""Basic building blocks: norms, embeddings, RoPE, MLPs, init helpers.

All modules are plain functions over pytrees of arrays (no framework). Matmuls
run in ``compute_dtype`` (bf16) with f32 accumulation; norms run in f32.
Parameter leaves use a *naming convention* that the sharding rules and the
adapter machinery key off (see repro/launch/sharding.py and repro/core/masks.py):

  wq wk wv wo        attention projections
  w_up w_gate w_down MLP projections
  in_proj out_proj   mamba mixer projections
  w_dkv w_uk w_uv wq_a wq_b  MLA projections
  emb lm_head        embeddings / unembedding
  scale bias         norm scale / linear bias (never adapted, never TP-sharded)
"""
from __future__ import annotations

import contextlib
import math
from typing import Optional

import jax
import jax.numpy as jnp

COMPUTE_DTYPE = jnp.bfloat16  # default; see compute_precision()


def compute_dtype():
    """The current matmul/activation dtype. Module code reads this at trace
    time so ``compute_precision`` can override it per scope."""
    return COMPUTE_DTYPE


@contextlib.contextmanager
def compute_precision(dtype):
    """Temporarily override the compute dtype (default bf16).

    Used by the multi-tenant serving parity tests/benchmarks, which compare
    two numerically different evaluation orders and need f32 matmuls for a
    meaningful tolerance. Jitted closures must be *traced* inside the scope:
    the dtype is read at trace time, and a closure traced outside the scope
    keeps whatever dtype was active then.
    """
    global COMPUTE_DTYPE
    prev = COMPUTE_DTYPE
    COMPUTE_DTYPE = dtype
    try:
        yield
    finally:
        COMPUTE_DTYPE = prev


def cast_compute(tree):
    """Cast >=2D float params to bf16 once per step (mixed precision: f32
    master weights live in the optimizer; all FSDP gathers / TP collectives
    then move bf16, halving parameter traffic)."""
    return jax.tree.map(
        lambda x: x.astype(compute_dtype())
        if (hasattr(x, "ndim") and x.ndim >= 2
            and jnp.issubdtype(x.dtype, jnp.floating)) else x,
        tree)


# ---------------------------------------------------------------------------
# Side-delta weights (multi-tenant serving)
# ---------------------------------------------------------------------------
# A weight leaf may be replaced by a dict bundling the shared base matrix
# with a per-adapter sparse-delta table and the batch's per-request adapter
# ids (see repro/serving/multitenant.py). ``pdot`` then computes the base
# matmul once for the whole batch plus each request's sparse correction via
# the Pallas sidedelta kernel. The bundle is a plain dict so it survives
# jax.lax.scan slicing over stacked layer weights. Tables may be quantized:
# ``sd.vals`` int8 with a per-adapter ``sd.scale`` (dequantized inside the
# kernel's VMEM, so the resident adapter tables stay ~4x smaller).

SIDEDELTA_KEY = "sd.base"

# Which execution mode the sidedelta kernel uses, read at TRACE time (same
# discipline as compute_precision): None = auto (Pallas interpret emulation
# off-TPU, compiled Mosaic on TPU); True/False force it. interpret=False
# off-TPU compiles the XLA twin, which computes the same sums from the same
# tables in any order. "xla"
# forces the pure-jnp XLA twin on every backend; the twin is differentiable
# w.r.t. the value tables, which the multi-adapter trainer's forward needs.
SIDEDELTA_INTERPRET = None


def sidedelta_interpret():
    if SIDEDELTA_INTERPRET is None:
        return jax.default_backend() != "tpu"
    return SIDEDELTA_INTERPRET


@contextlib.contextmanager
def sidedelta_backend(interpret):
    """Temporarily force the sidedelta kernel mode (True, False, or "xla").
    Jitted closures must be *traced* inside the scope — the flag is read at
    trace time."""
    global SIDEDELTA_INTERPRET
    prev = SIDEDELTA_INTERPRET
    SIDEDELTA_INTERPRET = interpret
    try:
        yield
    finally:
        SIDEDELTA_INTERPRET = prev


def sidedelta_weight(base: jax.Array, rows: jax.Array, cols: jax.Array,
                     vals: jax.Array, ids: jax.Array,
                     scale: Optional[jax.Array] = None,
                     blocks: Optional[jax.Array] = None) -> dict:
    """base: (n, m); rows/cols/vals: (A, K) packed per-adapter deltas
    (vals f32, or int8 with per-adapter ``scale`` (A,) f32);
    ids: (B,) int32 per-request adapter slot (-1 = base only);
    blocks: (A, C) sub-chunk origins of block-bucketed tables (the Pallas
    kernel needs them; the XLA twin reads tables in any order)."""
    w = {SIDEDELTA_KEY: base, "sd.rows": rows, "sd.cols": cols,
         "sd.vals": vals, "sd.ids": ids}
    if scale is not None:
        w["sd.scale"] = scale
    if blocks is not None:
        w["sd.blocks"] = blocks
    return w


def is_sidedelta(w) -> bool:
    return isinstance(w, dict) and SIDEDELTA_KEY in w


def pdot(x: jax.Array, w: jax.Array) -> jax.Array:
    """Matmul in bf16 (MXU accumulates f32 internally on TPU; bf16 output
    keeps backward cotangents AND row-parallel psums in bf16 — found via the
    dry-run: f32 outputs made every backward collective 2x, see §Perf).

    ``w`` may also be a side-delta bundle (multi-tenant serving): then the
    result is x @ base + per-request sparse deltas routed by the bundled ids.
    """
    if is_sidedelta(w):
        return _pdot_sidedelta(x, w)
    return jax.lax.dot_general(
        x.astype(compute_dtype()),
        w.astype(compute_dtype()),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=compute_dtype(),
    )


def _pdot_sidedelta(x: jax.Array, w: dict) -> jax.Array:
    from repro.kernels.ops import sidedelta  # deferred: kernels are leaf deps
    base = w[SIDEDELTA_KEY]
    if x.ndim == 2:
        # Flattened-token call sites (MoE shared experts): the model only
        # ever flattens row-major from (B, S, d), so the request axis is
        # recoverable from the bundled per-request ids. Single-program
        # serving only — an EP shard's local batch would divide B wrongly.
        B = w["sd.ids"].shape[0]
        T = x.shape[0]
        assert T % B == 0, (f"flattened tokens {T} not divisible by batch "
                            f"{B} at a side-delta weight")
        y2 = _pdot_sidedelta(x.reshape(B, T // B, x.shape[-1]), w)
        return y2.reshape(T, y2.shape[-1])
    assert x.ndim == 3, ("side-delta weights serve batched (B, S, d) "
                         f"activations, got {x.shape}")
    y = pdot(x, base)
    delta = sidedelta(x, w["sd.rows"], w["sd.cols"], w["sd.vals"],
                      w["sd.ids"], m=base.shape[-1],
                      blocks=w.get("sd.blocks"), scale=w.get("sd.scale"),
                      interpret=sidedelta_interpret())
    return (y.astype(jnp.float32) + delta).astype(y.dtype)


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    y = pdot(x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(compute_dtype())


def init_rms_norm(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def glorot(key, shape, in_axis=-2, out_axis=-1, dtype=jnp.float32):
    fan_in = shape[in_axis]
    fan_out = shape[out_axis]
    std = math.sqrt(2.0 / (fan_in + fan_out))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def normal_init(key, shape, std=0.02, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, yarn=None) -> jax.Array:
    """Inverse frequencies, shape (head_dim // 2,). With a ``YaRNConfig``,
    YaRN's blend as DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` builds
    it: frequencies that turn fewer than ``beta_slow`` times over the
    original context are divided by ``factor``, those that turn more than
    ``beta_fast`` times are kept, with a linear ramp between."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv = 1.0 / (theta ** exponent)
    if yarn is None:
        return inv

    def dim_of(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """x: (..., S, H, D); positions: (S,) or (..., S). Split-half convention.
    ``yarn`` (a ``YaRNConfig``) scales the frequencies and, where its two
    mscales differ, cos and sin."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta, yarn)                 # (d/2,)
    angles = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, d/2)
    mult = 1.0 if yarn is None else (yarn_mscale(yarn.factor, yarn.mscale)
                                     / yarn_mscale(yarn.factor,
                                                   yarn.mscale_all_dim))
    cos = jnp.cos(angles)[..., :, None, :] * mult    # (..., S, 1, d/2)
    sin = jnp.sin(angles)[..., :, None, :] * mult
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, act: str = "silu") -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_up": glorot(k1, (d_model, d_ff)),
        "w_down": glorot(k2, (d_ff, d_model)),
    }
    if act == "silu":  # SwiGLU
        p["w_gate"] = glorot(k3, (d_model, d_ff))
    return p


def mlp(params: dict, x: jax.Array, act: str = "silu") -> jax.Array:
    up = dense(x, params["w_up"])
    if act == "silu":
        gate = dense(x, params["w_gate"])
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(compute_dtype()) * up
    else:
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(compute_dtype())
    return dense(h, params["w_down"])


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int) -> dict:
    return {"emb": normal_init(key, (vocab, d_model), std=0.02)}


def embed(params: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["emb"], tokens, axis=0).astype(compute_dtype())


def unembed(params: dict, h: jax.Array, tie_to: Optional[jax.Array] = None,
            softcap: float = 0.0, logical_vocab: int = 0) -> jax.Array:
    w = tie_to.T if tie_to is not None else params["lm_head"]
    logits = jax.lax.dot_general(
        h.astype(compute_dtype()), w.astype(compute_dtype()),
        (((h.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    if logical_vocab and logical_vocab < w.shape[-1]:
        pad_mask = jnp.arange(w.shape[-1]) >= logical_vocab
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits  # f32


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token CE. logits f32 (..., V), labels int (...)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
