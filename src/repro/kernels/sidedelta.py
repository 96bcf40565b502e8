"""sidedelta v3 — tiled, vectorised per-request sparse side-delta matmul.

Multi-tenant SHiRA serving keeps ONE shared copy of the base weights and
gives every request in a batch its own adapter. Instead of patching the
weight per request (which would serialize the batch), the forward pass adds
each request's sparse delta as a side term:

  y[b] = x[b] @ W_shared  +  x[b] @ dW_{id[b]},   dW sparse with K nonzeros

The side term never materialises dW: an adapter is a packed table of
(row, col, val) triples, and the kernel computes, for request b with
adapter a = ids[b],

  delta[b, :, cols[a, k]] += x[b, :, rows[a, k]] * vals[a, k]   for all k

Design (v3 — compiled-mode):

  * Grid = (B, S tiles, K chunks). Program (b, s, k) takes the k-th chunk
    of ``kc`` nonzeros of request b's adapter and adds its contribution to
    the (bs, m) output block of S tile s, which stays resident in VMEM
    across the K axis (the innermost, accumulating grid axis). VMEM per
    program is therefore independent of K and of the prompt length:
    ``s_block`` picks the S tile from the budget, ``plan_tiles`` picks
    (bm, kc) for the rest.
  * Vectorised body: no per-nonzero scalar stores. The chunk's input
    columns are gathered with one-hot matmuls over n-tiles of x
    (x (bs, bn) @ onehot(rows) (bn, kc)) and scaled by ``vals``; each
    m-tile of the output then scatter-accumulates with a second one-hot
    matmul ((bs, kc) @ onehot(cols - tile_start)^T). Nonzeros whose column
    falls outside the current tile produce an all-zero one-hot row, which
    is exactly the required mask. Each nonzero is gathered once per S
    tile, and the gathered chunk serves every m-tile.
  * Tables are laid out (A, 1, K) for the kernel so that a (1, kc) block
    satisfies Mosaic's tiling rule (the last two block dims are divisible
    by (8, 128) or equal to the array's).
  * int8 tables in VMEM: ``vals`` may be int8 with a per-adapter f32
    ``scale`` (scalar-prefetch operand); the kernel dequantises AFTER the
    DMA, inside VMEM, so adapter HBM at serve time shrinks ~4x vs f32
    values. ``rows``/``cols`` may be int16 when both dims fit, shrinking
    the index tables 2x on top.
  * ``ids`` and ``scale`` are scalar-prefetch operands
    (PrefetchScalarGridSpec): the BlockSpec index maps route program b to
    *its adapter's* (rows, cols, vals) chunk — only the selected adapter's
    table is DMA'd into VMEM, not the whole registry. ids[b] < 0 means "no
    adapter": the index map clamps to slot 0 and the kernel skips the work
    and leaves the zeroed output block.

Backends: ``interpret=True`` runs the Pallas interpreter (kernel-body
emulation, any backend). ``interpret=False`` compiles — through Mosaic on
TPU, and elsewhere (jax has no compiled Pallas lowering for the CPU)
through ``_sidedelta_xla``, an XLA formulation of the *same* (bm, kc)
plan: the same local-column masking and the same int8 dequant placement.
That keeps the tiling/masking/dequant logic exercised by a genuinely
compiled executable in CPU CI.

The delta accumulates in f32 regardless of the compute dtype (the caller
adds it onto the base matmul's output), so batched multi-tenant serving
matches the sequential switch-per-batch path to fp32 accuracy.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Default VMEM byte budget for one program's working set. TPU v5e and v6e
# cores have 128 MiB of VMEM; the plan keeps a quarter of it, and the
# compiler is allowed ``_VMEM_HEADROOM`` more for its own scratch.
DEFAULT_VMEM_BUDGET = 32 * 1024 * 1024
_VMEM_HEADROOM = 16 * 1024 * 1024

_LANE = 128          # TPU lane width: last-dim tile granularity
_SUBLANE = 8         # TPU sublane count: second-to-last-dim granularity
_MAX_BM = 1024       # largest scatter m-tile
_MAX_KC = 512        # largest K chunk per grid step
_MAX_BN = 512        # largest gather n-tile


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


# ---------------------------------------------------------------------------
# Autotuned plan cache. ``analysis/autotune.py`` sweeps (bm, kc) candidates
# per (S, n, m, K) shape class against measured step times and installs the
# winners here; ``plan_tiles`` consults the cache before falling back to the
# static VMEM-budget heuristic. Cached plans are validated on lookup (tile
# alignment AND the budget bound) so a stale or hand-edited cache can never
# produce an over-budget or misaligned kernel — it just misses.
# ---------------------------------------------------------------------------

PlanKey = Tuple[int, int, int, int, int, int]     # S, n, m, K, budget, isize

_PLAN_CACHE: Dict[PlanKey, Tuple[int, int]] = {}
plan_cache_stats = {"hits": 0, "misses": 0, "rejected": 0}


def plan_cache_key(S: int, n: int, m: int, K: int,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET,
                   x_itemsize: int = 4) -> PlanKey:
    """One shape class = one cache entry; the budget and input itemsize are
    part of the class (they change the feasible plan set)."""
    return (int(S), int(n), int(m), int(K), int(vmem_budget), int(x_itemsize))


def plan_is_valid(S: int, n: int, m: int, K: int, bm: int, kc: int,
                  *, vmem_budget: int = DEFAULT_VMEM_BUDGET,
                  x_itemsize: int = 4) -> bool:
    """A usable (bm, kc): lane-aligned, positive, and within the budget."""
    if bm < _LANE or kc < _LANE or bm % _LANE or kc % _LANE:
        return False
    return vmem_estimate(S, n, m, K, bm, kc, x_itemsize=x_itemsize,
                         vmem_budget=vmem_budget) <= vmem_budget


def install_plan_cache(plans: Dict[PlanKey, Tuple[int, int]],
                       replace: bool = False) -> int:
    """Merge autotuned plans into the cache; returns entries installed."""
    global _PLAN_CACHE
    if replace:
        _PLAN_CACHE = {}
    for key, (bm, kc) in plans.items():
        _PLAN_CACHE[tuple(int(x) for x in key)] = (int(bm), int(kc))
    return len(_PLAN_CACHE)


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    for k in plan_cache_stats:
        plan_cache_stats[k] = 0


def plan_cache() -> Dict[PlanKey, Tuple[int, int]]:
    return dict(_PLAN_CACHE)


def _tile(dim_pad: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``dim_pad`` and is <= ``cap``."""
    t = min(cap, dim_pad) // _LANE * _LANE
    while dim_pad % t:
        t -= _LANE
    return t


def s_block(S: int, n: int, m: int, *,
            vmem_budget: int = DEFAULT_VMEM_BUDGET,
            x_itemsize: int = 4) -> int:
    """Rows of x per program (the S tile). The double-buffered x and output
    blocks grow with it, so they get at most half the budget: all of S
    when that fits, else equal tiles of a multiple of 8 rows (never fewer
    than 8 — ``plan_tiles`` then reports whether the rest still fits)."""
    n_pad = _round_up(max(n, 1), _LANE)
    m_pad = _round_up(max(m, 1), _LANE)
    per_row = 2 * (n_pad * x_itemsize + m_pad * 4)
    cap = (vmem_budget // 2) // per_row
    if S <= max(cap, _SUBLANE):
        return S
    cap = max(cap // _SUBLANE * _SUBLANE, _SUBLANE)
    tiles = -(-S // cap)
    return _round_up(-(-S // tiles), _SUBLANE)


def vmem_estimate(S: int, n: int, m: int, K: int, bm: int, kc: int,
                  *, x_itemsize: int = 4,
                  vmem_budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """Bytes one grid program keeps live in VMEM under the v3 tile plan.
    K does not enter: the tables stream through in ``kc`` chunks. The
    budget does, through the S tile ``s_block`` picks under it."""
    bs = s_block(S, n, m, vmem_budget=vmem_budget, x_itemsize=x_itemsize)
    n_pad = _round_up(max(n, 1), _LANE)
    bn = _tile(n_pad, _MAX_BN)
    m_pad = _round_up(max(m, 1), bm)
    blocks = 2 * bs * (n_pad * x_itemsize + m_pad * 4)   # x + out, 2 buffers
    # a (1, kc) table block fills a whole sublane tile: 32 bytes per column
    # whatever the dtype (8 x 4, 16 x 2, 32 x 1), three tables, 2 buffers
    tables = 2 * 3 * 32 * kc
    gathered = bs * kc * 4
    onehot_gather = bn * kc * (4 + x_itemsize)     # iota compare + one-hot
    onehot_scatter = bm * kc * 8 + bs * bm * 4     # ... + the partial tile
    return (blocks + tables + gathered
            + max(onehot_gather, onehot_scatter))


def plan_tiles(S: int, n: int, m: int, K: int,
               *, vmem_budget: int = DEFAULT_VMEM_BUDGET,
               x_itemsize: int = 4) -> Tuple[int, int]:
    """Pick (bm, kc) so one program's working set fits ``vmem_budget``.

    bm is the scatter m-tile (multiple of 128 dividing the padded m); kc
    the K chunk one grid step takes. The S tile's blocks are paid
    regardless; the largest K chunk (then the largest m-tile) that fits
    the remainder wins. Raises ``ValueError`` when not even the (128, 128)
    plan fits: a kernel over budget would be refused by the compiler, or
    run with less VMEM than its plan assumed.

    An autotuned plan cache (``install_plan_cache``, populated by
    ``analysis/autotune.py`` from measured step times) is consulted first;
    invalid cached plans are rejected, never trusted."""
    cached = _PLAN_CACHE.get(plan_cache_key(S, n, m, K, vmem_budget,
                                            x_itemsize))
    if cached is not None:
        bm_c, kc_c = cached
        if plan_is_valid(S, n, m, K, bm_c, kc_c, vmem_budget=vmem_budget,
                         x_itemsize=x_itemsize):
            plan_cache_stats["hits"] += 1
            return int(bm_c), int(kc_c)
        plan_cache_stats["rejected"] += 1
    plan_cache_stats["misses"] += 1
    m_pad = _round_up(max(m, 1), _LANE)
    K_pad = _round_up(max(K, 1), _LANE)
    for kc in range(min(K_pad, _MAX_KC), 0, -_LANE):
        bm = _tile(m_pad, _MAX_BM)
        while True:
            if vmem_estimate(S, n, m, K, bm, kc, x_itemsize=x_itemsize,
                             vmem_budget=vmem_budget) <= vmem_budget:
                return int(bm), int(kc)
            if bm == _LANE:
                break
            bm = _tile(m_pad, bm - _LANE)
    floor = vmem_estimate(S, n, m, K, _LANE, _LANE, x_itemsize=x_itemsize,
                          vmem_budget=vmem_budget)
    raise ValueError(
        f"sidedelta: no tile plan fits a {vmem_budget}-byte VMEM budget "
        f"for S={S}, n={n}, m={m} (x itemsize {x_itemsize}); the smallest "
        f"needs {floor} bytes")


# ---------------------------------------------------------------------------
# Pallas kernel body
# ---------------------------------------------------------------------------

def _sidedelta_kernel(ids_ref, scale_ref, x_ref, rows_ref, cols_ref,
                      vals_ref, out_ref, *, bn: int, bm: int, precision):
    b = pl.program_id(0)
    aid = ids_ref[b]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(aid >= 0)
    def _accumulate():
        bs = x_ref.shape[0]
        kc = rows_ref.shape[-1]
        r = rows_ref[...].astype(jnp.int32)                    # (1, kc)
        c = cols_ref[...].astype(jnp.int32)
        v = vals_ref[...].astype(jnp.float32) * scale_ref[aid]

        def gather(i, acc):
            # xs[:, k] += x[:, rows[k]] for the rows inside this n-tile
            n0 = pl.multiple_of(i * bn, bn)
            onehot = (jax.lax.broadcasted_iota(jnp.int32, (bn, kc), 0) + n0
                      == r).astype(x_ref.dtype)
            return acc + jax.lax.dot_general(
                x_ref[:, pl.ds(n0, bn)], onehot, (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)

        xs = jax.lax.fori_loop(0, x_ref.shape[1] // bn, gather,
                               jnp.zeros((bs, kc), jnp.float32)) * v

        def scatter(j, _):
            # nonzeros outside this m-tile get an all-zero one-hot row: the
            # segment-sum matmul masks them for free
            m0 = pl.multiple_of(j * bm, bm)
            onehot = (jax.lax.broadcasted_iota(jnp.int32, (bm, kc), 0) + m0
                      == c).astype(jnp.float32)
            out_ref[:, pl.ds(m0, bm)] += jax.lax.dot_general(
                xs, onehot, (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            return ()

        jax.lax.fori_loop(0, out_ref.shape[1] // bm, scatter, ())


# ---------------------------------------------------------------------------
# Compiled non-TPU dispatch: the same tile plan through XLA
# ---------------------------------------------------------------------------

def _sidedelta_xla(x: jax.Array, rows: jax.Array, cols: jax.Array,
                   vals: jax.Array, scale: jax.Array, ids: jax.Array,
                   m: int, bm: int, kc: int) -> jax.Array:
    """XLA twin of the kernel: per-request gather once, then a sequential
    map over m-tiles, each scatter-accumulating in the same kc-sized K
    chunks with the identical local-column one-hot mask (so the chunk
    bookkeeping — K padding, chunk count — is exercised by compiled CPU
    runs too, not only in interpret mode). Peak memory stays
    O(B*S*K + B*kc*bm) — dW is never materialised."""
    B, S, n = x.shape
    K = rows.shape[-1]                                    # pre-padded to kc
    slot = jnp.maximum(ids, 0)
    r = rows[slot].astype(jnp.int32)                      # (B, K)
    c = cols[slot].astype(jnp.int32)
    v = vals[slot].astype(jnp.float32) * scale[slot][:, None]
    xs = jax.vmap(lambda xb, rb: xb.astype(jnp.float32)[:, rb])(x, r)
    xs = (xs * v[:, None, :]).reshape(B, S, K // kc, kc)
    c = c.reshape(B, K // kc, kc)
    mt = _round_up(m, bm) // bm

    def tile(j):
        def chunk(i):
            local = c[:, i] - j * bm                      # (B, kc)
            onehot = (local[..., None]
                      == jnp.arange(bm)[None, None, :]).astype(jnp.float32)
            return jnp.einsum("bsk,bkc->bsc", xs[:, :, i], onehot)
        return jnp.sum(jax.lax.map(chunk, jnp.arange(K // kc)), axis=0)

    out = jax.lax.map(tile, jnp.arange(mt))               # (mt, B, S, bm)
    out = jnp.moveaxis(out, 0, 2).reshape(B, S, mt * bm)[..., :m]
    return jnp.where((ids >= 0)[:, None, None], out, 0.0)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def sidedelta_rows(x: jax.Array, rows: jax.Array, cols: jax.Array,
                   vals: jax.Array, ids: jax.Array, m: int,
                   *, scale: Optional[jax.Array] = None,
                   interpret=False,
                   bm: Optional[int] = None, kc: Optional[int] = None,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET) -> jax.Array:
    """x: (B, S, n); rows/cols: (A, K) int32 (or int16) per-adapter
    coordinates into (n, m); vals: (A, K) f32 or int8 (zero-padded);
    scale: (A,) f32 per-adapter dequant scale (None = 1, i.e. f32 tables);
    ids: (B,) int32 adapter slot per request, -1 = base model.
    Returns delta (B, S, m) f32.

    ``interpret`` selects the execution mode: ``False`` compiles (Pallas on
    TPU, the XLA twin elsewhere), ``True`` runs the Pallas kernel in
    interpret mode, and ``"xla"`` forces the XLA twin on every backend —
    the twin is pure jnp and therefore differentiable w.r.t. ``vals``,
    which is what the multi-adapter trainer's forward pass relies on.

    ``bm``/``kc`` override the tile plan (defaults from ``plan_tiles``
    under ``vmem_budget``)."""
    B, S, n = x.shape
    A, K = rows.shape
    if scale is None:
        scale = jnp.ones((A,), jnp.float32)
    if K == 0:
        return jnp.zeros((B, S, m), jnp.float32)
    plan_bm, plan_kc = plan_tiles(S, n, m, K, vmem_budget=vmem_budget,
                                  x_itemsize=x.dtype.itemsize)
    bm = bm or plan_bm
    kc = kc or plan_kc
    m_pad = _round_up(m, bm)
    K_pad = _round_up(K, kc)
    if K_pad != K:
        pad = ((0, 0), (0, K_pad - K))
        rows = jnp.pad(rows, pad)       # padded entries: (0, 0) with val 0,
        cols = jnp.pad(cols, pad)       # a harmless +0 in the segment sum
        vals = jnp.pad(vals, pad)
    if interpret == "xla" or (
            not interpret and jax.default_backend() != "tpu"):
        # jax has no compiled Pallas path off-TPU: run the same tile plan
        # through XLA so compiled-mode CI still exercises it
        return _sidedelta_xla(x, rows, cols, vals, scale, ids, m, bm, kc)
    isize = x.dtype.itemsize
    bs = s_block(S, n, m, vmem_budget=vmem_budget, x_itemsize=isize)
    S_pad, n_pad = _round_up(S, bs), _round_up(n, _LANE)
    bn = _tile(n_pad, _MAX_BN)
    if (S_pad, n_pad) != (S, n):
        x = jnp.pad(x, ((0, 0), (0, S_pad - S), (0, n_pad - n)))
    # exact gathers: a bf16 one-hot picks bf16 activations exactly at the
    # default precision; f32 activations (parity runs) need all the passes
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    kernel = functools.partial(_sidedelta_kernel, bn=bn, bm=bm,
                               precision=precision)

    def table_map(b, s, k, ids, scale):
        return (jnp.maximum(ids[b], 0), 0, k)

    def row_map(b, s, k, ids, scale):
        return (b, s, 0)

    table = pl.BlockSpec((None, 1, kc), table_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, S_pad // bs, K_pad // kc),
        in_specs=[pl.BlockSpec((None, bs, n_pad), row_map),
                  table, table, table],
        out_specs=pl.BlockSpec((None, bs, m_pad), row_map),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S_pad, m_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_budget + _VMEM_HEADROOM),
        interpret=interpret,
        name="sidedelta",
    )(ids, scale, x, *(t.reshape(A, 1, K_pad) for t in (rows, cols, vals)))
    return out[:, :S, :m]
