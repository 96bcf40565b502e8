"""sidedelta — tiled per-request sparse side-delta matmul over block-bucketed
adapter tables.

Multi-tenant SHiRA serving keeps ONE shared copy of the base weights and
gives every request in a batch its own adapter. Instead of patching the
weight per request (which would serialize the batch), the forward pass adds
each request's sparse delta as a side term:

  y[b] = x[b] @ W_shared  +  x[b] @ dW_{id[b]},   dW sparse with K nonzeros

The side term never materialises dW: an adapter is a packed table of
(row, col, val) triples, and the kernel computes, for request b with
adapter a = ids[b],

  delta[b, :, cols[a, k]] += x[b, :, rows[a, k]] * vals[a, k]   for all k

Table layout (``sidedelta_table``, built once per table build on the host):

  * The (n, m) weight is cut into 128 x 128 blocks (one lane tile each
    way: the narrowest window the chip slices x and the output by). Each
    adapter's nonzeros are sorted by block, and each block's run is padded
    to whole sub-chunks of ``kc`` entries with val 0 and indices inside
    the block. Every sub-chunk therefore lies in one block.
  * ``blocks`` (A, C) int32 gives each sub-chunk's block origin, packed as
    ``n0 << 16 | m0``; -1 marks the padding after an adapter's last
    sub-chunk (adapters have different counts, the table has one width).
  * ``rows``/``cols`` stay global indices, so any consumer that ignores
    the order (``kernels/ref.py``, the trainer) reads the same tables
    unchanged.

Kernel:

  * Grid = (B, S tiles, C / G). Program (b, s, g) takes the g-th group of
    G sub-chunks of request b's adapter and adds them into the
    (bs, m_pad) output block of S tile s, which stays resident in VMEM
    across the last (accumulating) grid axis, as does the (bs, n_pad)
    x block. The group's slice of ``blocks`` streams into SMEM beside the
    three table slices, so SMEM holds G words whatever C is.
  * Per sub-chunk: read its origin (n0, m0), gather the kc inputs with one
    (128, kc) one-hot matmul over ``x[:, n0:n0+128]``, scale by the
    values, and scatter-accumulate into ``out[:, m0:m0+128]`` with a
    second (128, kc) one-hot. One-hot work per nonzero is 256 elements
    plus the padding, where a layout in nonzero order needs n + m. The
    group's loop is unrolled and has no branch (a -1 sub-chunk runs at
    block (0, 0) on its zero values), so one sub-chunk's matmuls overlap
    the next one's one-hots; on a v5e that took a decode call at
    starcoder2-7b's ``w_up`` from 11.5 to 6.9 ms (PERF.md).
  * ``plan_tiles`` picks kc from the block counts the table build finds
    (padding against the per-sub-chunk cost); ``s_block`` picks the S
    tile from the VMEM budget; G follows from C, kc and the room the
    budget leaves beside the x and output blocks. Blocks stay 128 x 128:
    256 x 256 blocks (kc = 2048) were slower on the chip in decode and in
    prefill.
  * int8 tables in VMEM: ``vals`` may be int8 with a per-adapter f32
    ``scale`` (scalar-prefetch operand); the kernel dequantises after the
    DMA, so adapter HBM at serve time shrinks ~4x vs f32 values.
    ``rows``/``cols`` may be int16 when both dims fit.
  * ``ids`` and ``scale`` are scalar-prefetch operands: the index maps
    route program b to its adapter's slices, so only the selected
    adapter's table is DMA'd. ids[b] < 0 means "no adapter": the maps pin
    one block (nothing is fetched again) and the kernel leaves the zeroed
    output block.

Dispatch follows from the tables: ``sidedelta_rows`` runs this kernel on
tables that carry ``blocks`` (through Mosaic on TPU, the Pallas
interpreter elsewhere), and ``kernels/ref.py``'s plain jnp formulation on
tables that do not. The latter reads tables in any order and is
differentiable w.r.t. ``vals``, which the multi-adapter trainer needs.

The delta accumulates in f32 regardless of the compute dtype (the caller
adds it onto the base matmul's output), so batched multi-tenant serving
matches the sequential switch-per-batch path to fp32 accuracy.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import sidedelta_int8_ref, sidedelta_ref

# Default VMEM byte budget for one program's working set. TPU v5e and v6e
# cores have 128 MiB of VMEM; the plan keeps a quarter of it, and the
# compiler is allowed ``_VMEM_HEADROOM`` more for its own scratch.
DEFAULT_VMEM_BUDGET = 32 * 1024 * 1024
_VMEM_HEADROOM = 16 * 1024 * 1024

_LANE = 128          # TPU lane width: last-dim tile granularity
_SUBLANE = 8         # TPU sublane count: second-to-last-dim granularity
_BLOCK = _LANE       # rows and columns of one table block
_KCS = (128, 256, 384, 512)  # sub-chunk lengths ``plan_tiles`` weighs
_GROUP_ENTRIES = 16384   # most table entries one grid step streams (G * kc)
_MAX_GROUP = 64          # most sub-chunks one grid step unrolls
# Fixed cost of one sub-chunk (its SMEM read, address arithmetic and two
# matmul issues), in one-hot elements: what a sub-chunk costs beyond its
# 2 * 128 * kc one-hot elements. Fitted to decode times of the kernel at
# kc = 128, 256, 384 and 512 on a TPU v5e (PERF.md).
_SUBCHUNK_COST = 17600
# ``plan_tiles`` checks a plan at the largest S tile ``s_block`` can give,
# so that one table serves decode and prefill alike.
_ANY_S = 1 << 20
# A (1, G * kc) table slice fills whole sublane tiles: 32 bytes per entry
# whatever the dtype (8 x 4, 16 x 2, 32 x 1); three tables, 2 buffers.
_TABLE_BYTES_PER_ENTRY = 2 * 3 * 32


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def plan_is_valid(n: int, m: int, K: int, kc: int, *,
                  vmem_budget: int = DEFAULT_VMEM_BUDGET,
                  x_itemsize: int = 4) -> bool:
    """A usable kc: lane-aligned, positive, and within the budget at every
    S tile."""
    if kc < _LANE or kc % _LANE:
        return False
    return vmem_estimate(_ANY_S, n, m, kc, x_itemsize=x_itemsize,
                         vmem_budget=vmem_budget) <= vmem_budget


def _group_cap(n: int, m: int, kc: int, *, vmem_budget: int,
               x_itemsize: int) -> int:
    """Most sub-chunks one grid step takes: ``_GROUP_ENTRIES`` entries and
    ``_MAX_GROUP`` sub-chunks at most, and no more double-buffered table
    slices than the budget has room for beside the largest S tile's x and
    output blocks and one sub-chunk's one-hots (never fewer than one)."""
    room = vmem_budget - _fixed_vmem(_ANY_S, n, m, kc, x_itemsize=x_itemsize,
                                     vmem_budget=vmem_budget)
    return max(1, min(_MAX_GROUP, _GROUP_ENTRIES // kc,
                      room // (_TABLE_BYTES_PER_ENTRY * kc)))


def chunk_group(C: int, n: int, m: int, kc: int, *,
                vmem_budget: int = DEFAULT_VMEM_BUDGET,
                x_itemsize: int = 4) -> int:
    """Sub-chunks per grid step for a table of C sub-chunks of kc: all of
    them when they fit the cap (``_group_cap``), else the cap when it
    divides C, as it does for every table ``sidedelta_table`` lays out
    under the same budget and itemsize, else its largest halving that
    does."""
    g = _group_cap(n, m, kc, vmem_budget=vmem_budget, x_itemsize=x_itemsize)
    if C <= g:
        return C
    while g > 1 and C % g:
        g //= 2
    return g


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


def _fixed_vmem(S: int, n: int, m: int, kc: int, *, x_itemsize: int,
                vmem_budget: int) -> int:
    """VMEM bytes of one grid program beside its table slices: the resident
    x and output blocks of its S tile and one sub-chunk's one-hots."""
    bs = s_block(S, n, m, vmem_budget=vmem_budget, x_itemsize=x_itemsize)
    n_pad = _round_up(max(n, 1), _LANE)
    m_pad = _round_up(max(m, 1), _LANE)
    blocks = 2 * bs * (n_pad * x_itemsize + m_pad * 4)   # x + out, 2 buffers
    onehots = _BLOCK * kc * (4 + x_itemsize) + _BLOCK * kc * 8
    partials = bs * (kc * 4 + _BLOCK * (4 + x_itemsize))
    return blocks + onehots + partials


def vmem_estimate(S: int, n: int, m: int, kc: int, *, x_itemsize: int = 4,
                  vmem_budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """Bytes one grid program keeps live in VMEM: the resident x and output
    blocks of its S tile (``s_block`` under the budget), one group of
    table slices, and one sub-chunk's one-hots. K does not enter: the
    tables stream through in groups, whose size ``_group_cap`` fits to
    what the budget leaves, so a plan needs little more room than its
    x and output blocks and one sub-chunk's slices."""
    group = _group_cap(n, m, kc, vmem_budget=vmem_budget,
                       x_itemsize=x_itemsize)
    return (_fixed_vmem(S, n, m, kc, x_itemsize=x_itemsize,
                        vmem_budget=vmem_budget)
            + _TABLE_BYTES_PER_ENTRY * group * kc)


def _pad_slots(counts: np.ndarray, kc: int) -> int:
    """Table entries the blocks' runs take once padded to whole kc."""
    return int(np.sum(-(-counts // kc))) * kc


def plan_tiles(n: int, m: int, K: int, *, counts=None,
               vmem_budget: int = DEFAULT_VMEM_BUDGET,
               x_itemsize: int = 4) -> int:
    """Pick the sub-chunk length kc for an (n, m) weight whose adapters
    hold up to K nonzeros.

    ``counts`` are the nonzeros per 128 x 128 block that the table build
    found (any shape; every adapter and layer of the weight together).
    Without them, K spread evenly over the blocks stands in. Each kc in
    ``_KCS`` that fits the budget at every S tile costs its padded entries'
    one-hot work (2 * 128 per entry) plus ``_SUBCHUNK_COST`` per
    sub-chunk; the cheapest wins, the shorter on a tie. Raises
    ``ValueError`` when not even kc = 128 fits: a kernel over budget would
    be refused by the compiler, or run with less VMEM than its plan
    assumed."""
    if counts is None:
        nb = -(-max(n, 1) // _BLOCK) * -(-max(m, 1) // _BLOCK)
        per = max(K, 1) / nb
        counts = np.full(min(nb, max(K, 1)), max(per, 1.0))
    counts = np.ceil(np.asarray(counts, np.float64).reshape(-1))
    best, best_cost = None, None
    for kc in _KCS:
        if not plan_is_valid(n, m, K, kc, vmem_budget=vmem_budget,
                             x_itemsize=x_itemsize):
            continue
        slots = _pad_slots(counts, kc)
        cost = slots * 2 * _BLOCK + slots // kc * _SUBCHUNK_COST
        if best_cost is None or cost < best_cost:
            best, best_cost = kc, cost
    if best is None:
        floor = vmem_estimate(_ANY_S, n, m, _LANE, x_itemsize=x_itemsize,
                              vmem_budget=vmem_budget)
        raise ValueError(
            f"sidedelta: no tile plan fits a {vmem_budget}-byte VMEM budget "
            f"for n={n}, m={m} (x itemsize {x_itemsize}); the smallest "
            f"needs {floor} bytes")
    return int(best)


# ---------------------------------------------------------------------------
# Table layout: nonzeros bucketed by block (host, once per table build)
# ---------------------------------------------------------------------------

def _host_threads() -> int:
    return max(1, min(8, os.cpu_count() or 1))


class Buckets(NamedTuple):
    """Block-bucketed tables: R table rows (an adapter of one layer each),
    C sub-chunks of kc entries per row."""
    rows: np.ndarray         # (R, C * kc) global row indices
    cols: np.ndarray         # (R, C * kc) global column indices
    vals: np.ndarray         # (R, C * kc) values, 0 in the padding
    blocks: np.ndarray       # (R, C) int32 origin n0 << 16 | m0, -1 = none
    nnz: int                 # real nonzeros over all rows
    slots: int               # entries in sub-chunks that run (incl. pads)

    @property
    def pad_share(self) -> float:
        """Padded entries over real nonzeros in the sub-chunks that run."""
        return (self.slots - self.nnz) / max(self.nnz, 1)


def sidedelta_table(flat_idx: Sequence[np.ndarray],
                    vals: Sequence[np.ndarray], n: int, m: int, *,
                    kc: Optional[int] = None, idx_dtype=np.int32,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET,
                    x_itemsize: int = 4) -> Buckets:
    """Lay out R tables for the kernel: ``flat_idx[i]`` and ``vals[i]`` are
    the i-th table's nonzeros (an adapter of one layer; flat indices into
    the (n, m) weight, any order and length). The result is sorted by
    128 x 128 block, each block's run padded to whole sub-chunks of kc with
    val 0 and indices at the block's origin. kc comes from ``plan_tiles``
    over the block counts unless given. The row width C * kc is the
    longest table's, rounded up to a multiple of the kernel's group;
    shorter tables end in sub-chunks marked -1 in ``blocks``. Runs once
    per table build, not per batch.

    O(K) per table: block ids by shifts, a stable radix argsort on 16-bit
    keys where the block count allows, ranks within a block by
    ``bincount``/``cumsum``."""
    if n > 1 << 15 or m > 1 << 15:
        raise ValueError(f"sidedelta tables pack block origins in 16 bits: "
                         f"({n}, {m}) is too wide")
    nbm = -(-max(m, 1) // _BLOCK)
    nb = -(-max(n, 1) // _BLOCK) * nbm
    shift = _BLOCK.bit_length() - 1

    def split(i):
        r, c = np.divmod(np.asarray(flat_idx[i]).reshape(-1).astype(np.int32),
                         np.int32(m))
        bid = (r >> shift) * nbm + (c >> shift)
        return r, c, bid, np.bincount(bid, minlength=nb)

    # numpy drops the GIL in the sorts, counts and scatters: the tables
    # are laid out on several host threads at once
    R = len(flat_idx)
    with ThreadPoolExecutor(max_workers=_host_threads()) as pool:
        parts = list(pool.map(split, range(R)))
    K = max((len(p[0]) for p in parts), default=0)
    counts = (np.stack([p[3] for p in parts]) if R
              else np.zeros((0, nb), np.int64))
    if kc is None:
        kc = plan_tiles(n, m, K, counts=counts[counts > 0],
                        vmem_budget=vmem_budget, x_itemsize=x_itemsize)
    chunks = -(-counts // kc)                                   # (R, nb)
    C = max(int(chunks.sum(axis=1).max(initial=0)), 1)
    group = _group_cap(n, m, kc, vmem_budget=vmem_budget,
                       x_itemsize=x_itemsize)
    if C > group:
        C = _round_up(C, group)
    vdt = np.asarray(vals[0]).dtype if R else np.float32
    out_r = np.zeros((R, C * kc), idx_dtype)
    out_c = np.zeros((R, C * kc), idx_dtype)
    out_v = np.zeros((R, C * kc), vdt)
    out_b = np.full((R, C), -1, np.int32)
    origin = ((np.arange(nb, dtype=np.int32) // nbm * _BLOCK) << 16
              | (np.arange(nb, dtype=np.int32) % nbm * _BLOCK))
    key_dt = np.uint16 if nb <= 1 << 16 else np.int32

    def fill(i):
        (r, c, bid, cnt), ch = parts[i], chunks[i]
        used = int(ch.sum())
        if not used:
            return
        blk_origin = np.repeat(origin, ch)                      # (used,)
        out_b[i, :used] = blk_origin
        out_r[i, :used * kc] = np.repeat(blk_origin >> 16, kc)
        out_c[i, :used * kc] = np.repeat(blk_origin & 0xFFFF, kc)
        order = np.argsort(bid.astype(key_dt), kind="stable")
        # entry j of block b (in sorted order) goes to the block's first
        # sub-chunk * kc + its rank in the block
        first = (np.cumsum(ch) - ch) * kc - (np.cumsum(cnt) - cnt)
        dest = np.empty(len(bid), np.int64)
        dest[order] = first[bid[order]] + np.arange(len(bid))
        out_r[i, dest] = r
        out_c[i, dest] = c
        out_v[i, dest] = np.asarray(vals[i]).reshape(-1)

    with ThreadPoolExecutor(max_workers=_host_threads()) as pool:
        list(pool.map(fill, range(R)))
    nnz = sum(len(p[0]) for p in parts)
    return Buckets(out_r, out_c, out_v, out_b, nnz, int(chunks.sum()) * kc)


# ---------------------------------------------------------------------------
# Pallas kernel body
# ---------------------------------------------------------------------------

def _sidedelta_kernel(ids_ref, scale_ref, x_ref, rows_ref, cols_ref,
                      vals_ref, blocks_ref, out_ref, *, kc: int, precision):
    b = pl.program_id(0)
    aid = ids_ref[b]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(aid >= 0)
    def _accumulate():
        scale = scale_ref[aid]

        def sub_chunk(i, carry):
            # a -1 (padding) sub-chunk holds val 0: it runs at block (0, 0)
            # and adds zeros, which keeps the unrolled group free of
            # branches
            origin = jnp.maximum(blocks_ref[0, i], 0)
            n0 = pl.multiple_of(origin >> 16, _BLOCK)
            m0 = pl.multiple_of(origin & 0xFFFF, _BLOCK)
            k0 = pl.multiple_of(i * kc, _LANE)
            r = rows_ref[:, pl.ds(k0, kc)].astype(jnp.int32)       # (1, kc)
            c = cols_ref[:, pl.ds(k0, kc)].astype(jnp.int32)
            v = vals_ref[:, pl.ds(k0, kc)].astype(jnp.float32) * scale
            # xs[:, k] = x[:, rows[k]]: every row lies in [n0, n0 + 128)
            gather = (jax.lax.broadcasted_iota(jnp.int32, (_BLOCK, kc), 0)
                      + n0 == r).astype(x_ref.dtype)
            xs = jax.lax.dot_general(
                x_ref[:, pl.ds(n0, _BLOCK)], gather,
                (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * v
            # out[:, cols[k]] += xs[:, k]: every col lies in [m0, m0 + 128)
            scatter = (jax.lax.broadcasted_iota(jnp.int32, (_BLOCK, kc), 0)
                       + m0 == c).astype(jnp.float32)
            out_ref[:, pl.ds(m0, _BLOCK)] += jax.lax.dot_general(
                xs, scatter, (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            return carry

        # unrolled: the scheduler overlaps one sub-chunk's matmuls with the
        # next one's one-hots
        jax.lax.fori_loop(0, blocks_ref.shape[-1], sub_chunk, 0, unroll=True)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def sidedelta_rows(x: jax.Array, rows: jax.Array, cols: jax.Array,
                   vals: jax.Array, ids: jax.Array, m: int,
                   *, blocks: Optional[jax.Array] = None,
                   scale: Optional[jax.Array] = None,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET) -> jax.Array:
    """x: (B, S, n); rows/cols: (A, K) int32 (or int16) per-adapter
    coordinates into (n, m); vals: (A, K) f32 or int8;
    blocks: (A, C) int32 sub-chunk origins from ``sidedelta_table`` (the
    tables then hold C sub-chunks of K // C entries);
    scale: (A,) f32 per-adapter dequant scale (None = 1, i.e. f32 tables);
    ids: (B,) int32 adapter slot per request, -1 = base model.
    Returns delta (B, S, m) f32.

    Tables with ``blocks`` run the Pallas kernel: compiled through Mosaic
    on TPU, in the Pallas interpreter elsewhere. Tables without them (the
    trainer's trainable tables) run ``ref.sidedelta_ref``, plain jnp that
    reads tables in any order and is differentiable w.r.t. ``vals``."""
    if blocks is None:
        if scale is None:
            return sidedelta_ref(x, rows, cols, vals, ids, m)
        return sidedelta_int8_ref(x, rows, cols, vals, scale, ids, m)
    B, S, n = x.shape
    A, K = rows.shape
    if scale is None:
        scale = jnp.ones((A,), jnp.float32)
    if K == 0:
        return jnp.zeros((B, S, m), jnp.float32)
    C = blocks.shape[-1]
    kc = K // C
    if kc * C != K or kc % _LANE:
        raise ValueError(f"sidedelta: {K} table entries are not {C} "
                         "sub-chunks of a multiple of 128")
    isize = x.dtype.itemsize
    group = chunk_group(C, n, m, kc, vmem_budget=vmem_budget,
                        x_itemsize=isize)
    bs = s_block(S, n, m, vmem_budget=vmem_budget, x_itemsize=isize)
    S_pad, n_pad = _round_up(S, bs), _round_up(n, _BLOCK)
    m_pad = _round_up(m, _BLOCK)
    if (S_pad, n_pad) != (S, n):
        x = jnp.pad(x, ((0, 0), (0, S_pad - S), (0, n_pad - n)))
    # exact gathers: a bf16 one-hot picks bf16 activations exactly at the
    # default precision; f32 activations (parity runs) need all the passes
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    kernel = functools.partial(_sidedelta_kernel, kc=kc, precision=precision)

    def group_of(b, k, ids):
        # a base row pins one block, so nothing is fetched again
        return jnp.maximum(ids[b], 0), jnp.where(ids[b] >= 0, k, 0)

    def table_map(b, s, k, ids, scale):
        a, g = group_of(b, k, ids)
        return (a, 0, g)

    def blocks_map(b, s, k, ids, scale):
        return group_of(b, k, ids) + (0, 0)

    def row_map(b, s, k, ids, scale):
        return (b, s, 0)

    table = pl.BlockSpec((None, 1, group * kc), table_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, S_pad // bs, C // group),
        in_specs=[pl.BlockSpec((None, bs, n_pad), row_map),
                  table, table, table,
                  pl.BlockSpec((None, None, 1, group), blocks_map,
                               memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((None, bs, m_pad), row_map),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S_pad, m_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_budget + _VMEM_HEADROOM),
        interpret=jax.default_backend() != "tpu",
        name="sidedelta",
    )(ids, scale, x, *(t.reshape(A, 1, K) for t in (rows, cols, vals)),
      blocks.reshape(A, C // group, 1, group))
    return out[:, :S, :m]
