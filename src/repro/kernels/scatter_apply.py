"""scatter_apply — the paper's `torch.Tensor.scatter_` re-thought for TPU.

SHiRA rapid switching adds a pack's 1-2% of entries into the weights in
place (and subtracts them back out on unload). XLA's generic scatter walks
the updates one by one after sorting them, so it costs some 15 ns per entry
on a v5e. Here the entries are bucketed by 128 x 128 weight block and each
block's nonzeros are expanded into a dense tile delta with one-hot matmuls
on the MXU, the same idiom as ``sidedelta``.

Layout (``bucket``, traced inside the caller's jitted program, once per
pack load): a leaf (..., n, m) is seen as B = prod(...) matrices. Each
entry gets its block key (b, r // 128, c // 128) and its place inside the
block, r % 128 * 128 + c % 128; one flat sort by key over the leaf lays
the entries out block after block. The sorted places and values (as
int32 bits) are stored as two planes of (R, 128) rows, and ``bounds``
gives, for each tile of the grid, the entry offsets where each of its
blocks starts (plus where its last one ends): searches over the sorted
keys, no scatter.

Kernel (``scatter_apply``): one grid step per tile of ``q`` blocks along a
block row (128 x 128q), the weight tile aliased input -> output. A step
adds its streams (for a switch, the unloaded pack with a negative scale
and the loaded one with a positive scale; one traced body loops over
them) into an f32 accumulator and writes ``w + acc`` back once:

  * The tile's entries are contiguous in each stream. A window of rows
    holding them is DMA'd from HBM into VMEM, double-buffered across grid
    steps (the next tile's window is in flight while this one computes);
    a tile whose entries overflow its window fetches the rest in further
    windows.
  * Each block walks its rows of 128 entries, masking entries outside
    its [start, end). Per row, a (128, 128) row one-hot and a column
    one-hot times the scaled values give the row's delta as an NT matmul.
    ``matmul_rows`` rows go into one matmul, about the rows a block spans
    (four at 2%: on a v5e that took a 2% pack's unload plus load on a
    2 x 7168 x 19200 leaf from 31.8 ms, a row a matmul, to 17.7; PERF.md).
    The values are split into three bf16 parts (hi + mid + lo carries
    f32's 24 bits) and the one-hots are exact in bf16, so the delta is the
    f32 sum of the block's values, duplicates included.
  * Edge tiles (n or m not a multiple of the tile) are partial blocks of
    the pipeline: rows and columns past the edge are neither read into a
    delta nor written back.

``interpret=True`` runs the same kernel in the Pallas interpreter (off the
TPU); ``core.adapters.apply_pack`` (XLA's scatter) is the plain reference.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK = 128         # rows and columns of one weight block (a lane tile)
_LANE = 128          # entries in one row of a stream
_SUBLANE = 8
_MAX_Q = 8           # most blocks one tile (grid step) holds
_MAX_WINDOW = 256    # most rows of 128 entries one window holds
_PLACE_BITS = 14     # r % 128 * 128 + c % 128 < 2 ** 14
_MAX_ROWS = 4        # most rows of 128 entries one matmul takes


class Layout(NamedTuple):
    """One pack's entries of one leaf, bucketed by weight block."""
    # (2, R, 128) int32: [0] the entry's place in its block,
    # r % 128 * 128 + c % 128; [1] its f32 value's bits (0 in the padding)
    entries: jax.Array
    bounds: jax.Array    # (T, 1, q + 1) int32 entry offset of each block


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(x: int, mult: int) -> int:
    return _cdiv(x, mult) * mult


def grid_of(shape: Sequence[int]) -> Tuple[int, int, int, int, int]:
    """(B, n, m, q, tiles per block row) of a (..., n, m) leaf: q blocks
    per tile, at most ``_MAX_Q`` and no more than the row has."""
    *lead, n, m = shape
    q = min(_MAX_Q, _cdiv(m, _BLOCK))
    return int(np.prod(lead, dtype=np.int64)), n, m, q, _cdiv(m, _BLOCK * q)


def window_rows(shape: Sequence[int], k: int) -> int:
    """Rows of 128 entries one window holds for a leaf whose matrices carry
    k entries each: twice the rows an evenly filled tile needs, plus its
    blocks' ragged ends, as a multiple of 8 and at most the stream."""
    B, n, m, q, nt = grid_of(shape)
    per_tile = k / max(_cdiv(n, _BLOCK) * nt, 1)
    want = _round_up(2 * _cdiv(int(per_tile), _LANE) + q + 1, _SUBLANE)
    return min(want, _MAX_WINDOW, stream_rows(shape, k))


def matmul_rows(shape: Sequence[int], k: int) -> int:
    """Rows of 128 entries one matmul takes: about the rows a block's run
    spans when k entries a matrix spread evenly over its blocks (at 2% of
    a 128 x 128 block, 328 entries over 3-4 rows), at most ``_MAX_ROWS``.
    Fewer rows a matmul waste less on sparse blocks; more rows amortise a
    matmul's fixed cost."""
    B, n, m, q, nt = grid_of(shape)
    per_block = k / (_cdiv(n, _BLOCK) * _cdiv(m, _BLOCK))
    return int(min(_MAX_ROWS, max(1, np.ceil(per_block / _LANE + 0.5))))


def stream_rows(shape: Sequence[int], k: int) -> int:
    """Rows of the (R, 128) stream of a leaf's B * k entries (at least 8)."""
    B = grid_of(shape)[0]
    return max(_round_up(_cdiv(B * k, _LANE), _SUBLANE), _SUBLANE)


def bucket(idx: jax.Array, val: jax.Array, shape: Sequence[int]) -> Layout:
    """Lay out a pack's (..., k) flat indices into a (..., n, m) leaf and
    their values for ``scatter_apply``. One sort by block key; block
    offsets by binary search."""
    B, n, m, q, nt = grid_of(shape)
    if n * m >= 1 << 31:
        raise ValueError(f"scatter_apply: flat indices of a ({n}, {m}) "
                         "matrix overflow int32")
    nbi, nbj = _cdiv(n, _BLOCK), _cdiv(m, _BLOCK)
    k = idx.shape[-1]
    idx = idx.reshape(B, k).astype(jnp.int32)
    r, c = idx // m, idx % m
    # one flat sort over every matrix of the leaf (XLA sorts a 1-D array
    # several times faster than the rows of a 2-D one)
    key = ((jnp.arange(B, dtype=jnp.int32)[:, None] * nbi + r // _BLOCK)
           * nbj + c // _BLOCK).reshape(-1)
    place = ((r % _BLOCK) * _BLOCK + c % _BLOCK).reshape(-1)
    val = val.reshape(-1).astype(jnp.float32)
    if B * nbi * nbj <= 1 << (31 - _PLACE_BITS):
        # block key and place in one int32: a two-operand sort
        packed, val = jax.lax.sort(((key << _PLACE_BITS) | place, val),
                                   num_keys=1)
        key, place = packed >> _PLACE_BITS, packed & ((1 << _PLACE_BITS) - 1)
    else:
        key, place, val = jax.lax.sort((key, place, val), num_keys=1)
    # block j of tile (b, i, t) is block (b, i, min(t * q + j, nbj)); j = q
    # (and any block past the row's end) is where the next row's entries
    # start
    b = np.arange(B)[:, None, None, None]
    i = np.arange(nbi)[None, :, None, None]
    j = np.minimum(np.arange(nt)[None, None, :, None] * q
                   + np.arange(q + 1)[None, None, None, :], nbj)
    queries = jnp.asarray(((b * nbi + i) * nbj + j).reshape(-1), jnp.int32)
    starts = jnp.searchsorted(key, queries).astype(jnp.int32)
    R = stream_rows(shape, k)
    pad = R * _LANE - B * k

    entries = jnp.stack([place, jax.lax.bitcast_convert_type(val, jnp.int32)])
    entries = jnp.pad(entries, ((0, 0), (0, pad))).reshape(2, R, _LANE)
    return Layout(entries, starts.reshape(B * nbi * nt, 1, q + 1))


def rows_walked(layout: Layout) -> jax.Array:
    """Rows of 128 entries the kernel walks for a layout: each non-empty
    block's rows from the one holding its first entry to its last."""
    s, e = layout.bounds[:, 0, :-1], layout.bounds[:, 0, 1:]
    n = (e + _LANE - 1) // _LANE - s // _LANE
    return jnp.sum(jnp.where(e > s, n, 0))


def _split3(v):
    """f32 -> three bf16 parts whose f32 sum is v (to f32 rounding)."""
    hi = v.astype(jnp.bfloat16)
    r1 = v - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _kernel(scale_ref, *refs, streams: int, q: int, win: int,
            rows: Tuple[int, ...], mm_rows: int):
    S = streams
    bnd = refs[:S]
    bnd_next = refs[S:2 * S]
    w_ref = refs[2 * S]
    hbm = refs[2 * S + 1:3 * S + 1]
    out_ref, acc_ref, buf, sem = refs[3 * S + 1:]

    t = pl.program_id(0)
    slot = t % 2

    def pick(s, values):
        """values[s] for a traced stream index s (S is 1 or 2)."""
        out = values[0]
        for i in range(1, S):
            out = jnp.where(s == i, values[i], out)
        return out

    def bound(s, j):
        """Entry offset where block j of stream s's tile starts."""
        return pick(s, [b[0, j] for b in bnd])

    def base_of(s, row):
        """First row of stream s's window holding ``row``."""
        return jnp.minimum(row, pick(s, rows) - win)

    def window(s, base, slot, act):
        """Start or wait (``act``) the DMAs of stream s's window at base
        into ``slot``; s may be traced."""
        for i in range(S):
            @pl.when(s == i)
            def _():
                getattr(pltpu.make_async_copy(
                    hbm[i].at[:, pl.ds(base, win)], buf.at[i, slot],
                    sem.at[i, slot]), act)()

    @pl.when(t == 0)
    def _first():
        for s in range(S):
            window(s, base_of(s, bnd[s][0, 0] // _LANE), 0, "start")

    @pl.when(t + 1 < pl.num_programs(0))
    def _prefetch():
        for s in range(S):
            window(s, base_of(s, bnd_next[s][0, 0] // _LANE), 1 - slot,
                   "start")

    sub = jax.lax.broadcasted_iota(jnp.int32, (_BLOCK, _LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)

    def hots(s, base, r, first, end, limit):
        """One-hots of stream s's rows r .. r + mm_rows - 1 (the window at
        base) for the block whose entries are [first, end): rows at or past
        ``limit`` and entries outside the block add nothing. Their NT
        product is the rows' (128, 128) delta."""
        scale = scale_ref[s]
        lhs, rhs = [], []
        for u in range(mm_rows):
            ru = r + u
            at = pl.ds(jnp.minimum(ru, limit - 1) - base, 1)
            p = buf[s, slot, 0, at, :]                             # (1, 128)
            v = jax.lax.bitcast_convert_type(buf[s, slot, 1, at, :],
                                             jnp.float32)
            k = ru * _LANE + lane
            ok = (ru < limit) & (k >= first) & (k < end)
            v = jnp.where(ok, v * scale, 0.0)
            row_hot = (sub == jnp.where(ok, p // _BLOCK, -1)).astype(
                jnp.bfloat16)
            col = sub == p % _BLOCK
            lhs += [row_hot] * 3
            rhs += [jnp.where(col, x.astype(jnp.float32), 0.0).astype(
                jnp.bfloat16) for x in _split3(v)]
        return jax.lax.dot_general(
            jnp.concatenate(lhs, axis=1), jnp.concatenate(rhs, axis=1),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def walk(s, base, lo, hi):
        """Add stream s's entries in rows [lo, hi) of the window at base,
        block by block, mm_rows rows a matmul."""
        def block(j, carry):
            first, end = bound(s, j), bound(s, j + 1)
            r0 = jnp.maximum(first // _LANE, lo)
            r1 = jnp.minimum((end + _LANE - 1) // _LANE, hi)

            def step(i, acc):
                return acc + hots(s, base, r0 + i * mm_rows, first, end, r1)

            acc = jax.lax.fori_loop(
                0, (r1 - r0 + mm_rows - 1) // mm_rows, step,
                jnp.zeros((_BLOCK, _BLOCK), jnp.float32))
            cols = pl.ds(pl.multiple_of(j * _BLOCK, _BLOCK), _BLOCK)
            acc_ref[:, cols] = acc_ref[:, cols] + acc
            return carry

        jax.lax.fori_loop(0, q, block, 0)

    def stream(s, carry):
        row0 = bound(s, 0) // _LANE
        hi = (bound(s, q) + _LANE - 1) // _LANE
        base = base_of(s, row0)
        window(s, base, slot, "wait")

        def phase(carry):
            base, lo = carry
            walk(s, base, lo, jnp.minimum(hi, base + win))
            lo = base + win

            @pl.when(lo < hi)
            def _overflow():
                # a tile with more entries than a window: fetch the rest
                window(s, base_of(s, lo), slot, "start")
                window(s, base_of(s, lo), slot, "wait")

            return base_of(s, lo), lo

        jax.lax.while_loop(lambda c: c[1] < hi, phase, (base, row0))
        return carry

    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    # one traced body for every stream: half the code of an unrolled pair
    jax.lax.fori_loop(0, S, stream, 0)
    out_ref[...] = (w_ref[...].astype(jnp.float32)
                    + acc_ref[...]).astype(out_ref.dtype)


def scatter_apply(w: jax.Array, layouts: Sequence[Layout],
                  scales: jax.Array, *, interpret: bool = False) -> jax.Array:
    """w (..., n, m) + sum_s scales[s] * scatter(layouts[s]), in place
    (the weight is aliased input -> output: donate it to avoid a copy).
    ``layouts`` are one or two ``bucket`` results for this leaf's shape;
    ``scales`` (S,) f32, e.g. (-alpha_out, +alpha_in) for a switch."""
    S = len(layouts)
    if not S:
        return w
    shape = w.shape
    B, n, m, q, nt = grid_of(shape)
    nbi = _cdiv(n, _BLOCK)
    T = B * nbi * nt
    for lay in layouts:
        if lay.bounds.shape != (T, 1, q + 1):
            raise ValueError(f"scatter_apply: bounds {lay.bounds.shape} do "
                             f"not fit a {shape} leaf ({T}, 1, {q + 1})")
    rows = tuple(lay.entries.shape[1] for lay in layouts)
    ks = [r * _LANE // B for r in rows]
    win = min(window_rows(shape, k) for k in ks)
    mm_rows = max(matmul_rows(shape, k) for k in ks)

    def w_map(t, scale):
        return (t // (nbi * nt), t // nt % nbi, t % nt)

    def cur(t, scale):
        return (t, 0, 0)

    def nxt(t, scale):
        return (jnp.minimum(t + 1, T - 1), 0, 0)

    bspec = [pl.BlockSpec((None, 1, q + 1), f, memory_space=pltpu.SMEM)
             for f in (cur, nxt) for _ in range(S)]
    tile = pl.BlockSpec((None, _BLOCK, _BLOCK * q), w_map)
    hbm = [pl.BlockSpec(memory_space=pl.ANY)] * S
    scratch = [pltpu.VMEM((_BLOCK, _BLOCK * q), jnp.float32),
               pltpu.VMEM((S, 2, 2, win, _LANE), jnp.int32),
               pltpu.SemaphoreType.DMA((S, 2))]
    kernel = functools.partial(_kernel, streams=S, q=q, win=win, rows=rows,
                               mm_rows=mm_rows)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T,),
            in_specs=bspec + [tile] + hbm, out_specs=tile,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, n, m), w.dtype),
        input_output_aliases={1 + 2 * S: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="scatter_apply",
    )(scales.astype(jnp.float32),
      *[lay.bounds for lay in layouts], *[lay.bounds for lay in layouts],
      w.reshape(B, n, m), *[lay.entries for lay in layouts])
    return out.reshape(shape)
