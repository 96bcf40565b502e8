"""Multi-tenant SHiRA serving: per-request adapters in ONE batch.

The sequential serving path (``launch/serve.py`` + ``SwitchEngine``) swaps
adapters *between* batches: two users wanting different adapters can never
share a decode step. Sparse adapters make the per-request fix cheap — each
request's delta is 1-2% of the weights — so this engine keeps ONE shared
copy of the base weights and applies every request's SHiRA pack as a
batched sparse side term in the forward pass:

  y[b] = x[b] @ W_shared  +  x[b] @ dW_{adapter(b)}

The side term is computed by the Pallas ``sidedelta`` kernel
(repro/kernels/sidedelta.py) from packed per-adapter (row, col, val)
tables, bucketed by 128 x 128 weight block at table build so that each
of the kernel's sub-chunks touches one block; the weight leaves of the
served parameter tree are replaced by ``layers.sidedelta_weight`` bundles,
which ``layers.pdot`` understands and which survive the LM's ``lax.scan``
over stacked layer weights (every table carries the weight's leading layer
dims).

Fused-state scheduling: with a ``core.switching.FusedLRU`` scheduler, the
engine additionally fuses the *hot* tenant into the shared base (a single
sparse scatter — the paper's rapid switch), so dominant-tenant requests skip
the side term entirely. The other tenants are then served with diff packs
(their delta minus the fused one, built by ``fusion.fuse_packs``), and base
-model requests with the negated fused pack. Demotion scatters the delta
back out and restores plain packs.

Device tables may be quantized (``table_dtype="int8"``): values stay int8
with a per-adapter scale (dequantized inside the kernel's VMEM) and the
index halves drop to int16 where the weight dims fit, so resident adapter
HBM shrinks ~4x (values) / ~2.4x (total). Packs registered from an int8
``AdapterStore`` reach the tables in their original quantization — no f32
round trip, no second rounding.

Tenants need not be single adapters: a request may name an adapter *stack*
(tuple of names) whose deltas are merged into one side pack, and a
``FusedLRU(capacity>1)`` promotes a hot stack into the base as a group —
diff packs are then group-aware (each tenant's delta minus the fused sum).
Request-level serving with continuous batching lives one layer up, in
``repro.hub.ServingEngine``, which drives this engine's prefill/decode with
per-slot adapter ids and cache positions.

Limitations: adapters on ``w_uk``/``w_uv`` (MLA absorbed-decode weights,
consumed via reshape rather than a matmul) and on routed experts
(``experts_w_*``, consumed by the grouped expert matmul) are rejected —
exclude them from ``AdapterConfig.target_modules`` when serving MLA or MoE
archs multi-tenant, or serve such adapters by switching.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import trace
from repro.core.adapters import AdapterPack, apply_pack
from repro.core.fusion import fuse_packs
from repro.core.switching import (FusedLRU, SwitchEngine, Tenant,
                                  normalize_tenant, tenant_key,
                                  tenant_members)
from repro.kernels.ops import sidedelta_table
from repro.models import lm
from repro.models.layers import sidedelta_weight
from repro.runtime import faults

BASE = None            # the "no adapter" tenant in a names list
_BASE_SLOT = "__base__"

# MLA absorbed-decode weights are reshaped, not matmul'd, and routed experts
# run in the grouped expert matmul — pdot never sees them, so a side-delta
# bundle there would crash or silently serve the base weights.
UNSUPPORTED_LEAVES = {
    "w_uk": "consumed outside pdot (MLA absorbed decode; ROADMAP R3)",
    "w_uv": "consumed outside pdot (MLA absorbed decode; ROADMAP R3)",
    "experts_w_up": "a routed expert, served by the grouped expert matmul, "
                    "which has no side-delta path (ROADMAP R2)",
    "experts_w_gate": "a routed expert, served by the grouped expert matmul, "
                      "which has no side-delta path (ROADMAP R2)",
    "experts_w_down": "a routed expert, served by the grouped expert matmul, "
                      "which has no side-delta path (ROADMAP R2)",
}


def _leaf_shapes(params) -> Dict[str, Tuple[int, ...]]:
    out = {}
    from repro.core import masks as M
    for p, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        out[M.path_str(p)] = tuple(x.shape)
    return out


def greedy_decode(cfg, batch, tokens: int, prefill, decode):
    """The serving decode loop, shared by the engine, the sequential
    references, and the benchmark so position bookkeeping (incl. the vision
    prefix) cannot drift between them.

    prefill(batch) -> (logits, caches); decode(tok, caches, pos) ->
    (logits, caches). Returns (greedy tokens (B, tokens) int32, last-step
    logits (B, V)).
    """
    prompt_len = batch["tokens"].shape[1]
    pos0 = prompt_len + (cfg.num_prefix_embeds
                         if cfg.modality == "vision" else 0)
    logits, caches = prefill(batch)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    outs = [nxt]
    for i in range(tokens - 1):
        logits, caches = decode(nxt, caches, pos0 + i)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        outs.append(nxt)
    jax.block_until_ready(logits)
    return jnp.concatenate(outs, axis=1), logits


def serving_cache_size(cfg, prompt_len: int, tokens: int) -> int:
    """KV-cache slots for a serve call: prompt + generated + slack, PLUS the
    vision prefix (prefix embeddings occupy cache positions too)."""
    prefix = cfg.num_prefix_embeds if cfg.modality == "vision" else 0
    return prompt_len + prefix + tokens + 8


def switch_per_request_reference(cfg, params, packs, toks, names,
                                 tokens: int):
    """Ground-truth baseline: serve each request ALONE after rapid-switching
    (SwitchEngine) to its adapter. The multi-tenant engine's batched outputs
    are validated against this in tests and examples (the benchmark uses the
    stronger switch-per-GROUP baseline instead).

    toks: (B, S) int; names: per-request adapter name or None. Returns
    (greedy tokens (B, tokens) int32, last-step logits (B, V) f32, seconds).
    """
    toks = np.asarray(toks)
    B, S = toks.shape
    cs = serving_cache_size(cfg, S, tokens)
    by_name = {p.name: p for p in packs}
    engine = SwitchEngine(params)
    prefill = jax.jit(lambda p, b: lm.prefill(p, cfg, b, cs))
    decode = jax.jit(lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos))
    out = np.zeros((B, tokens), np.int32)
    logits_last = np.zeros((B, cfg.padded_vocab), np.float32)
    t0 = time.perf_counter()
    for b, name in enumerate(names):
        while engine.active:
            engine.unload()
        if name is not None:
            engine.load(by_name[name])
        seq, logits = greedy_decode(
            cfg, {"tokens": jnp.asarray(toks[b:b + 1])}, tokens,
            lambda bb: prefill(engine.params, bb),
            lambda t, c, pos: decode(engine.params, t, c, pos))
        out[b] = np.asarray(seq)[0]
        logits_last[b] = np.asarray(logits, np.float32)[0]
    dt = time.perf_counter() - t0
    while engine.active:
        engine.unload()
    return out, logits_last, dt


class MultiTenantEngine:
    """Serves mixed-adapter batches off one shared base parameter tree.

    A request's tenant may be ``None`` (base model), one adapter name, or an
    adapter *stack* — a tuple of names whose deltas are applied together
    (the side pack is their merged sum). With a ``FusedLRU(capacity>1)``
    scheduler a hot stack is fused into the shared base as a group, and
    every other tenant is served with a group-aware diff pack (its delta
    minus the fused sum). With an ``AdapterStore``, ``register`` also
    accepts a registered adapter id instead of a pack object."""

    def __init__(self, cfg, params, *, scheduler: Optional[FusedLRU] = None,
                 store=None, table_dtype: str = "f32", slot_pad: int = 1):
        if table_dtype not in ("f32", "int8"):
            raise ValueError(f"table_dtype must be 'f32' or 'int8', got "
                             f"{table_dtype!r}")
        if slot_pad < 1:
            raise ValueError(f"slot_pad must be >= 1, got {slot_pad}")
        self.cfg = cfg
        self.shared = params                 # base (+ the fused packs, if any)
        self.packs: Dict[str, AdapterPack] = {}
        self.scheduler = scheduler
        self.store = store
        self.table_dtype = table_dtype       # device-table value dtype
        # slot-capacity bucket: the tables' adapter axis is rounded up to a
        # multiple of this, so registering an adapter within the padded
        # capacity keeps every table shape constant — no prefill/decode
        # recompile on cold admission (padded slots hold zero-valued rows
        # that contribute nothing). 1 = exact sizing (the old behavior).
        self.slot_pad = slot_pad
        self.fused: Optional[Tenant] = None
        self.fuse_transitions = 0            # promote/demote scatter count
        self._shapes = _leaf_shapes(params)
        self._tables: Dict[str, dict] = {}   # path -> rows/cols/vals[/scale]
        self._qpacks: Dict[str, Any] = {}    # name -> QuantPack (int8 tables)
        self._qtables: Dict[str, dict] = {}  # name -> decoded int8_tables()
        self._slots: Dict[Any, int] = {}     # tenant -> table slot
        self._stacks: Dict[Any, int] = {}    # multi-adapter tenant -> last use
        self._batch_no = 0                   # ids_for calls (stack recency)
        self.stack_ttl = 64                  # drop stacks idle this many calls
        self._dirty = False
        self._structural = False             # old table rows invalid too
        self._epoch = 0                      # bumps on table-invalidating change
        self._build_pool: Optional[ThreadPoolExecutor] = None
        self._build_fut = None               # (epoch, Future, decision|None)
        self._pending = None                 # deferred FusedDecision
        self.async_builds = 0                # background builds submitted
        self.async_adopted = 0               # adopted (saved a sync rebuild)
        self.async_stale = 0                 # discarded (state moved on)

        def _prefill(p, b, cs):
            return lm.prefill(p, self.cfg, b, cs)

        def _decode(p, t, c, pos):
            return lm.decode_step(p, self.cfg, t, c, pos)

        self._prefill = jax.jit(_prefill, static_argnums=2)
        self._decode = jax.jit(_decode)

    # ------------------------------------------------------------------
    # Registration / side-delta tables
    # ------------------------------------------------------------------

    def register(self, pack) -> None:
        from repro.hub.packio import QuantPack  # deferred: hub imports us
        qp = None
        if isinstance(pack, str):
            if self.store is None:
                raise ValueError(f"adapter named by id {pack!r} but no "
                                 "AdapterStore attached")
            if self.table_dtype == "int8" and hasattr(self.store, "get_raw"):
                # int8 tables can be built straight from the store's
                # quantized resident form — no f32 round trip, no second
                # quantization error
                pack = self.store.get_raw(pack)
            else:
                pack = self.store.get(pack)
        if isinstance(pack, QuantPack):
            qp, pack = pack, pack.dequantize()
        for path in pack.entries:
            leaf = path.rsplit("/", 1)[-1]
            if leaf in UNSUPPORTED_LEAVES:
                raise ValueError(
                    f"adapter {pack.name!r} targets {path!r}: {leaf} is "
                    f"{UNSUPPORTED_LEAVES[leaf]}; exclude it from "
                    "target_modules for multi-tenant serving")
            if path not in self._shapes:
                raise KeyError(f"adapter {pack.name!r} targets unknown "
                               f"weight {path!r}")
        brand_new = pack.name not in self.packs
        if pack.name in tenant_members(self.fused):
            # un-fuse the OLD delta before replacing the pack, or the next
            # demote would subtract the new one from a base holding the old
            self._demote()
            if self.scheduler is not None and pack.name in tenant_members(
                    self.scheduler.fused):
                self.scheduler.fused = None  # keep it re-promotable
        self.packs[pack.name] = pack
        self._qpacks.pop(pack.name, None)
        self._qtables.pop(pack.name, None)
        if qp is not None:
            self._qpacks[pack.name] = qp
        self._mark_dirty(additive=brand_new)

    def unregister(self, name: str) -> bool:
        """Drop a registered adapter (hot-swap retirement: a superseded
        ``name@v`` whose in-flight requests have drained). If the adapter
        is fused — alone or inside the fused stack — it is demoted first
        so the shared base returns to clean weights. Removal dirt is
        *additive*: like stack-TTL retirement, the remaining tenants'
        table rows stay valid until the rebuild, so serving may keep
        decoding them off the old tables. Returns False if unknown."""
        if name not in self.packs:
            return False
        if name in tenant_members(self.fused):
            self._demote()
            if (self.scheduler is not None
                    and name in tenant_members(self.scheduler.fused)):
                self.scheduler.fused = None
        del self.packs[name]
        self._qpacks.pop(name, None)
        self._qtables.pop(name, None)
        for t in [t for t in self._stacks if name in tenant_members(t)]:
            del self._stacks[t]
        if self.scheduler is not None:
            for t in [t for t in self.scheduler.share
                      if name in tenant_members(t)]:
                self.scheduler.share.pop(t, None)
                self.scheduler.last_used.pop(t, None)
        self._mark_dirty(additive=True)
        return True

    def resolve(self, name):
        """Map a tenant's members through the attached store's versioned-id
        resolution (bare ``name`` -> newest ``name@v``). Identity without a
        store. Request-level engines (``repro.hub``) call this at submit so
        a request is pinned to the version that was newest when it arrived,
        even if a newer one is published mid-stream."""
        if self.store is None or not hasattr(self.store, "resolve"):
            return name
        members = tenant_members(name)
        if not members:
            return name
        resolved = tuple(self.store.resolve(m) for m in members)
        if resolved == members:
            return name
        return resolved[0] if isinstance(name, str) else resolved

    def _tenants(self) -> set:
        """Side-served tenants: every registered adapter singly, plus every
        multi-adapter stack a request has named."""
        return set(self.packs) | set(self._stacks)

    def _mark_dirty(self, additive: bool = False) -> None:
        """Tables no longer match the tenant/fused state. The epoch bump
        invalidates any background build snapshotted before this point.

        ``additive`` dirt only *adds* tenants (a new pack registered, a new
        stack named): every existing table row is still correct, so serving
        may keep using the stale tables for already-covered tenants
        (``ids_covered``) while the rebuild runs in the background.
        Structural dirt (re-register, promote/demote, anything touching the
        fused state) invalidates existing rows as well."""
        self._dirty = True
        self._epoch += 1
        if not additive:
            self._structural = True

    def _side_packs(self, packs, stacks, fused) -> Dict[Any, AdapterPack]:
        """What each tenant's side delta must be, given the fused state.
        Operates on explicit (possibly snapshotted) state so background
        builds never read dicts the serving thread is mutating."""
        fused_m = tenant_members(fused)
        out = {}
        for t in set(packs) | set(stacks):
            if t == fused:
                continue                     # fused tenant rides the base
            members = tenant_members(t)
            if not fused_m and len(members) == 1:
                out[t] = packs[members[0]]
            else:
                parts = ([packs[m] for m in members]
                         + [packs[f] for f in fused_m])
                weights = [1.0] * len(members) + [-1.0] * len(fused_m)
                out[t] = fuse_packs(
                    parts, weights=weights,
                    name=(tenant_key(t) +
                          (f"-minus-{tenant_key(fused)}" if fused_m
                           else "")))
        if fused_m:                          # base traffic must un-see it
            out[_BASE_SLOT] = fuse_packs(
                [packs[f] for f in fused_m],
                weights=[-1.0] * len(fused_m),
                name=f"-{tenant_key(fused)}")
        return out

    def _quant_direct(self, name, pk, path, packs, qpacks):
        """The store's quantized values for this side pack, when they can be
        used verbatim: a plain single-adapter tenant (no diff/merge math)
        registered from a QuantPack. Returns (idx (nl, k) int64,
        vq (nl, k) int8, scale float) or None."""
        if self.table_dtype != "int8" or not isinstance(name, str):
            return None
        if pk is not packs.get(name) or name not in qpacks:
            return None                      # diff/merged pack: f32 math
        qp = qpacks[name]
        if path not in qp.entries:
            return None
        if name not in self._qtables:    # decode the gap streams once
            self._qtables[name] = qp.int8_tables()
        idx, vq, scale = self._qtables[name][path]
        return idx, vq, scale * qp.alpha

    def _rebuild(self) -> None:
        """Synchronous (serving-thread) table rebuild — the fallback when no
        background build matches the current state."""
        with trace.span("table_rebuild", cat="tables") as sp:
            side = self._side_packs(self.packs, self._stacks, self.fused)
            slots, tables, meta = self._build_tables(side, self.packs,
                                                     self._qpacks)
            sp.set(**meta)
        self._slots, self._tables = slots, tables
        self._dirty = False
        self._structural = False

    def _build_tables(self, side, packs, qpacks):
        """Pack side deltas into device tables, laid out block by block
        (``ops.sidedelta_table``). Pure w.r.t. engine state (reads only
        the passed snapshots + immutable ``_shapes``), so the sync rebuild
        and the async build produce identical tables from identical
        inputs. Returns (slots, tables, meta); ``meta["pad_share"]`` is
        the layout's padded entries over the real nonzeros."""
        from repro.kernels.ops import quantize_table
        # injected device-OOM point: covers the sync rebuild AND the async
        # build worker (poll_async_build contains worker failures; the hub
        # engines back off and retry on TableBuildError from sync builds)
        faults.on_table_build()
        order = sorted(side, key=lambda t: t if isinstance(t, str)
                       else tenant_key(t))
        slots = {name: i for i, name in enumerate(order)}
        paths = sorted({p for pk in side.values() for p in pk.entries})
        tables: Dict[str, dict] = {}
        pad = self.slot_pad
        A = max(-(-max(len(side), 1) // pad) * pad, 1)
        int8 = self.table_dtype == "int8"
        vdt = np.int8 if int8 else np.float32
        nnz = slots_run = 0
        for path in paths:
            shape = self._shapes[path]
            *lead, n, m = shape
            nl = int(np.prod(lead)) if lead else 1
            # int8 tables also shrink the index halves when the dims fit
            # int16 (the kernel widens them to int32 inside VMEM)
            idx_dt = (np.int16 if int8 and n < 2 ** 15 and m < 2 ** 15
                      else np.int32)
            # one table per (layer, slot), layer-major like the device arrays
            flat = [np.zeros(0, np.int64)] * (nl * A)
            vals = [np.zeros(0, vdt)] * (nl * A)
            scale = np.ones((nl, A), np.float32)
            for name, pk in side.items():
                if path not in pk.entries:
                    continue
                s = slots[name]
                direct = self._quant_direct(name, pk, path, packs, qpacks)
                if direct is not None:       # store int8 -> table int8, 1:1
                    idxf, vq, sc = direct
                    idxf = np.asarray(idxf).reshape(nl, -1)
                    vq = np.asarray(vq).reshape(nl, -1)
                    for i in range(nl):
                        flat[i * A + s], vals[i * A + s] = idxf[i], vq[i]
                    scale[:, s] = sc
                    continue
                idx, val = pk.entries[path]
                idxf = np.asarray(idx).reshape(nl, -1)
                valf = np.asarray(val, np.float32).reshape(nl, -1) * pk.alpha
                for i in range(nl):
                    flat[i * A + s] = idxf[i]
                    if int8:
                        vals[i * A + s], scale[i, s] = quantize_table(valf[i])
                    else:
                        vals[i * A + s] = valf[i]
            t = sidedelta_table(flat, vals, n, m, idx_dtype=idx_dt)
            nnz, slots_run = nnz + t.nnz, slots_run + t.slots
            lead = tuple(lead)
            entry = {k: jnp.asarray(a.reshape(lead + (A, a.shape[-1])))
                     for k, a in (("rows", t.rows), ("cols", t.cols),
                                  ("vals", t.vals), ("blocks", t.blocks))}
            if int8:
                entry["scale"] = jnp.asarray(scale.reshape(lead + (A,)))
            tables[path] = entry
        # padded table entries in the sub-chunks the kernel runs, over the
        # real nonzeros: how well the block layout fits these masks
        meta = {"tenants": len(side), "paths": len(tables),
                "bytes": sum(int(x.nbytes) for t in tables.values()
                             for x in t.values()),
                "pad_share": round((slots_run - nnz) / max(nnz, 1), 4)}
        return slots, tables, meta

    # ------------------------------------------------------------------
    # Async table builds (overlap rebuild + H2D with in-flight decode)
    # ------------------------------------------------------------------

    def tables_ready(self) -> bool:
        """True when serving can proceed without a synchronous rebuild —
        tables are clean, or a completed background build was adopted."""
        if self._dirty:
            self.poll_async_build()
        return not self._dirty

    def kick_async_build(self) -> bool:
        """Start rebuilding the device tables on a background worker so the
        table pack + H2D upload overlap whatever the serving thread does
        next (the in-flight decode step). Snapshot semantics: the build
        captures the tenant/fused state at submit; any later
        ``_mark_dirty`` makes it stale and it is discarded at poll time.
        With a deferred fused transition pending, the build targets the
        *post-transition* state. Returns True when tables are clean or a
        matching build is in flight; False means a stale build is still
        running (back off and kick again next step)."""
        if not self._dirty and self._pending is None:
            return True
        if self._build_fut is not None:
            ep, fut, trans = self._build_fut
            if not fut.done():
                return ep == self._epoch and trans is self._pending
            self.poll_async_build()
            if not self._dirty and self._pending is None:
                return True
        epoch = self._epoch
        pending = self._pending
        packs, qpacks = dict(self.packs), dict(self._qpacks)
        stacks, fused = dict(self._stacks), self.fused
        if pending is not None:
            fused = (normalize_tenant(pending.promote)
                     if pending.promote is not None else None)
        if self._build_pool is None:
            self._build_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="shira-tables")

        def job():
            with trace.span("prefetch.h2d", cat="tables") as sp:
                side = self._side_packs(packs, stacks, fused)
                slots, tables, meta = self._build_tables(side, packs, qpacks)
                # land the uploads on the worker: the serving thread must
                # never pay this build's device sync
                jax.block_until_ready([x for t in tables.values()
                                       for x in t.values()])
                sp.set(**meta)
            return slots, tables

        self._build_fut = (epoch, self._build_pool.submit(job), pending)
        self.async_builds += 1
        return True

    def poll_async_build(self) -> bool:
        """Adopt a completed background build if it still matches the
        engine state; discard it otherwise. A transition build also
        dispatches its deferred fuse/unfuse scatter at adoption. Never
        blocks. Returns True when tables are clean after the poll."""
        if self._build_fut is None:
            return not self._dirty
        ep, fut, trans = self._build_fut
        if not fut.done():
            return not self._dirty
        self._build_fut = None
        try:
            slots, tables = fut.result()
        except Exception:
            trace.instant("prefetch.h2d_failed", cat="tables")
            return not self._dirty
        if ep != self._epoch or trans is not self._pending:
            self.async_stale += 1
        elif trans is not None:
            # apply the deferred transition: the scatter is async-dispatched
            # (device-ordered before anything that reads the new shared
            # tree), the matching tables swap in the same host step
            if trans.promote is not None:
                self._promote(trans.promote)
            elif trans.demote is not None:
                self._demote()
            self._pending = None
            self._slots, self._tables = slots, tables
            self._dirty = False
            self._structural = False
            self.async_adopted += 1
        elif self._dirty:
            self._slots, self._tables = slots, tables
            self._dirty = False
            self._structural = False
            self.async_adopted += 1
        else:
            self.async_stale += 1
        return not self._dirty

    def _ensure_tables(self) -> None:
        """Make the tables serve-ready: adopt a finished background build,
        wait for a matching in-flight one (``prefetch.stall`` — the time
        async serving failed to hide), or fall back to the synchronous
        rebuild. Token output is identical on every path: same builder,
        same inputs."""
        if not self._dirty:
            return
        if not self.poll_async_build():
            if (self._build_fut is not None
                    and self._build_fut[0] == self._epoch):
                with trace.span("prefetch.stall", cat="tables"):
                    try:
                        self._build_fut[1].result()
                    except Exception:
                        pass
                self.poll_async_build()
        if self._dirty:
            self._rebuild()

    def shutdown(self) -> None:
        """Join the background build worker (tests / clean teardown)."""
        pool, self._build_pool = self._build_pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    def table_nbytes(self) -> Dict[str, int]:
        """Device-side adapter-table bytes by component (what multi-tenant
        serving keeps resident in HBM). int8 tables shrink ``vals`` 4x and,
        when the dims fit int16, ``rows``/``cols`` 2x."""
        self._ensure_tables()
        out = {"rows": 0, "cols": 0, "vals": 0, "blocks": 0, "scale": 0}
        for t in self._tables.values():
            for k in out:
                if k in t:
                    out[k] += int(t[k].nbytes)
        out["total"] = sum(out.values())
        return out

    # ------------------------------------------------------------------
    # Fused-state transitions (the scheduler's promote/demote)
    # ------------------------------------------------------------------

    def _demote(self) -> None:
        if self.fused is None:
            return
        with trace.span("unfuse", cat="switch",
                        tenant=tenant_key(self.fused)):
            for m in tenant_members(self.fused):
                self.shared = apply_pack(self.shared, self.packs[m],
                                         sign=-1.0)
        self.fused = None
        self.fuse_transitions += 1
        self._mark_dirty()

    def _promote(self, tenant: Tenant) -> None:
        tenant = normalize_tenant(tenant)
        if tenant == self.fused or tenant is None:
            return
        self._demote()
        with trace.span("fuse", cat="switch", tenant=tenant_key(tenant)):
            for m in tenant_members(tenant):
                self.shared = apply_pack(self.shared, self.packs[m],
                                         sign=+1.0)
        self.fused = tenant
        self.fuse_transitions += 1
        self._mark_dirty()

    def schedule(self, names: Sequence, defer: bool = False) -> None:
        """Consult the scheduler for this batch's traffic; apply its
        promote/demote before serving.

        ``defer=True`` (the async serving engines) does not apply the
        transition inline: the decision is stashed and the tables for the
        *post-transition* state are built in the background while serving
        continues — fully correct — on the current fused state and tables.
        When that build lands (``poll_async_build``) the fuse/unfuse
        scatter is dispatched and the tables swap atomically, so a
        promotion costs the in-flight decode nothing."""
        if self.scheduler is None:
            return
        d = self.scheduler.observe([normalize_tenant(n) for n in names])
        if d.promote is None and d.demote is None:
            return
        if defer:
            # replacing an unapplied decision is safe: _promote/_demote
            # always transition from the engine's CURRENT fused state, and
            # the old pending build dies on the identity check at poll time
            self._pending = d
            return
        if d.promote is not None:
            self._promote(d.promote)
        elif d.demote is not None:
            self._demote()

    # ------------------------------------------------------------------
    # Forward passes
    # ------------------------------------------------------------------

    def ids_covered(self, names: Sequence) -> bool:
        """True when the current tables can still serve these tenants
        correctly even though a rebuild is pending: only *additive* changes
        (new tenants) happened since the last build, and every requested
        tenant already has a slot. The async serving engines use this to
        keep decoding hot tenants off stale tables while a cold adapter's
        rebuild runs in the background."""
        if not self._dirty:
            return True
        if self._structural:
            return False
        for t in (normalize_tenant(n) for n in names):
            if t is None:
                if self.fused is not None and _BASE_SLOT not in self._slots:
                    return False
            elif t != self.fused and t not in self._slots:
                return False
        return True

    def ids_for(self, names: Sequence, stale_ok: bool = False) -> jax.Array:
        norm = [normalize_tenant(n) for n in names]
        self._batch_no += 1
        for t in norm:
            for m in tenant_members(t):
                if m not in self.packs:
                    raise KeyError(f"request names unregistered adapter "
                                   f"{m!r}")
            if t is not None and not isinstance(t, str):
                if t not in self._stacks:
                    self._mark_dirty(additive=True)  # new stack: needs a slot
                self._stacks[t] = self._batch_no
        # retire stacks that left the traffic mix, or table slots (and
        # rebuild work per new ad-hoc combination) grow without bound
        for t in [t for t, used in self._stacks.items()
                  if t != self.fused
                  and self._batch_no - used > self.stack_ttl]:
            del self._stacks[t]
            # removal: remaining tenants' rows stay valid until the rebuild
            self._mark_dirty(additive=True)
        if not (stale_ok and self.ids_covered(norm)):
            self._ensure_tables()
        ids = []
        for t in norm:
            if t == self.fused or (t is BASE and self.fused is None):
                ids.append(-1)               # pure shared base
            elif t is BASE:
                ids.append(self._slots[_BASE_SLOT])
            else:
                ids.append(self._slots[t])
        return jnp.asarray(ids, jnp.int32)

    def wrapped_params(self, ids: jax.Array, stale_ok: bool = False):
        """The shared tree with side-delta bundles at every adapted weight.
        ``stale_ok`` trusts the caller's ``ids_for(..., stale_ok=True)``
        coverage check and skips the rebuild barrier."""
        if not stale_ok:
            self._ensure_tables()
        tables = self._tables

        def walk(tree, prefix):
            if isinstance(tree, dict):
                return {k: walk(v, prefix + (str(k),)) for k, v in tree.items()}
            if isinstance(tree, (list, tuple)):
                t = [walk(v, prefix + (str(i),)) for i, v in enumerate(tree)]
                return tuple(t) if isinstance(tree, tuple) else t
            key = "/".join(prefix)
            if key in tables:
                t = tables[key]
                lead = tree.shape[:-2]
                return sidedelta_weight(
                    tree, t["rows"], t["cols"], t["vals"],
                    jnp.broadcast_to(ids, lead + ids.shape),
                    scale=t.get("scale"), blocks=t["blocks"])
            return tree

        return walk(self.shared, ())

    def prefill(self, batch, names: Sequence[Optional[str]], cache_size: int):
        p = self.wrapped_params(self.ids_for(names))
        return self._prefill(p, batch, cache_size)

    def decode_step(self, tokens, caches, pos, names: Sequence[Optional[str]]):
        p = self.wrapped_params(self.ids_for(names))
        return self._decode(p, tokens, caches, pos)

    def generate(self, batch, names: Sequence[Optional[str]], tokens: int,
                 cache_size: Optional[int] = None):
        """Greedy-decode ``tokens`` tokens for a mixed-adapter batch.

        Returns (out_tokens (B, tokens) int32, seconds)."""
        cs = cache_size or serving_cache_size(self.cfg,
                                              batch["tokens"].shape[1],
                                              tokens)
        self.schedule(names)
        ids = self.ids_for(names)
        p = self.wrapped_params(ids)
        t0 = time.perf_counter()
        out, _ = greedy_decode(
            self.cfg, batch, tokens,
            lambda b: self._prefill(p, b, cs),
            lambda t, c, pos: self._decode(p, t, c, pos))
        dt = time.perf_counter() - t0
        return out, dt
