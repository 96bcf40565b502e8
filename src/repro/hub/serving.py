"""Request-level serving: continuous batching over lanes or over pages.

Two engines share one request API (``submit`` -> ``ServeFuture``,
``step()``/``run()`` drive the loop; greedy decode; per-request adapters
routed through ``MultiTenantEngine``'s side-delta tables):

**ServingEngine** — the lane engine. ``slots`` decode lanes share one
jitted decode step and one contiguous cache allocation; every lane owns a
full ``cache_size`` KV stripe. Admission prefloods the request at batch 1
and splices the resulting cache into the lane's batch row — the splice uses
explicit per-leaf batch-axis metadata from ``lm.cache_batch_axes`` (KV
leaves carry scan-stack dims in front of batch; hybrid mamba leaves two of
them), never shape inference. Capacity is the number of free *lanes*: a
lane is busy for a request's whole lifetime even though a short request
uses a sliver of its stripe. That stranded memory is what the paged engine
removes.

**PagedServingEngine** — the paged engine (dense/moe text models). KV
memory is one global page pool per layer stack (``lm.init_paged_cache``;
optionally int8 ``QuantKV`` pages) and each request owns a *block table*
mapping logical KV blocks to physical pages, so resident bytes track
actual tokens, not worst-case stripes:

  - **Admission is gated on free pages, not free lanes**: a request enters
    when ``PagePool.can_alloc`` covers its page budget (prompt +
    max_tokens - 1 rounded up to pages, plus COW reserve); otherwise it
    waits FIFO. A slot is just a row in the batched decode step.
  - **Prefix sharing (COW)**: prompt prefixes are hashed per page boundary
    (salted by the request's adapter stack — identical tokens under
    different adapters produce different KV) into the pool's registry
    after prefill; a later request with the same adapter and prefix maps
    the shared pages into its table instead of recomputing them. Shared pages (refcount > 1) are immutable — the engine resolves
    every write range with ``_ensure_writable``, copying a shared page to a
    fresh one (``copy_page``) before the first divergent write. Cold
    registry entries are evicted LRU when the free list runs dry.
  - **Chunked prefill**: prompts prefill in fixed ``chunk_size`` slices,
    one chunk per engine step, interleaved with the decode pass over live
    lanes — a long prompt never stalls live decode by more than one step.
    Chunks are padded to a single static shape (one jit trace); padding
    rows write to the pinned scratch page 0 and are masked out of
    attention.

Greedy decode throughout, so both engines are token-for-token identical to
the fixed-batch engine for the same prompt+adapter (pinned by the parity
tests, including through shared-prefix admission).

**Async adapter prefetch** (``async_prefetch=True``, both engines): a cold
request's adapter starts loading the moment it enters the admission queue
— the disk read on the store's prefetch workers (``prefetch.disk`` spans),
the device-table build + H2D upload on the engine's build worker
(``prefetch.h2d``), both overlapping the in-flight decode steps. Admission
is FIFO-gated on the prefetch landing; hot tenants keep decoding off the
previous tables (``MultiTenantEngine.ids_covered``) while a cold rebuild
is in flight, and FusedLRU transitions are deferred until their
post-transition tables are built in the background (``schedule(defer=)``).
When there is no live decode to hide behind, the engine blocks on the head
request (``prefetch.stall`` spans — the cost async could not hide;
``replay.verify_overlap`` reports the fraction it did). Per-request token
output is identical on every path — same prefill/decode math, same builder
— and with the flag off (default) the engines are byte-for-byte the old
synchronous code path.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import trace
from repro.core.switching import (FusedLRU, Tenant, normalize_tenant,
                                  prior_version, split_version,
                                  tenant_members)
from repro.models import lm
from repro.runtime import faults
from repro.runtime.faults import (AdapterUnavailable, EngineWatchdog,
                                  RequestShed, ServingError, SlotPoisoned,
                                  StoreError, TableBuildError)
from repro.serving.multitenant import MultiTenantEngine

_NO_FALLBACK = object()     # degradation ladder exhausted (sentinel)


class ServeFuture:
    """Resolves when the request's final token is generated — or fails
    with the request's *typed* terminal error (``runtime/faults.py``
    taxonomy: ``RequestShed`` when admission shed it, ``SlotPoisoned``
    when its decode slot was quarantined, ``StoreError`` /
    ``AdapterUnavailable`` when its adapter could not be served and the
    fallback ladder was exhausted). ``degraded`` marks a request the
    ladder downgraded (previous version or base model);
    ``degraded_from`` records what it originally resolved to."""

    def __init__(self, rid: int, adapter: Tenant, max_tokens: int):
        self.rid = rid
        self.adapter = adapter
        self.max_tokens = max_tokens
        self.tokens: List[int] = []
        self.submitted_step: Optional[int] = None
        self.finished_step: Optional[int] = None
        self.submit_time: Optional[float] = None
        self.finish_time: Optional[float] = None
        self.deadline_s: Optional[float] = None  # queue-time budget (shed)
        self.ttft: Optional[float] = None     # seconds to first token
        self.first_token_step: Optional[int] = None
        self.cold = False     # adapter needed a disk load at submit time
        self.cancelled = False
        self.error: Optional[Exception] = None   # typed terminal failure
        self.degraded = False                    # served below what was asked
        self.degraded_from: Optional[Tenant] = None
        self._done = False
        self._event = threading.Event()

    def done(self) -> bool:
        return self._done

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """The generated tokens. A shed/cancelled/poisoned/failed request
        raises its typed terminal error instead of pretending to be in
        flight. ``timeout=`` waits (bounded) for a terminal state —
        useful when another thread drives the engine; the default stays
        non-blocking, exactly the old contract."""
        if timeout is not None and not self._done:
            self._event.wait(timeout)
        if self.error is not None:
            raise self.error
        if self.cancelled:
            raise RuntimeError(f"request {self.rid} was cancelled")
        if not self._done:
            raise RuntimeError(f"request {self.rid} still in flight "
                               f"({len(self.tokens)}/{self.max_tokens} tokens)"
                               " — drive the engine with step()/run()")
        return np.asarray(self.tokens, np.int32)


class _Pending:
    def __init__(self, fut: ServeFuture, prompt: np.ndarray,
                 eos_id: Optional[int], handles=None):
        self.fut = fut
        self.prompt = prompt
        self.eos_id = eos_id
        self.handles = handles or []   # in-flight store prefetches


def _slot_insert(big, small, slot: int, axes):
    """Splice a batch-1 cache tree into lane ``slot`` of the shared cache.

    ``axes`` is the matching pytree of per-leaf batch-axis indices from
    ``lm.cache_batch_axes`` — KV leaves carry scan-stack dims in front of
    batch and hybrid mamba leaves carry two, so the axis is metadata, not
    something to infer from shapes (which is ambiguous whenever any other
    dim equals ``slots``)."""
    def leaf(bg, sm, ax):
        return jax.lax.dynamic_update_slice_in_dim(
            bg, sm.astype(bg.dtype), slot, axis=ax)
    return jax.tree.map(leaf, big, small, axes)


def _first_token(logits) -> int:
    """Greedy first token of a finished prompt (row 0), pulled to the host."""
    row = logits[0]
    with trace.span("sync"):
        row = np.asarray(row)
    return int(np.argmax(row))


def _prefix_salt(adapter: Tenant) -> bytes:
    """Prefix-registry namespace for one request's adapter stack. KV pages
    hold the output of the forward pass that wrote them, so identical
    tokens under different adapters must never share pages."""
    return repr(adapter).encode()


def _resolve_adapter(engine: MultiTenantEngine, adapter: Tenant) -> Tenant:
    """Normalize + validate a request's tenant, lazily pulling members from
    the attached AdapterStore. Bare names resolve to the store's newest
    published version first (``engine.resolve``), so the returned tenant
    holds concrete ``name@v`` ids."""
    adapter = normalize_tenant(engine.resolve(normalize_tenant(adapter)))
    for m in tenant_members(adapter):
        if m not in engine.packs:
            store = engine.store
            if store is not None and m in store:
                engine.register(m)       # lazy: pull it from the store
            else:
                raise KeyError(f"request names unregistered adapter {m!r}")
    return adapter


class _EngineCommon:
    """Request bookkeeping shared by the lane and paged engines."""

    async_prefetch = False    # overridden per instance by the constructors

    def register(self, pack) -> None:
        self.engine.register(pack)

    # -- fault tolerance ------------------------------------------------
    #
    # The degradation ladder (full writeup: src/repro/runtime/README.md).
    # Store-level failures (StoreError after retries, AdapterUnavailable
    # for a quarantined pack) are handled *per request*: the fallback
    # policy walks the request down to the last-good published version
    # (``fallback="previous"``: name@v -> name@v-1 -> ... -> base) or
    # straight to the base model (``"base"``; adapter stacks always fall
    # straight to base — a partially-applied stack is a different model,
    # not a degraded one). A downgraded request is flagged
    # ``fut.degraded`` and keeps decoding; only a request the ladder
    # cannot place fails, with its typed error on the future. Admission
    # failures (bounded queue, queue deadline) shed with ``RequestShed``
    # — never a silent drop. ``nan_guard`` (off by default: the guard
    # reads logits back, which the pre-PR hot path never did) quarantines
    # a slot whose logits go non-finite while the rest of the batch keeps
    # decoding.

    def _init_faults(self, max_queue: Optional[int], fallback: str,
                     nan_guard: bool) -> None:
        if fallback not in ("previous", "base", "none"):
            raise ValueError(f"unknown fallback policy {fallback!r} "
                             "(previous | base | none)")
        self.max_queue = max_queue
        self.fallback = fallback
        self.nan_guard = nan_guard
        self.watchdog = EngineWatchdog()
        self.shed = 0          # requests rejected/expired by admission
        self.degraded = 0      # requests served below what they asked for
        self.poisoned = 0      # slots quarantined on non-finite logits
        self.failed = 0        # requests terminated with a typed error

    def _fail_fut(self, fut: ServeFuture, err: Exception,
                  count_failed: bool = True) -> None:
        """Terminal failure: typed error onto the future, versions
        unpinned, waiters released."""
        fut.error = err
        fut._done = True
        fut._event.set()
        if count_failed:
            self.failed += 1
        self._unpin_versions(fut)

    def _fallback_candidate(self, cand: Tenant):
        """The next rung down the ladder from ``cand``, or the
        ``_NO_FALLBACK`` sentinel when there is nowhere left to go.
        ``None`` (the base model) is a real candidate — it always
        serves."""
        if self.fallback == "none" or cand is None:
            return _NO_FALLBACK
        members = tenant_members(normalize_tenant(cand))
        if len(members) == 1 and self.fallback == "previous":
            prev = prior_version(self.engine.resolve(members[0]))
            store = self.engine.store
            while prev is not None:
                if prev in self.engine.packs or \
                        (store is not None and prev in store
                         and prev not in getattr(store, "quarantined",
                                                 list)()):
                    return prev
                prev = prior_version(prev)
        return None            # base model: the ladder's floor

    def _degrade_submit(self, fut: ServeFuture, orig: Tenant,
                        err: ServingError):
        """Walk the ladder at submit time (the sync path's inline load
        failed). Returns the prepared (adapter, handles, cold) of the
        rung that worked, or None after failing the future typed."""
        cand = self._fallback_candidate(orig)
        while cand is not _NO_FALLBACK:
            try:
                adapter, handles, cold = self._prepare_adapter(cand)
            except (StoreError, AdapterUnavailable):
                cand = self._fallback_candidate(cand)
                continue
            fut.degraded = True
            fut.degraded_from = normalize_tenant(orig)
            self.degraded += 1
            trace.instant("degrade", cat="serving", rid=fut.rid,
                          to=repr(adapter), err=type(err).__name__)
            return adapter, handles, cold
        self._fail_fut(fut, err)
        return None

    def _degrade_queued(self, p, err: ServingError) -> bool:
        """Walk the ladder for a queued request whose async prefetch
        failed: release the dead handles, re-pin and re-prefetch the
        fallback. False when the ladder is exhausted (the request left
        the queue with its typed error)."""
        for h in p.handles:
            h.release()
        p.handles = []
        self._unpin_versions(p.fut)
        orig = p.fut.adapter
        cand = self._fallback_candidate(orig)
        while cand is not _NO_FALLBACK:
            try:
                adapter, handles, _ = self._prepare_adapter(cand)
            except (StoreError, AdapterUnavailable):
                cand = self._fallback_candidate(cand)
                continue
            p.fut.adapter = adapter
            p.handles = handles
            if p.fut.degraded_from is None:
                p.fut.degraded_from = orig
            p.fut.degraded = True
            self._pin_versions(p.fut)
            self.degraded += 1
            trace.instant("degrade", cat="serving", rid=p.fut.rid,
                          to=repr(adapter), err=type(err).__name__)
            return True
        try:
            self._queue.remove(p)
        except ValueError:
            pass
        self._fail_fut(p.fut, err)
        return False

    def _expired(self, fut: ServeFuture,
                 now: Optional[float] = None) -> bool:
        return (fut.deadline_s is not None
                and fut.submit_time is not None
                and ((now or time.perf_counter())
                     - fut.submit_time) > fut.deadline_s)

    def _shed_queued(self, p, reason: str) -> None:
        """Remove a queued request with a typed ``RequestShed`` (cancels
        its prefetches; versions unpinned via the failure path)."""
        try:
            self._queue.remove(p)
        except ValueError:
            pass
        for h in p.handles:
            h.cancel()
        p.handles = []
        self.shed += 1
        trace.instant(f"shed.{reason}", cat="serving", rid=p.fut.rid)
        self._fail_fut(p.fut, RequestShed(
            f"request {p.fut.rid} shed ({reason})", rid=p.fut.rid,
            reason=reason), count_failed=False)

    def _shed_expired(self) -> None:
        """Expire queued requests past their deadline — every step, so a
        deadlined request is never silently parked in the queue."""
        now = time.perf_counter()
        for p in [p for p in self._queue if self._expired(p.fut, now)]:
            self._shed_queued(p, "deadline")

    def health(self) -> Dict[str, Any]:
        """Liveness + degradation snapshot: the watchdog's stall view,
        queue/lane occupancy, fault counters, store quarantine list."""
        store = self.engine.store
        return {
            "watchdog": self.watchdog.snapshot(),
            "queued": len(self._queue),
            "active": sum(a is not None for a in self._active),
            "step_count": self.step_count,
            "tokens_out": self.tokens_out,
            "shed": self.shed,
            "degraded": self.degraded,
            "poisoned": self.poisoned,
            "failed": self.failed,
            "quarantined": (list(store.quarantined())
                            if store is not None
                            and hasattr(store, "quarantined") else []),
        }

    def _next_tokens(self, logits, live: List[int]):
        """Greedy argmax + the poison/NaN-guard path. With no injector
        and ``nan_guard`` off this is byte-for-byte the pre-PR argmax —
        logits never come back to the host."""
        pslot = faults.poison_logits(self.step_count)
        if pslot is None and not self.nan_guard:
            nxt = jnp.argmax(logits, -1)
            with trace.span("sync"):
                return np.asarray(nxt, np.int32), ()
        with trace.span("sync"):
            lg = np.array(logits, np.float32)   # writable host copy
        if pslot is not None and live:
            lg[live[pslot % len(live)]] = np.nan
        bad = tuple(s for s in live
                    if not np.isfinite(lg[s]).all()) if self.nan_guard else ()
        nxt = np.nan_to_num(lg, nan=0.0, posinf=0.0,
                            neginf=0.0).argmax(-1).astype(np.int32)
        return nxt, bad

    # -- versioned hot-swap --------------------------------------------
    #
    # Requests are pinned to the adapter *version* they resolved to at
    # submit (``_prepare_adapter``): ``_vpins`` counts in-flight requests
    # per ``name@v`` and the store's own inflight refcount keeps the pack
    # eviction-proof while pinned. When the last request on a superseded
    # version drains, ``_evict_stale`` retires it from the engine tables
    # and the store's resident tier — the hot-swap completes without ever
    # touching an in-flight request's weights.

    def _pin_versions(self, fut) -> None:
        store = self.engine.store
        fut._vpins = []
        if store is None or not hasattr(store, "pin_use"):
            return
        for m in tenant_members(fut.adapter):
            if split_version(m)[1] is None:
                continue                 # unversioned: nothing to retire
            store.pin_use(m)
            self._vpins[m] = self._vpins.get(m, 0) + 1
            fut._vpins.append(m)

    def _unpin_versions(self, fut) -> None:
        store = self.engine.store
        for m in getattr(fut, "_vpins", ()):
            left = self._vpins.get(m, 0) - 1
            if left > 0:
                self._vpins[m] = left
            else:
                self._vpins.pop(m, None)
            store.unpin_use(m)
        fut._vpins = []
        self._evict_stale()

    def _evict_stale(self) -> None:
        """Retire every registered ``name@v`` that is both superseded (the
        store has published a newer version) and drained (no in-flight
        request pinned to it)."""
        store = self.engine.store
        if store is None or not hasattr(store, "latest_version"):
            return
        for name in list(self.engine.packs):
            base, v = split_version(name)
            if v is None:
                continue
            latest = store.latest_version(base)
            if latest is None or latest <= v or self._vpins.get(name, 0):
                continue
            self.engine.unregister(name)
            store.evict(name)
            trace.instant("hotswap.evict", cat="store", name=name,
                          superseded_by=latest)

    # -- async prefetch pipeline ---------------------------------------
    #
    # With ``async_prefetch=True`` a cold request's adapter starts loading
    # the moment it enters the admission queue: the disk read runs on the
    # store's worker pool, the device-table build on the engine's build
    # worker, and both overlap the in-flight decode steps. The request is
    # only admitted once its packs are registered, and hot tenants keep
    # decoding off the previous tables (``ids_covered``) while the rebuild
    # is in flight. With the flag off (default) nothing below runs and the
    # engines behave exactly as the synchronous path always has.

    def _prepare_adapter(self, adapter):
        """Submit-side adapter resolution. Sync mode registers (and
        disk-loads) inline, exactly as before; async mode only *starts*
        the loads and hands the handles to the queued request. Returns
        (normalized adapter, handles, cold). Version resolution happens
        HERE, at arrival: bare names map to the store's newest published
        version, and that concrete ``name@v`` rides the request's future
        for its whole lifetime — a publish mid-stream never moves an
        in-flight request."""
        adapter = normalize_tenant(self.engine.resolve(
            normalize_tenant(adapter)))
        store = self.engine.store
        members = tenant_members(adapter)
        cold = any(m not in self.engine.packs
                   and not (store is not None
                            and getattr(store, "is_resident",
                                        lambda _n: True)(m))
                   for m in members)
        if not self.async_prefetch:
            return _resolve_adapter(self.engine, adapter), [], cold
        handles = []
        for m in members:
            if m in self.engine.packs:
                # already in the device-table tier: nothing to load
                trace.instant("prefetch.hit", cat="store", name=m,
                              tier="tables")
                continue
            if store is None or m not in store:
                raise KeyError(f"request names unregistered adapter {m!r}")
            handles.append(store.prefetch(
                m, dequantize=self.engine.table_dtype != "int8"))
        return adapter, handles, cold

    def _drain_prefetches(self) -> None:
        """Register every queued request whose prefetch has landed, and
        keep a background table build moving for any pending dirt or
        deferred fused transition. Never blocks."""
        if not self.async_prefetch:
            return
        for p in list(self._queue):
            if p.handles and all(h.done() for h in p.handles):
                try:
                    for h in p.handles:
                        self.engine.register(h.result())
                    p.handles = []
                except ServingError as e:
                    # failed load: walk the request down the fallback
                    # ladder (or fail it typed) — never crash the loop
                    self._degrade_queued(p, e)
        self.engine.kick_async_build()

    def _stall_for_head(self) -> None:
        """No live decode to hide behind: block on the head request's
        prefetch so admission can proceed. The span is the measured cost
        async serving could NOT hide."""
        p = self._queue[0]
        with trace.span("prefetch.stall", cat="store", rid=p.fut.rid):
            while p.handles:
                try:
                    for h in p.handles:
                        self.engine.register(h.result())
                    p.handles = []
                except ServingError as e:
                    if not self._degrade_queued(p, e):
                        return        # head failed out of the queue, typed
                    # else: p now carries the fallback's handles; block on
                    # those too (still the head, still nothing live)
        self.engine.kick_async_build()

    def _admittable(self, p, had_live: bool) -> bool:
        """FIFO admission gate: a request past its deadline is shed right
        here (typed, never silently dropped); with the async pipeline a
        request enters only once its packs are registered, and — while a
        table rebuild is in flight — only if the current tables already
        cover its tenant (hot) or there is no live decode the stall could
        disturb."""
        if self._expired(p.fut):
            self._shed_queued(p, "deadline")
            return False
        if not self.async_prefetch:
            return True
        if p.handles:
            return False              # disk load still in flight
        if self.engine.tables_ready():
            return True
        if self.engine.ids_covered([p.fut.adapter]):
            return True
        return not had_live

    def cancel(self, fut: ServeFuture) -> bool:
        """Abort a still-queued request: drop it from the queue and cancel
        any in-flight prefetch (the disk read is skipped when it has not
        started). Admitted requests cannot be cancelled."""
        for p in self._queue:
            if p.fut is fut:
                self._queue.remove(p)
                for h in p.handles:
                    h.cancel()
                p.handles = []
                fut.cancelled = True
                trace.instant("prefetch.cancel", cat="store", rid=fut.rid)
                self._fail_fut(fut, RequestShed(
                    f"request {fut.rid} was cancelled", rid=fut.rid,
                    reason="cancelled"), count_failed=False)
                return True
        return False

    def shutdown(self, include_store: bool = False) -> None:
        """Join the engine's background build worker (and optionally the
        store's prefetch pool — stores may be shared, so opt-in)."""
        self.engine.shutdown()
        store = self.engine.store
        if include_store and store is not None \
                and hasattr(store, "shutdown"):
            store.shutdown()

    def pending(self) -> int:
        return len(self._queue) + sum(p is not None for p in self._active)

    def kv_cache_bytes(self) -> int:
        return sum(int(x.nbytes) for x in jax.tree.leaves(self.caches))

    def _emit(self, slot: int, token: int) -> None:
        """Record one generated token. ``_pos`` is NOT touched here — it
        always points at the cache index the next decode step writes to."""
        p = self._active[slot]
        p.fut.tokens.append(int(token))
        self.tokens_out += 1
        if len(p.fut.tokens) == 1:
            p.fut.first_token_step = self.step_count
            if p.fut.submit_time is not None:
                p.fut.ttft = time.perf_counter() - p.fut.submit_time
        self._last[slot] = token
        if (len(p.fut.tokens) >= p.fut.max_tokens
                or (p.eos_id is not None and int(token) == p.eos_id)):
            self._finish(slot)

    def run(self, max_steps: int = 100_000) -> float:
        """Drive step() until every queued request resolved; returns
        wall-clock seconds."""
        t0 = time.perf_counter()
        for _ in range(max_steps):
            if not self.step() and not self._queue \
                    and all(p is None for p in self._active):
                break
        else:
            raise RuntimeError(f"run() hit max_steps={max_steps} with "
                               f"{self.pending()} requests in flight")
        return time.perf_counter() - t0


class ServingEngine(_EngineCommon):
    """Continuous-batching front end over the multi-tenant side-delta path."""

    def __init__(self, cfg, params, *, slots: int = 4, cache_size: int = 128,
                 scheduler: Optional[FusedLRU] = None, store=None,
                 table_dtype: str = "f32",
                 async_prefetch: bool = False, slot_pad: int = 1,
                 max_queue: Optional[int] = None,
                 fallback: str = "previous", nan_guard: bool = False):
        if cfg.encoder_only:
            raise ValueError("encoder-only archs have no decode serving path")
        self.cfg = cfg
        self.async_prefetch = async_prefetch
        self.slots = slots
        self.cache_size = cache_size
        self.engine = MultiTenantEngine(cfg, params, scheduler=scheduler,
                                        store=store, table_dtype=table_dtype,
                                        slot_pad=slot_pad)
        self.caches = lm.init_cache(cfg, slots, cache_size)
        self._axes = lm.cache_batch_axes(cfg)
        self._active: List[Optional[_Pending]] = [None] * slots
        self._pos = np.zeros((slots,), np.int32)      # next cache write index
        self._last = np.zeros((slots,), np.int32)     # last generated token
        self._queue: "deque[_Pending]" = deque()
        self._rid = 0
        self._vpins: Dict[str, int] = {}   # name@v -> in-flight requests
        self.step_count = 0
        self.tokens_out = 0
        self.decode_slot_waste = 0    # idle-lane decode steps (utilization)
        self._init_faults(max_queue, fallback, nan_guard)

    # ------------------------------------------------------------------
    # Request API
    # ------------------------------------------------------------------

    def submit(self, prompt_tokens, adapter: Tenant = None,
               max_tokens: int = 16,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None) -> ServeFuture:
        """Queue one request; returns its future. ``adapter`` is a registered
        adapter id, a stack of ids, or None for the base model.
        ``deadline_s`` bounds *queue* time: a request still unadmitted
        that long after submit is shed with ``RequestShed``. A full
        bounded queue (``max_queue=``) sheds at submit — the returned
        future carries the typed error instead of a silent drop."""
        if max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        prefix = (self.cfg.num_prefix_embeds
                  if self.cfg.modality == "vision" else 0)
        # the final generated token is returned but never written back to
        # the cache, so a request needs one slot less than prompt+max_tokens
        need = prompt.shape[0] + prefix + max_tokens - 1
        if need > self.cache_size:
            raise ValueError(f"prompt ({prompt.shape[0]}) + max_tokens "
                             f"({max_tokens}) needs {need} cache slots, "
                             f"engine has {self.cache_size}")
        # arrival is stamped BEFORE adapter resolution: the sync path's
        # inline disk load is queue time the request actually waited
        t_sub = time.perf_counter()
        fut = ServeFuture(self._rid, normalize_tenant(adapter), max_tokens)
        self._rid += 1
        fut.submit_time = t_sub
        fut.deadline_s = deadline_s
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self.shed += 1
            trace.instant("shed.queue_full", cat="serving", rid=fut.rid)
            self._fail_fut(fut, RequestShed(
                f"request {fut.rid} shed (queue_full: "
                f"{len(self._queue)} >= {self.max_queue})", rid=fut.rid,
                reason="queue_full"), count_failed=False)
            return fut
        try:
            adapter, handles, cold = self._prepare_adapter(adapter)
        except (StoreError, AdapterUnavailable) as e:
            prepared = self._degrade_submit(fut, adapter, e)
            if prepared is None:
                return fut             # ladder exhausted: typed failure
            adapter, handles, cold = prepared
        fut.adapter = adapter
        fut.cold = cold
        self._pin_versions(fut)
        self._queue.append(_Pending(fut, prompt, eos_id, handles))
        return fut

    # ------------------------------------------------------------------
    # Scheduling loop
    # ------------------------------------------------------------------

    def _batch_for(self, prompt: np.ndarray) -> Dict[str, Any]:
        batch = {"tokens": jnp.asarray(prompt[None])}
        if self.cfg.modality == "vision":
            batch["patch_embeds"] = jnp.zeros(
                (1, self.cfg.num_prefix_embeds, self.cfg.d_model))
        return batch

    def _finish(self, slot: int) -> None:
        p = self._active[slot]
        p.fut.finished_step = self.step_count
        p.fut.finish_time = time.perf_counter()
        p.fut._done = True
        p.fut._event.set()
        self._active[slot] = None
        self._pos[slot] = 0
        self._last[slot] = 0
        self._unpin_versions(p.fut)

    def _poison(self, slot: int) -> None:
        """Quarantine ONE decode slot whose logits went non-finite: its
        request fails typed, the lane is freed, the rest of the batch
        never stops decoding."""
        p = self._active[slot]
        self.poisoned += 1
        trace.instant("slot.poison", cat="serving", rid=p.fut.rid,
                      slot=slot, step=self.step_count)
        self._active[slot] = None
        self._pos[slot] = 0
        self._last[slot] = 0
        self._fail_fut(p.fut, SlotPoisoned(
            f"request {p.fut.rid} poisoned: non-finite logits on slot "
            f"{slot} at step {self.step_count}", rid=p.fut.rid,
            step=self.step_count))

    def _admit(self, slot: int, p: _Pending) -> None:
        with trace.span("admit", rid=p.fut.rid, slot=slot,
                        prompt=int(p.prompt.shape[0])):
            names: List[Tenant] = [p.fut.adapter]
            stale = self.async_prefetch
            ids = self.engine.ids_for(names, stale_ok=stale)
            wp = self.engine.wrapped_params(ids, stale_ok=stale)
            logits, c1 = self.engine._prefill(wp, self._batch_for(p.prompt),
                                              self.cache_size)
            self.caches = [_slot_insert(big, small, slot, ax)
                           for big, small, ax
                           in zip(self.caches, c1, self._axes)]
            prefix = (self.cfg.num_prefix_embeds
                      if self.cfg.modality == "vision" else 0)
            self._active[slot] = p
            p.fut.submitted_step = self.step_count
            self._pos[slot] = p.prompt.shape[0] + prefix
            self._emit(slot, _first_token(logits))

    def step(self) -> bool:
        """Admit queued requests into free slots, then run one decode step
        over every occupied lane. Returns False when fully drained."""
        with trace.span("step", engine="lane") as sp:
            t0 = time.perf_counter()
            faults.on_engine_step(self.step_count)
            self._shed_expired()
            self._drain_prefetches()
            had_live = any(a is not None for a in self._active)
            if self.async_prefetch and not had_live and self._queue \
                    and self._queue[0].handles:
                self._stall_for_head()
            for slot in range(self.slots):
                if self._active[slot] is None and self._queue:
                    if not self._admittable(self._queue[0], had_live):
                        break          # FIFO: head's prefetch still landing
                    p = self._queue.popleft()
                    try:
                        self._admit(slot, p)
                    except TableBuildError:
                        # build failed (simulated OOM): requeue at the
                        # head and retry on the next step's fresh build
                        self._queue.appendleft(p)
                        trace.instant("fault.build_backoff", cat="tables")
                        break
            live = [s for s in range(self.slots)
                    if self._active[s] is not None]
            if not live:
                return bool(self._queue)
            self.step_count += 1
            sp.set(step=self.step_count, live=len(live))
            self.decode_slot_waste += self.slots - len(live)
            names = [self._active[s].fut.adapter
                     if self._active[s] is not None else None
                     for s in range(self.slots)]
            # the scheduler sees only live lanes: idle slots are not
            # base-model traffic, and counting them would dilute every
            # tenant's share
            self.engine.schedule([names[s] for s in live],
                                 defer=self.async_prefetch)
            try:
                with trace.span("decode", live=len(live)):
                    stale = self.async_prefetch
                    ids = self.engine.ids_for(names, stale_ok=stale)
                    wp = self.engine.wrapped_params(ids, stale_ok=stale)
                    toks = jnp.asarray(self._last[:, None])
                    logits, self.caches = self.engine._decode(
                        wp, toks, self.caches, jnp.asarray(self._pos))
                    nxt, bad = self._next_tokens(logits, live)
            except TableBuildError:
                # nothing was emitted and no position advanced: the whole
                # decode retries next step against a fresh table build
                trace.instant("fault.build_backoff", cat="tables")
                return True
            for s in live:
                self._pos[s] += 1      # this step's KV landed at _pos[s]
                if s in bad:
                    self._poison(s)
                else:
                    self._emit(s, int(nxt[s]))
            self.watchdog.record(time.perf_counter() - t0)
            return True


# ---------------------------------------------------------------------------
# Paged engine
# ---------------------------------------------------------------------------

class _PagedRequest:
    __slots__ = ("fut", "prompt", "eos_id", "need", "nblk", "state", "done",
                 "pages", "reserve", "handles")

    def __init__(self, fut: ServeFuture, prompt: np.ndarray,
                 eos_id: Optional[int], need: int, nblk: int, handles=None):
        self.fut = fut
        self.prompt = prompt
        self.eos_id = eos_id
        self.need = need          # KV rows this request may write
        self.nblk = nblk          # block-table entries it needs
        self.state = "prefill"
        self.done = 0             # prompt tokens already in the cache
        self.pages: List[int] = []     # block-table pages (1 ref each)
        self.reserve: List[int] = []   # preallocated COW spares
        self.handles = handles or []   # in-flight store prefetches


class PagedServingEngine(_EngineCommon):
    """Continuous batching over a paged KV pool with COW prefix sharing and
    chunked-prefill admission. Dense/moe text models only (SSM state is O(1)
    per request; vision prefixes are not token-addressed)."""

    def __init__(self, cfg, params, *, slots: int = 4, num_pages: int = 64,
                 page_size: int = 8, max_len: Optional[int] = None,
                 chunk_size: Optional[int] = None,
                 scheduler: Optional[FusedLRU] = None, store=None,
                 table_dtype: str = "f32", quant_kv: bool = False,
                 async_prefetch: bool = False, slot_pad: int = 1,
                 max_queue: Optional[int] = None,
                 fallback: str = "previous", nan_guard: bool = False):
        if cfg.encoder_only:
            raise ValueError("encoder-only archs have no decode serving path")
        from repro.serving.kvcache import PagePool, copy_page, pages_for
        self.cfg = cfg
        self.async_prefetch = async_prefetch
        self.slots = slots
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_len = max_len or (num_pages - 1) * page_size
        self.max_blocks = pages_for(self.max_len, page_size)
        self.chunk_size = chunk_size or page_size
        self.engine = MultiTenantEngine(cfg, params, scheduler=scheduler,
                                        store=store, table_dtype=table_dtype,
                                        slot_pad=slot_pad)
        self.pool = PagePool(num_pages, page_size)
        self.caches = lm.init_paged_cache(cfg, num_pages, page_size,
                                          quant=quant_kv)
        self._bt = np.zeros((slots, self.max_blocks), np.int32)
        self._pos = np.zeros((slots,), np.int32)
        self._last = np.zeros((slots,), np.int32)
        self._active: List[Optional[_PagedRequest]] = [None] * slots
        self._queue: "deque[_PagedRequest]" = deque()
        self._rid = 0
        self._vpins: Dict[str, int] = {}   # name@v -> in-flight requests
        self.step_count = 0
        self.tokens_out = 0
        self.decode_slot_waste = 0
        self.prefill_chunks = 0
        self._init_faults(max_queue, fallback, nan_guard)
        self.peak_resident = 0        # max concurrently admitted requests
        self.peak_used_pages = 0      # incl. evictable registry-only pages
        self.peak_ws_pages = 0        # pages pinned by admitted requests

        def _dec(p, t, c, pos, bt):
            return lm.decode_step(p, self.cfg, t, c, pos, block_tables=bt)

        def _pfc(p, toks, c, bt, start, valid):
            return lm.prefill_chunk(p, self.cfg, toks, c, bt, start, valid)

        self._decode = jax.jit(_dec)
        self._prefill_chunk = jax.jit(_pfc)
        self._copy = jax.jit(functools.partial(copy_page, page_axis=1))

    def page_bytes(self) -> int:
        """Device bytes of ONE physical page across the whole layer stack."""
        return self.kv_cache_bytes() // self.num_pages

    # ------------------------------------------------------------------
    # Request API
    # ------------------------------------------------------------------

    def submit(self, prompt_tokens, adapter: Tenant = None,
               max_tokens: int = 16,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None) -> ServeFuture:
        from repro.serving.kvcache import pages_for
        if max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        # the final generated token is never written back: one row less
        need = prompt.shape[0] + max_tokens - 1
        if need > self.max_len:
            raise ValueError(f"prompt ({prompt.shape[0]}) + max_tokens "
                             f"({max_tokens}) needs {need} KV rows, engine "
                             f"caps requests at {self.max_len}")
        nblk = pages_for(need, self.page_size)
        if nblk > self.num_pages - 1:
            raise ValueError(f"request needs {nblk} pages, pool has "
                             f"{self.num_pages - 1}")
        # arrival stamp precedes adapter resolution (see ServingEngine.submit)
        t_sub = time.perf_counter()
        fut = ServeFuture(self._rid, normalize_tenant(adapter), max_tokens)
        self._rid += 1
        fut.submit_time = t_sub
        fut.deadline_s = deadline_s
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self.shed += 1
            trace.instant("shed.queue_full", cat="serving", rid=fut.rid)
            self._fail_fut(fut, RequestShed(
                f"request {fut.rid} shed (queue_full: "
                f"{len(self._queue)} >= {self.max_queue})", rid=fut.rid,
                reason="queue_full"), count_failed=False)
            return fut
        try:
            adapter, handles, cold = self._prepare_adapter(adapter)
        except (StoreError, AdapterUnavailable) as e:
            prepared = self._degrade_submit(fut, adapter, e)
            if prepared is None:
                return fut             # ladder exhausted: typed failure
            adapter, handles, cold = prepared
        fut.adapter = adapter
        fut.cold = cold
        self._pin_versions(fut)
        self._queue.append(_PagedRequest(fut, prompt, eos_id, need, nblk,
                                         handles))
        return fut

    # ------------------------------------------------------------------
    # Page plumbing
    # ------------------------------------------------------------------

    def _try_admit(self, slot: int, r: _PagedRequest) -> bool:
        """Map the request into ``slot`` if the pool can cover its pages:
        unshared blocks, plus a COW reserve for the boundary page (when the
        prefix match ends inside a shared page) and for the prompt tail
        (prefix registration re-shares it, so the first decode write must
        copy). Takes no pages on failure."""
        p = self.page_size
        L_ = r.prompt.shape[0]
        with trace.span("admit", rid=r.fut.rid, slot=slot,
                        prompt=int(L_)) as sp:
            shared_len, shared = self.pool.match_prefix(
                r.prompt, salt=_prefix_salt(r.fut.adapter))
            cow = int(shared_len < len(shared) * p)
            cow += int(r.need > L_ and L_ % p != 0)
            n_owned = r.nblk - len(shared)
            if not self.pool.can_alloc(n_owned + cow):
                self.pool.release(shared)
                sp.set(admitted=False)
                return False
            fresh = self.pool.alloc(n_owned + cow)
            owned, r.reserve = fresh[:n_owned], fresh[n_owned:]
            row = list(shared) + owned
            r.pages = list(row)
            self._bt[slot, :] = 0
            self._bt[slot, :len(row)] = row
            r.state = "prefill"
            r.done = shared_len
            self._active[slot] = r
            r.fut.submitted_step = self.step_count
            sp.set(admitted=True, shared_len=int(shared_len),
                   pages=len(row), reserve=len(r.reserve))
            return True

    def _ensure_writable(self, slot: int, lo: int, hi: int) -> None:
        """COW every shared page under write range [lo, hi)."""
        p = self.page_size
        r = self._active[slot]
        for blk in range(lo // p, (hi - 1) // p + 1):
            pg = int(self._bt[slot, blk])
            if not self.pool.is_shared(pg):
                continue
            dst = r.reserve.pop() if r.reserve else self.pool.alloc(1)[0]
            with trace.span("cow_copy", cat="pages", slot=slot,
                            src=pg, dst=int(dst)):
                self.caches = self._copy(self.caches, pg, dst)
            self._bt[slot, blk] = dst
            r.pages[r.pages.index(pg)] = dst
            self.pool.release([pg])
            self.pool.cow_copies += 1

    def _finish(self, slot: int) -> None:
        r = self._active[slot]
        r.fut.finished_step = self.step_count
        r.fut.finish_time = time.perf_counter()
        r.fut._done = True
        r.fut._event.set()
        self.pool.release(r.pages + r.reserve)
        r.pages, r.reserve = [], []
        self._active[slot] = None
        self._bt[slot, :] = 0
        self._pos[slot] = 0
        self._last[slot] = 0
        self._unpin_versions(r.fut)

    def _poison(self, slot: int) -> None:
        """Quarantine ONE decode slot (non-finite logits): fail the
        request typed, free its pages, keep the rest of the batch live."""
        r = self._active[slot]
        self.poisoned += 1
        trace.instant("slot.poison", cat="serving", rid=r.fut.rid,
                      slot=slot, step=self.step_count)
        self.pool.release(r.pages + r.reserve)
        r.pages, r.reserve = [], []
        self._active[slot] = None
        self._bt[slot, :] = 0
        self._pos[slot] = 0
        self._last[slot] = 0
        self._fail_fut(r.fut, SlotPoisoned(
            f"request {r.fut.rid} poisoned: non-finite logits on slot "
            f"{slot} at step {self.step_count}", rid=r.fut.rid,
            step=self.step_count))

    def _prefill_step(self, slot: int) -> None:
        from repro.serving.kvcache import pages_for
        r = self._active[slot]
        L_ = r.prompt.shape[0]
        lo = r.done
        hi = min(L_, lo + self.chunk_size)
        with trace.span("prefill_chunk", slot=slot, lo=int(lo), hi=int(hi)):
            self._ensure_writable(slot, lo, hi)
            toks = np.zeros((1, self.chunk_size), np.int32)
            toks[0, :hi - lo] = r.prompt[lo:hi]
            stale = self.async_prefetch
            ids = self.engine.ids_for([r.fut.adapter], stale_ok=stale)
            wp = self.engine.wrapped_params(ids, stale_ok=stale)
            logits, self.caches = self._prefill_chunk(
                wp, jnp.asarray(toks), self.caches,
                jnp.asarray(self._bt[slot:slot + 1]),
                jnp.int32(lo), jnp.int32(hi - lo))
        r.done = hi
        self.prefill_chunks += 1
        if hi == L_:
            # registry refs re-share the prompt pages (incl. the pristine
            # partial tail); the COW reserve covers the first decode write
            self.pool.register_prefix(
                r.prompt, [int(x) for x in
                           self._bt[slot, :pages_for(L_, self.page_size)]],
                salt=_prefix_salt(r.fut.adapter))
            r.state = "live"
            self._pos[slot] = L_
            self._emit(slot, _first_token(logits))

    # ------------------------------------------------------------------
    # Scheduling loop
    # ------------------------------------------------------------------

    def step(self) -> bool:
        """FIFO-admit while pages last, run ONE prefill chunk, then one
        decode step over every live lane. Returns False when drained."""
        with trace.span("step", engine="paged") as sp:
            t0 = time.perf_counter()
            faults.on_engine_step(self.step_count)
            self._shed_expired()
            self._drain_prefetches()
            had_live = any(a is not None for a in self._active)
            if self.async_prefetch and not had_live and self._queue \
                    and self._queue[0].handles:
                self._stall_for_head()
            for slot in range(self.slots):
                if self._active[slot] is None and self._queue:
                    if not self._admittable(self._queue[0], had_live):
                        break          # FIFO: head's prefetch still landing
                    if not self._try_admit(slot, self._queue[0]):
                        break
                    self._queue.popleft()
            pf = [s for s in range(self.slots) if self._active[s] is not None
                  and self._active[s].state == "prefill"]
            live = [s for s in range(self.slots)
                    if self._active[s] is not None
                    and self._active[s].state == "live"]
            self.peak_resident = max(self.peak_resident, len(pf) + len(live))
            self.peak_used_pages = max(self.peak_used_pages,
                                       self.pool.used_pages())
            # working set = distinct pages pinned by admitted requests
            # (block tables, shared prefixes counted once, COW reserves).
            # Registry-only pages are excluded: they are an LRU cache,
            # reclaimable on demand.
            ws = set()
            for s in pf + live:
                ws.update(int(x) for x in self._bt[s] if x)
                ws.update(self._active[s].reserve)
            self.peak_ws_pages = max(self.peak_ws_pages, len(ws))
            if not pf and not live:
                return bool(self._queue)
            self.step_count += 1
            sp.set(step=self.step_count, prefill=len(pf), live=len(live))
            trace.counter("free_pages", self.pool.free_pages(),
                          cat="pages")
            trace.counter("resident", len(pf) + len(live))
            if pf:
                try:
                    self._prefill_step(pf[0])
                except TableBuildError:
                    # chunk not applied (r.done untouched): retried next
                    # step against a fresh table build
                    trace.instant("fault.build_backoff", cat="tables")
            if live:
                self.decode_slot_waste += self.slots - len(live)
                live_set = set(live)
                names = [self._active[s].fut.adapter
                         if s in live_set else None
                         for s in range(self.slots)]
                self.engine.schedule([names[s] for s in live],
                                     defer=self.async_prefetch)
                try:
                    with trace.span("decode", live=len(live)):
                        stale = self.async_prefetch
                        ids = self.engine.ids_for(names, stale_ok=stale)
                        wp = self.engine.wrapped_params(ids, stale_ok=stale)
                        for s in live:
                            self._ensure_writable(s, int(self._pos[s]),
                                                  int(self._pos[s]) + 1)
                        # idle / still-prefilling lanes decode against the
                        # scratch page
                        mask = np.zeros((self.slots,), bool)
                        mask[live] = True
                        bt = np.where(mask[:, None], self._bt, 0)
                        pos = np.where(mask, self._pos, 0)
                        logits, self.caches = self._decode(
                            wp, jnp.asarray(self._last[:, None]), self.caches,
                            jnp.asarray(pos), jnp.asarray(bt))
                        nxt, bad = self._next_tokens(logits, live)
                except TableBuildError:
                    # no emit, no position advance: whole decode retries
                    trace.instant("fault.build_backoff", cat="tables")
                    return True
                for s in live:
                    self._pos[s] += 1  # this step's KV landed at _pos[s]
                    if s in bad:
                        self._poison(s)
                    else:
                        self._emit(s, int(nxt[s]))
            self.watchdog.record(time.perf_counter() - t0)
            return True
