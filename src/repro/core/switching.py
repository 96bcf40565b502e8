"""Rapid adapter switching (paper §3.2, App. A/B) and the LoRA comparison.

``SwitchEngine`` manages a deployed base model. Loading a SHiRA pack
overwrites only the pack's 1-2% of entries (scatter-add of the delta);
unloading subtracts it back — no separate fuse/unfuse stage, no unfused
branches in the forward pass. ``LoraEngine`` reproduces the HuggingFace
load->fuse->infer->unfuse->unload pipeline the paper benchmarks against
(W + s*A@B touches and rewrites *every* entry).

Both engines account bytes moved so benchmarks/rapid_switching.py can report
the switch-cost asymmetry measured in the paper's Fig. 5 alongside wall-clock.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import trace
from repro.core import masks as M
from repro.core.adapters import AdapterPack
from repro.core.masks import path_str
from repro.kernels.scatter_apply import Layout, bucket, rows_walked
from repro.kernels.scatter_apply import scatter_apply as scatter_tiles

# A tenant names either the base model (None), one adapter ("a0"), or an
# adapter *stack* (("a0", "lang_de")) — several adapters applied together,
# e.g. an agent persona on top of a domain adapter.
Tenant = Union[None, str, Tuple[str, ...]]


def normalize_tenant(name) -> Tenant:
    """Canonical tenant key: None | str | sorted tuple (len >= 2).

    Stacks are additive (scatter-adds commute), so order inside a stack is
    irrelevant — sorting makes ("b", "a") and ("a", "b") one tenant."""
    if name is None or isinstance(name, str):
        return name
    members = sorted(set(name))      # dedup: ("a", "a") must not double-load
    if not members:
        return None
    return members[0] if len(members) == 1 else tuple(members)


def tenant_members(name: Tenant) -> List[str]:
    if name is None:
        return []
    return [name] if isinstance(name, str) else list(name)


def tenant_key(name: Tenant) -> str:
    """Stable string key for sorting/labelling mixed str|tuple tenants."""
    return "" if name is None else "+".join(tenant_members(name))


def split_version(name: str) -> Tuple[str, Optional[int]]:
    """Parse a versioned adapter id: ``"persona@3" -> ("persona", 3)``.

    Unversioned ids (no ``@``, or a non-numeric suffix — ``@`` is legal in
    plain adapter names) come back as ``(name, None)``. The versioned-id
    scheme is how continuous personalization publishes retrained adapters:
    ``AdapterStore.publish`` assigns monotonically increasing versions per
    base name, lookups of the bare name resolve newest-wins, and in-flight
    serving requests stay pinned to the concrete ``name@v`` they resolved
    at submit time."""
    base, sep, v = name.rpartition("@")
    if sep and base and v.isdigit():
        return base, int(v)
    return name, None


def versioned_id(base: str, version: int) -> str:
    """The canonical id for one published version of an adapter."""
    return f"{base}@{int(version)}"


def prior_version(name: str) -> Optional[str]:
    """The previous published version of a versioned id — the fallback
    ladder's next candidate when ``name@v`` fails to load
    (``"persona@3" -> "persona@2"``). ``None`` for ``@1`` and for
    unversioned names: the ladder falls through to the base model."""
    base, v = split_version(name)
    if v is None or v <= 1:
        return None
    return versioned_id(base, v - 1)


@dataclass
class SwitchStats:
    name: str
    seconds: float
    entries_written: int
    bytes_written: int
    weight_bytes_total: int


def _tree_bytes(tree) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))


def _one_device(x) -> bool:
    """A leaf the switch program takes: a float matrix (or a stack of
    them) held whole by one device. Anything else (a sharded leaf, a host
    array) keeps XLA's scatter."""
    return (isinstance(x, jax.Array) and x.ndim >= 2
            and jnp.issubdtype(x.dtype, jnp.floating)
            and len(x.sharding.device_set) == 1)


def _blocks(shape) -> int:
    """128 x 128 weight blocks of a (..., n, m) leaf."""
    *lead, n, m = shape
    return int(np.prod(lead, dtype=np.int64)) * -(-n // 128) * -(-m // 128)


@functools.partial(jax.jit, static_argnames=("shapes",))
def _bucket_program(entries, *, shapes):
    """Bucket each leaf's (idx, vals) by weight block (one sort a leaf)."""
    return tuple(bucket(i, v, shape) for (i, v), shape in zip(entries,
                                                               shapes))


@jax.jit
def _copy_program(leaves):
    """Fresh buffers for leaves the caller owns (the first write of each)."""
    return tuple(jnp.copy(x) for x in leaves)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("interpret",))
def _apply_program(leaves, outgoing, incoming, scales, *, interpret: bool):
    """One pass over every adapted leaf, in place (the leaves are donated):
    w - a_out * S_out + a_in * S_in, with ``scales`` = (-a_out, a_in) and
    each leaf's layouts in ``outgoing`` / ``incoming``. A load's outgoing
    layouts (and an unload's incoming ones) are empty: the same program
    serves all three. Returns the new leaves and the rows of entries the
    kernel walks."""
    new, walked = [], jnp.zeros((), jnp.int32)
    for w, out, inc in zip(leaves, outgoing, incoming):
        new.append(_leaf_program(w, out, inc, scales, interpret=interpret))
        walked = walked + rows_walked(out) + rows_walked(inc)
    return tuple(new), walked


@functools.partial(jax.jit, static_argnames=("interpret",))
def _leaf_program(w, out, inc, scales, *, interpret: bool):
    # a nested jit: leaves of one shape share one trace and lowering
    return scatter_tiles(w, [out, inc], scales, interpret=interpret)


def _empty(lay: Layout) -> Layout:
    """A layout with no entries, shaped as ``lay``: every block's range is
    [0, 0), so the kernel walks none of its rows."""
    return lay._replace(bounds=jnp.zeros(lay.bounds.shape, jnp.int32))


class SwitchEngine:
    """Holds deployed params; one active adapter (or fused set) at a time.

    With an ``AdapterStore`` attached, packs may be referred to by name —
    ``load``/``switch``/``load_fused`` accept either an ``AdapterPack`` or a
    registered adapter id, and the store handles disk residency.

    A switch is one jitted program per pack layout (the adapted paths,
    leaf shapes and per-leaf K, the same for every pack of a deployment):
    for every adapted leaf it subtracts the outgoing pack and adds the
    incoming one in a single pass of the ``scatter_apply`` tile kernel.
    The incoming pack is bucketed by weight block on the device first
    (span ``switch.bucket``, one sort a leaf); the engine keeps that
    layout beside ``active`` while the pack is the last one loaded, so its
    unload needs no sort. ``load`` (no outgoing pack), ``unload`` (no
    incoming one), ``switch`` and ``load_fused`` all run the same program.

    In place: a leaf this engine wrote is donated to its next program and
    updated without a copy. The caller's own arrays are never donated —
    the first write of each leaf copies it — so the tree passed in stays
    valid. **A tree read from ``engine.params`` is invalid after the next
    load, unload or switch**: read ``engine.params`` again after each.
    Leaves not held whole by one device (sharded, or host arrays) take
    XLA's scatter (``M.scatter_packed_add``) instead; the tracer counts
    both kinds per program (``switch.kernel_leaves``,
    ``switch.fallback_leaves``).

    Each ``load``, ``unload`` and ``switch`` dispatches its programs
    (spans ``switch.load``/``switch.unload``: the host's dispatch; the
    load's holds ``switch.bucket``) and then, with ``blocking=True``,
    waits once for every leaf it touched (span ``switch.wait``, which
    records the rows of 128 entries the kernel walked while a trace
    records); ``SwitchStats.seconds`` runs from the call until those
    leaves are ready. ``blocking=False`` skips the wait and rides JAX
    async dispatch: the device-side update overlaps whatever the host
    does next, still ordered before any later computation that reads
    ``self.params``. ``seconds`` then measures dispatch, not completion —
    keep the default for switch-latency benchmarking."""

    def __init__(self, params, store=None, blocking: bool = True):
        self.params = params
        self.store = store
        self.blocking = blocking
        self.active: List[AdapterPack] = []
        # path -> the leaf this engine last wrote there (donated next time)
        self._mine: Dict[str, jax.Array] = {}
        # bucketed layout of active[-1]: (pack, {path: Layout})
        self._kept: Optional[Tuple[AdapterPack, dict]] = None
        self._walked: List[jax.Array] = []
        self._leaving: Optional[AdapterPack] = None   # see ``switch``

    def _resolve(self, pack) -> AdapterPack:
        if isinstance(pack, str):
            if self.store is None:
                raise ValueError(f"adapter named by id {pack!r} but no "
                                 "AdapterStore attached")
            return self.store.get(pack)
        return pack

    def _apply(self, pack: AdapterPack, sign: float) -> None:
        """Dispatch one pack's unload (sign < 0) or load (sign > 0). A load
        from ``switch`` carries the unload of the pack it replaces
        (``_leaving``) in the same program."""
        if sign < 0:
            self._program(pack, None)
        else:
            self._program(self._leaving, pack)

    def _program(self, out: Optional[AdapterPack],
                 inc: Optional[AdapterPack]) -> None:
        """Dispatch one pass: unload ``out`` and load ``inc`` (either may
        be None) into every leaf they touch."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.params)
        leaves = [x for _, x in flat]
        where = {path_str(p): i for i, (p, _) in enumerate(flat)}
        paths = [p for p in dict.fromkeys(
            [*(out.entries if out else ()), *(inc.entries if inc else ())])
            if p in where]
        kernel = [p for p in paths if _one_device(leaves[where[p]])]
        fallback = [p for p in paths if p not in set(kernel)]
        kept = (self._kept[1] if out is not None and self._kept is not None
                and self._kept[0] is out else None)
        signed = [(pk, sign * pk.alpha) for pk, sign in ((out, -1.0),
                                                         (inc, 1.0))
                  if pk is not None]
        with trace.span("switch.load" if inc is not None else "switch.unload",
                        cat="switch", name=(inc or out).name,
                        bytes=sum(p.nbytes() for p in (out, inc) if p),
                        leaves=len(kernel),
                        blocks=sum(_blocks(leaves[where[p]].shape)
                                   for p in kernel)):
            # the packs' layouts: the kept one, or bucketed now
            lays = {(id(out), p): kept[p] for p in kept or ()}
            todo = [(pk, p) for pk, _ in signed for p in kernel
                    if p in pk.entries and (id(pk), p) not in lays]
            if todo:
                with trace.span("switch.bucket", cat="switch",
                                leaves=len(todo)):
                    lays.update(zip(
                        [(id(pk), p) for pk, p in todo],
                        _bucket_program(
                            tuple(pk.entries[p] for pk, p in todo),
                            shapes=tuple(leaves[where[p]].shape
                                         for _, p in todo))))
            # the caller's leaves are copied once, never donated
            theirs = [p for p in kernel
                      if self._mine.get(p) is not leaves[where[p]]]
            if theirs:
                for p, x in zip(theirs, _copy_program(
                        tuple(leaves[where[p]] for p in theirs))):
                    leaves[where[p]] = x
            def layout(pk, p, other):
                if pk is not None and p in pk.entries:
                    return lays[(id(pk), p)]
                return _empty(lays[(id(other), p)])

            new, walked = _apply_program(
                tuple(leaves[where[p]] for p in kernel),
                tuple(layout(out, p, inc) for p in kernel),
                tuple(layout(inc, p, out) for p in kernel),
                jnp.asarray([-out.alpha if out else 0.0,
                             inc.alpha if inc else 0.0], jnp.float32),
                interpret=jax.default_backend() != "tpu")
            for p, x in zip(kernel, new):
                leaves[where[p]] = self._mine[p] = x
            for p in fallback:
                x = leaves[where[p]]
                for pk, a in signed:
                    if p in pk.entries:
                        idx, val = pk.entries[p]
                        x = M.scatter_packed_add(x, idx, val,
                                                 alpha=a).astype(x.dtype)
                leaves[where[p]] = x
            self.params = jax.tree_util.tree_unflatten(treedef, leaves)
        trace.counter("switch.kernel_leaves", len(kernel), cat="switch")
        trace.counter("switch.fallback_leaves", len(fallback), cat="switch")
        self._walked.append(walked)
        if inc is not None:
            self._kept = (inc, {p: lays[(id(inc), p)] for p in kernel
                                if p in inc.entries})
        elif kept is not None:
            self._kept = None

    def _wait(self, packs: List[AdapterPack]) -> None:
        """Wait, once, until every leaf the packs touch is ready."""
        walked, self._walked = self._walked, []
        if not self.blocking:
            return
        paths = {path for p in packs for path in p.entries}
        leaves = [x for p, x in jax.tree_util.tree_flatten_with_path(
            self.params)[0] if path_str(p) in paths]
        with trace.span("switch.wait", cat="switch",
                        leaves=len(leaves)) as sp:
            jax.block_until_ready(leaves)
            if trace.recording():
                sp.set(chunks=int(sum(int(w) for w in walked)))

    def _stats(self, name: str, t0: float,
               packs: List[AdapterPack]) -> SwitchStats:
        return SwitchStats(name, time.perf_counter() - t0,
                           sum(p.num_params() for p in packs),
                           sum(p.nbytes() for p in packs),
                           _tree_bytes(self.params))

    def _drop_to(self, keep: int) -> List[AdapterPack]:
        """Unload active packs until ``keep`` are left; the last of them
        through its kept layout. Returns the packs unloaded."""
        gone = []
        while len(self.active) > keep:
            gone.append(self.active.pop())
            self._apply(gone[-1], -1.0)
        return gone

    def load(self, pack) -> SwitchStats:
        pack = self._resolve(pack)
        t0 = time.perf_counter()
        self._apply(pack, +1.0)
        self._wait([pack])
        self.active.append(pack)
        return self._stats(pack.name, t0, [pack])

    def unload(self) -> Optional[SwitchStats]:
        if not self.active:
            return None
        t0 = time.perf_counter()
        pack = self._drop_to(len(self.active) - 1)[0]
        self._wait([pack])
        return self._stats("-" + pack.name, t0, [pack])

    def switch(self, pack) -> SwitchStats:
        """unload current -> load new; the paper's rapid-switch operation.
        The last active pack's unload and the new pack's load are one
        program (others active beneath it are unloaded first). One wait,
        at the end, on every leaf touched; the stats count the entries of
        every pack written."""
        pack = self._resolve(pack)
        t0 = time.perf_counter()
        with trace.span("switch", cat="switch", name=pack.name):
            written = self._drop_to(1)
            self._leaving = self.active.pop() if self.active else None
            try:
                self._apply(pack, +1.0)
            finally:
                out, self._leaving = self._leaving, None
            self.active.append(pack)
            written += ([out] if out is not None else []) + [pack]
            self._wait(written)
        return self._stats(pack.name, t0, written)

    def load_fused(self, packs: List,
                   weights: Optional[List[float]] = None) -> List[SwitchStats]:
        """Multi-adapter fusion by naive addition (paper Fig. 3(b))."""
        weights = weights or [1.0] * len(packs)
        out = []
        for p, w in zip(packs, weights):
            p = self._resolve(p)
            scaled = AdapterPack(p.name, p.entries, alpha=p.alpha * w)
            out.append(self.load(scaled))
        return out


class LoraEngine:
    """The fuse/unfuse pipeline the paper compares against (App. A)."""

    def __init__(self, params):
        self.params = params
        self.active = None

    def fuse(self, lora: Dict[str, dict], scale: float) -> float:
        """lora: path -> {"A","B"}; W += scale * A@B for every target."""
        t0 = time.perf_counter()

        def walk(tree, prefix):
            if isinstance(tree, dict):
                return {k: walk(v, prefix + (str(k),)) for k, v in tree.items()}
            if isinstance(tree, (list, tuple)):
                # type-preserving, like apply_pack's walk: returning a list
                # for a tuple node corrupts the pytree structure
                t = [walk(v, prefix + (str(i),)) for i, v in enumerate(tree)]
                return tuple(t) if isinstance(tree, tuple) else t
            key = "/".join(prefix)
            if key in lora:
                t = lora[key]
                delta = scale * jnp.einsum("...nr,...rm->...nm",
                                           t["A"].astype(jnp.float32),
                                           t["B"].astype(jnp.float32))
                return (tree.astype(jnp.float32) + delta).astype(tree.dtype)
            return tree

        self.params = walk(self.params, ())
        jax.block_until_ready(jax.tree.leaves(self.params)[0])
        self.active = (lora, scale)
        return time.perf_counter() - t0

    def unfuse(self) -> float:
        if self.active is None:
            return 0.0
        lora, scale = self.active
        t = self.fuse(lora, -scale)
        self.active = None
        return t


@dataclass
class FusedDecision:
    """One scheduling step: fuse ``promote`` into the shared base (after
    un-fusing ``demote``), or leave things alone (both None). Either side
    may be a single adapter name or an adapter-stack tuple."""

    promote: Optional[Tenant] = None
    demote: Optional[Tenant] = None


class FusedLRU:
    """LRU fused-state scheduler for multi-tenant serving.

    The multi-tenant engine serves every request off ONE shared copy of the
    base weights plus a per-request sparse side-delta. When traffic is
    dominated by a single adapter, it is cheaper to *fuse* that adapter into
    the shared base (one sparse scatter, paper §3.2) so its requests skip the
    side-delta entirely; the remaining tenants are then served with diff
    packs (their delta minus the fused one). This object only decides WHO is
    fused — the engine applies the scatter and rebuilds its tables.

    Policy: an exponential moving average of each tenant's share of batch
    traffic, plus a recency stamp. A tenant is promoted when its share
    crosses ``promote_at``; the fused tenant is demoted back to side-delta
    form when its share decays below ``demote_at`` or when it has been unused
    for ``max_idle`` scheduling steps (the LRU part). The fused state holds
    exactly one *tenant* at a time: fusing two distinct tenants would make
    the shared base equal to the sum of their deltas, which neither wants.
    A tenant may however be an adapter *stack* (a tuple of names served
    together, e.g. agent stacks) — ``capacity`` bounds how many adapters a
    promotable stack may contain, so ``capacity=2`` fuses a hot pair in one
    transition while singles-only traffic behaves exactly as ``capacity=1``.
    Ties in share are broken deterministically by tenant name (lexicographic
    on the "a+b" key), never by dict insertion order.
    """

    def __init__(self, promote_at: float = 0.5, demote_at: float = 0.2,
                 decay: float = 0.5, max_idle: int = 8, capacity: int = 1):
        assert 0.0 <= demote_at <= promote_at <= 1.0
        assert capacity >= 1
        self.promote_at = promote_at
        self.demote_at = demote_at
        self.decay = decay
        self.max_idle = max_idle
        self.capacity = capacity
        self.share: Dict[Tenant, float] = {}
        self.last_used: Dict[Tenant, int] = {}
        self.step = 0
        self.fused: Optional[Tenant] = None

    def observe(self, names: Sequence) -> FusedDecision:
        """Record one batch of per-request tenants (None = base model, str =
        one adapter, tuple = adapter stack) and return the promotion/demotion
        to apply before serving it."""
        self.step += 1
        n = max(len(names), 1)
        counts: Dict[Tenant, int] = {}
        for name in names:
            name = normalize_tenant(name)
            if name is not None:
                counts[name] = counts.get(name, 0) + 1
                self.last_used[name] = self.step
        seen = set(counts) | set(self.share)
        for name in seen:
            frac = counts.get(name, 0) / n
            self.share[name] = (self.decay * self.share.get(name, 0.0)
                                + (1.0 - self.decay) * frac)
        # prune decayed-out idle tenants so long-lived serving doesn't scan
        # every adapter ever seen on each batch
        for name in [n_ for n_, s in self.share.items()
                     if n_ != self.fused and s < 1e-4
                     and self.step - self.last_used.get(n_, 0) > self.max_idle]:
            del self.share[name]
            self.last_used.pop(name, None)

        decision = FusedDecision()
        if self.fused is not None:
            idle = self.step - self.last_used.get(self.fused, 0)
            if (self.share.get(self.fused, 0.0) < self.demote_at
                    or idle >= self.max_idle):
                decision.demote = self.fused
        eligible = [name for name in self.share
                    if len(tenant_members(name)) <= self.capacity]
        # min over (-share, key): highest share wins, equal shares resolve
        # to the lexicographically-first tenant (stable across runs)
        hot = min(eligible, key=lambda m: (-self.share[m], tenant_key(m)),
                  default=None)
        if (hot is not None and hot != self.fused
                and self.share[hot] >= self.promote_at):
            if self.fused is not None:
                decision.demote = self.fused
            decision.promote = hot
        if decision.promote:
            trace.instant("sched.promote", cat="switch",
                          tenant=tenant_key(decision.promote))
            self.fused = decision.promote
        elif decision.demote:
            trace.instant("sched.demote", cat="switch",
                          tenant=tenant_key(decision.demote))
            self.fused = None
        return decision


@jax.jit
def _diff_counts(xs, ys):
    return [jnp.sum(jnp.not_equal(a, b)) for a, b in zip(xs, ys)]


def changed_fraction(base, switched) -> float:
    """%C from the paper's tables: fraction of weights differing from base.

    All per-leaf comparisons run in ONE jitted computation with a single
    host sync at the end — the old per-leaf ``int(jnp.sum(...))`` did a
    blocking device round-trip per leaf, which dominated the switching
    benchmarks on deep stacks."""
    a = jax.tree.leaves(base)
    b = jax.tree.leaves(switched)
    tot = sum(x.size for x in a)
    diff = sum(int(c) for c in jax.device_get(_diff_counts(a, b)))
    return diff / max(tot, 1)
