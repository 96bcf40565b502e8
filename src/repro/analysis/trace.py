"""Low-overhead host-side serving tracer: spans, instants, counters.

The serving engines are instrumented with ``trace.span(...)`` /
``trace.instant(...)`` / ``trace.counter(...)`` calls at every phase the
replay cost model (``analysis/replay.py``) attributes time to: engine
steps, admission, prefill chunks, decode passes, COW page copies, fuse /
demote scatters, device-table rebuilds (H2D uploads) and adapter disk
loads. Tracing is OFF by default and the hooks are then near-free: each
call is one module-global load plus a singleton return — no allocation,
no branching in the recorded path (``tests/test_observability.py`` pins
the per-call cost at far below 1% of a decode step).

Enable tracing by installing a tracer::

    from repro.analysis import trace
    tr = trace.install()            # or trace.install(Tracer(capacity=...))
    ... serve ...
    trace.uninstall()
    tr.to_jsonl("run.trace.jsonl")          # one event per line

The profiler is a second sink. While a ``jax.profiler`` session is
recording, every span is also a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` with the span's args as metadata, whether or not a
tracer is installed: the spans then sit on the host plane of the
``.xplane.pb``, on the same clock as the device's executions and the
benchmark's ``bench.*`` marks, and Perfetto shows them beside the device
rows. With neither a tracer nor a session the hook is one cheap check
and the null return. Three spans exist on the profiler side only, since
the serving loop does not open them itself: ``repro.gc`` (a Python
garbage collection, arg ``generation``, from a ``gc.callbacks`` hook),
``repro.jaxpr`` (JAX tracing a function to a jaxpr, arg ``fun``) and
``repro.compile`` (arg ``stage``: ``lower``, or ``backend`` for an XLA
compile or a load from the persistent compile cache, which JAX does not
annotate). The last two come from ``jax.monitoring``, which marks each
stage's start with a scalar event and its end with a duration event.

Event model (serving loop on one host thread, plus prefetch workers):

  * ``span(name, cat=..., **args)`` — a context manager timing a phase.
    Recorded on exit as ``{"ph": "X", "name", "cat", "ts", "dur",
    "depth", "tid", "args"}`` with ``ts``/``dur`` in microseconds
    relative to the tracer's epoch. ``depth`` is the nesting level at
    entry (0 = top level) *within the recording thread*; the object
    returned by ``__enter__`` supports ``.set(**kw)`` to attach args
    discovered mid-span.
  * ``instant(name, **args)`` — a zero-duration marker (``"ph": "i"``).
  * ``counter(name, value)`` — a sampled gauge (``"ph": "C"``), e.g.
    page-pool pressure per step.

Threads: the serving loop stays single-threaded, but the async adapter
prefetch pipeline (``AdapterStore.prefetch`` disk loads, background
device-table builds) records spans from worker threads. Every event
carries a small ``tid``: 0 is the thread that called ``install()`` (the
serving loop), workers get 1, 2, ... in first-seen order. Span nesting
(``depth``, and the replay model's interval stacks) is tracked per
thread, so a worker's ``prefetch.disk`` span overlapping the main
thread's ``decode`` never corrupts either thread's tree — that overlap
is exactly what ``replay.verify_overlap`` measures.

The buffer is a bounded ring: when ``capacity`` events have been
recorded the oldest are dropped (``tracer.dropped`` counts them), so a
long-lived serving loop can stay instrumented without unbounded host
memory.

Fault / degradation events (``runtime/faults.py``; full failure model in
``src/repro/runtime/README.md``): the injector marks every injected
event as a ``fault.*`` instant (cat ``fault``: ``fault.disk_fail``,
``fault.corrupt``, ``fault.io_latency``, ``fault.worker_death``,
``fault.build_fail``, ``fault.poison``, ``fault.preempt``), and the
degradation ladder emits its decisions as instants too — ``store.retry``
/ ``store.quarantine`` (cat ``store``), ``shed.deadline`` /
``shed.queue_full`` / ``degrade`` / ``slot.poison`` (cat ``serving``),
``fault.build_backoff`` (cat ``tables``) — so a chaos-bench trace shows
both what was injected and how serving absorbed it.
"""
from __future__ import annotations

import gc
import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax

__all__ = ["Tracer", "install", "uninstall", "active", "enabled",
           "recording", "span", "instant", "counter"]


class _NullSpan:
    """Singleton returned by ``span()`` when tracing is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw):
        return self


_NULL = _NullSpan()
_tracer: Optional["Tracer"] = None
# True while a profiler session records (a check in native code, tens of
# nanoseconds); a TraceAnnotation opened with none recording still costs
# about a microsecond
_recording = jax.profiler.TraceAnnotation.is_enabled


def _annotation(name: str, args: dict):
    return jax.profiler.TraceAnnotation("repro." + name, **args)


class _ProfilerSpan:
    """A span on the profiler's clock only: no tracer is installed."""
    __slots__ = ("_ann",)

    def __init__(self, name: str, args: dict):
        self._ann = _annotation(name, args)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False

    def set(self, **kw):
        self._ann.set_metadata(**kw)
        return self


class _Span:
    __slots__ = ("_tr", "name", "cat", "args", "_t0", "_depth", "_tid",
                 "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str, args: dict):
        self._tr = tr
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = None

    def set(self, **kw):
        """Attach args discovered while the span is open."""
        self.args.update(kw)
        if self._ann is not None:
            self._ann.set_metadata(**kw)
        return self

    def __enter__(self):
        tr = self._tr
        self._tid = tr._tid()
        self._depth = tr._enter_depth()
        if _recording():
            self._ann = _annotation(self.name, self.args)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self._tr
        tr._exit_depth()
        tr._push({"ph": "X", "name": self.name, "cat": self.cat,
                  "ts": (self._t0 - tr.epoch) * 1e6,
                  "dur": (t1 - self._t0) * 1e6,
                  "depth": self._depth, "tid": self._tid,
                  "args": self.args})
        return False


class Tracer:
    """Bounded in-memory event ring with JSONL export."""

    def __init__(self, capacity: int = 1 << 16):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.epoch = time.perf_counter()
        self._buf: "deque[Dict[str, Any]]" = deque()
        self.dropped = 0
        # per-thread small tids (0 = the installing/serving thread; workers
        # get 1, 2, ... first-seen) and per-thread nesting depth — prefetch
        # workers record concurrently with the serving loop
        self._lock = threading.Lock()
        self._tids: Dict[int, int] = {threading.get_ident(): 0}
        self._depths: Dict[int, int] = {}

    # -- recording -----------------------------------------------------

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _enter_depth(self) -> int:
        ident = threading.get_ident()
        d = self._depths.get(ident, 0)
        self._depths[ident] = d + 1
        return d

    def _exit_depth(self) -> None:
        ident = threading.get_ident()
        self._depths[ident] = max(self._depths.get(ident, 1) - 1, 0)

    def _push(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._buf) >= self.capacity:
                self._buf.popleft()
                self.dropped += 1
            self._buf.append(ev)

    def span(self, name: str, cat: str = "serving",
             args: Optional[dict] = None) -> _Span:
        return _Span(self, name, cat, dict(args or {}))

    def instant(self, name: str, cat: str = "serving",
                args: Optional[dict] = None) -> None:
        self._push({"ph": "i", "name": name, "cat": cat,
                    "ts": (time.perf_counter() - self.epoch) * 1e6,
                    "dur": 0.0,
                    "depth": self._depths.get(threading.get_ident(), 0),
                    "tid": self._tid(), "args": dict(args or {})})

    def counter(self, name: str, value: float,
                cat: str = "serving") -> None:
        self._push({"ph": "C", "name": name, "cat": cat,
                    "ts": (time.perf_counter() - self.epoch) * 1e6,
                    "dur": 0.0,
                    "depth": self._depths.get(threading.get_ident(), 0),
                    "tid": self._tid(), "args": {"value": float(value)}})

    # -- access / export ----------------------------------------------

    def __len__(self) -> int:
        return len(self._buf)

    def events(self) -> List[Dict[str, Any]]:
        """All buffered events in timestamp order."""
        return sorted(self._buf, key=lambda e: e["ts"])

    def clear(self) -> None:
        self._buf.clear()
        self.dropped = 0
        self._depths.clear()

    def to_jsonl(self, path: str) -> str:
        """One event object per line — the replay cost model's input."""
        with open(path, "w") as f:
            for ev in self.events():
                f.write(json.dumps(ev, sort_keys=True))
                f.write("\n")
        return path


# ---------------------------------------------------------------------------
# Module-level switchboard (what the instrumentation hooks call).
# ---------------------------------------------------------------------------

def install(tracer: Optional[Tracer] = None) -> Tracer:
    """Install (and return) the active tracer. Hooks record into it until
    ``uninstall()``."""
    global _tracer
    _tracer = tracer if tracer is not None else Tracer()
    return _tracer


def uninstall() -> Optional[Tracer]:
    """Disable tracing; returns the tracer that was active (if any)."""
    global _tracer
    tr, _tracer = _tracer, None
    return tr


def active() -> Optional[Tracer]:
    return _tracer


def enabled() -> bool:
    return _tracer is not None


def recording() -> bool:
    """True while a tracer is installed or a profiler session records:
    spans then keep their args, so a caller may pay to fill them."""
    return _tracer is not None or _recording()


def span(name: str, /, cat: str = "serving", **args):
    """Time a phase. No-op (returns a shared null context) when no tracer
    is installed and no profiler session records — safe to leave in hot
    serving loops. ``name``/``cat`` are positional-only so span args may
    themselves be called ``name``."""
    t = _tracer
    if t is None:
        if not _recording():
            return _NULL
        return _ProfilerSpan(name, args)
    return t.span(name, cat, args)


def instant(name: str, /, cat: str = "serving", **args) -> None:
    t = _tracer
    if t is not None:
        t.instant(name, cat, args)


def counter(name: str, value: float, cat: str = "serving") -> None:
    t = _tracer
    if t is not None:
        t.counter(name, value, cat)


# ---------------------------------------------------------------------------
# Spans the serving thread does not open: garbage collection and compiles.
# Profiler only — the ring buffer's reader (``replay``) models the serving
# loop's own phases, and would count a collection on a prefetch worker as
# prefetch work.
# ---------------------------------------------------------------------------

_gc_open = None         # collections neither nest nor overlap


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        if _recording():
            _gc_open = _annotation("gc", {"generation": info["generation"]})
            _gc_open.__enter__()
    elif _gc_open is not None:
        ann, _gc_open = _gc_open, None
        ann.__exit__(None, None, None)


# jax.monitoring event -> (span, stage). A jaxpr trace runs on every jit
# cache miss of the Python path, also where the program is then found
# built; lowering and the backend step (an XLA compile, or a load from
# the persistent cache) run only for a program not built yet
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": ("jaxpr", None),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("compile", "lower"),
    "/jax/core/compile/backend_compile_duration": ("compile", "backend")}
_compiles = threading.local()      # per thread: open (event, annotation)


def _on_compile_start(event: str, value, **kw) -> None:
    span_stage = _COMPILE_STAGES.get(event)
    if span_stage is None or not _recording():
        return
    name, stage = span_stage
    args = {"fun": kw.get("fun_name", "")}
    if stage is not None:
        args["stage"] = stage
    ann = _annotation(name, args)
    ann.__enter__()
    stack = getattr(_compiles, "stack", None)
    if stack is None:
        stack = _compiles.stack = []
    stack.append((event, ann))


def _on_compile_end(event: str, duration: float, **kw) -> None:
    stack = getattr(_compiles, "stack", None)
    if stack and stack[-1][0] == event:
        stack.pop()[1].__exit__(None, None, None)


gc.callbacks.append(_on_gc)
jax.monitoring.register_scalar_listener(_on_compile_start)
jax.monitoring.register_event_duration_secs_listener(_on_compile_end)
