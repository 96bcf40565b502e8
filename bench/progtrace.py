"""The program's own spans, read from the profiler trace of a traced run.

While a profiler session records, the program opens a
``jax.profiler.TraceAnnotation`` named ``repro.<span>`` for each of its
spans: ``repro.step``, ``repro.decode``, ``repro.sync`` (the host waiting
on the device), ``repro.switch`` with ``repro.switch.unload``,
``repro.switch.load`` and ``repro.switch.wait`` inside it, ``repro.gc``
(a Python garbage collection), ``repro.jaxpr`` (JAX tracing a function)
and ``repro.compile`` (lowering, or an XLA compile or persistent-cache
load: a program not built yet). JAX marks an XLA compile itself too
(``backend_compile_and_load``). All of them sit on the host plane of the
``.xplane.pb``, on the clock of the ``bench.*`` marks, onto which
``tracereduce`` moves the device's time; so the readers here combine
them with ``Trace.window``, ``Trace.annotations`` and ``Trace.busy_ns``.

A reader's ``ctx`` carries no path, so ``events`` reads the newest
``*.xplane.pb`` under ``<checkout>/.bench_out/trace/``: ``run.py``
clears a cell's directory as its window opens, so that is the file
``tracereduce.load`` just read. A file whose ``bench.window`` is not the
trace's window is not read. A program that opens no ``repro.*`` span
(one older than these spans) reads ``None`` in every reader.
"""
from __future__ import annotations

import bisect
import glob
import os
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from tracereduce import WINDOW, Interval, _merge

ROOT = Path(__file__).resolve().parent.parent / ".bench_out" / "trace"
PREFIX = "repro."
JAX_COMPILE = ("backend_compile_and_load", "backend_compile")
# host work that is not the serving loop's own, during which the device
# may starve
STALLS = ("repro.gc", "repro.compile") + JAX_COMPILE

Events = Dict[str, List[Interval]]
_read: Dict[Tuple[str, float], Events] = {}


def read_file(path: str) -> Events:
    """Name -> sorted (start_ns, end_ns) of the host events read here:
    ``repro.*``, JAX's compile marks and ``bench.window``."""
    from jax.profiler import ProfileData
    out: Events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if (e.name.startswith(PREFIX) or e.name in JAX_COMPILE
                        or e.name == WINDOW):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    for ivs in out.values():
        ivs.sort()
    return out


def events(trace, root: Optional[Path] = None) -> Optional[Events]:
    """The host events of the file ``trace`` was reduced from (cached per
    file); None where there is no such file or the program in it opened
    no ``repro.*`` span."""
    files = glob.glob(os.path.join(str(root or ROOT), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _read:
        _read[key] = read_file(path)
    ev = _read[key]
    w = ev.get(WINDOW)
    if not w or (w[0][0], w[-1][1]) != tuple(trace.window):
        return None
    if not any(name.startswith(PREFIX) for name in ev):
        return None
    return ev


def _within(ivs: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(s, e) for s, e in ivs if s >= lo and e <= hi]


class _Union:
    """Merged intervals, for the length of their union inside a span."""

    def __init__(self, ivs: List[Interval]):
        self.ivs = _merge(ivs)
        self.starts = [s for s, _ in self.ivs]

    def covered(self, lo: float, hi: float) -> float:
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        tot = 0.0
        while i < len(self.ivs) and self.ivs[i][0] < hi:
            s, e = self.ivs[i]
            tot += max(0.0, min(e, hi) - max(s, lo))
            i += 1
        return tot


def host_ms(ev: Events, trace) -> Optional[float]:
    """Mean over the window's ``repro.step`` spans of the step's length
    less the union of its ``repro.sync`` spans and the
    ``bench.prefill_chunk`` marks inside it (traced runs wait for each
    chunk inside that mark): the engine's own host time per step, in ms."""
    steps = _within(ev.get("repro.step", []), *trace.window)
    if not steps:
        return None
    waits = _Union(ev.get("repro.sync", [])
                   + trace.annotations.get("bench.prefill_chunk", []))
    own = sum((e - s) - waits.covered(s, e) for s, e in steps)
    return own / len(steps) / 1e6


def dispatch_ms(ev: Events, trace) -> Optional[float]:
    """Mean over the window's ``repro.switch`` spans of the summed
    ``repro.switch.unload`` and ``repro.switch.load`` spans inside each:
    the host's walk and dispatch of a switch's scatters, in ms."""
    switches = _within(ev.get("repro.switch", []), *trace.window)
    if not switches:
        return None
    parts = ev.get("repro.switch.unload", []) + ev.get("repro.switch.load",
                                                       [])
    tot = sum(e - s for lo, hi in switches for s, e in _within(parts, lo, hi))
    return tot / len(switches) / 1e6


def stall_ms(ev: Events, trace) -> float:
    """Device-idle time inside the window that lies inside a garbage
    collection or a compile, in ms: over the union of those spans, each
    clipped to the window, its length less the device's busy time in it.
    0.0 where the window holds none."""
    lo, hi = trace.window
    spans = [(max(s, lo), min(e, hi)) for name in STALLS
             for s, e in ev.get(name, []) if e > lo and s < hi]
    return sum((e - s) - trace.busy_ns(s, e) for s, e in _merge(spans)) / 1e6
