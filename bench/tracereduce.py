"""Reduce a profiler trace to the intervals the metric readers need.

A traced run records its window with ``jax.profiler`` and wraps its steps
in ``bench.*`` annotations (``jax.profiler.TraceAnnotation``). The trace
file (``*.xplane.pb``) holds, for each device, the line "XLA Modules" (one
event per program execution) and "XLA Ops" (one event per operation), and
on the host plane the annotations and the runtime's "CompleteCallbacks"
events, which carry the ``run_id`` of the execution they complete.

Device and host timestamps share a base but not a clock: on a TPU v5 lite
the device's lag the host's by about two milliseconds. The reduction
measures that offset from the run_ids seen on both sides (for each
execution, the lag from its end to the earliest completion of its run_id)
and moves device times onto the host's clock, so that a device interval
can be placed inside a host annotation. The offset is the least lag
within ``OFFSET_TOLERANCE_NS`` of the median lag: a completion matched
with the wrong execution reads tens of milliseconds off, and may not set
it.

Busy time is the union of program executions; idle share is one minus
busy over the window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns), host clock

WINDOW = "bench.window"
# ops whose events span other ops' events (a loop and its body)
CONTROL_FLOW = ("while", "conditional", "call")
OFFSET_TOLERANCE_NS = 1e6


@dataclass
class Op:
    start: float
    end: float
    name: str             # HLO text as the trace names it
    module: str           # the program it ran in


def _merge(iv: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def op_short(text: str) -> str:
    """'%sidedelta.3 = f32[...] custom-call(...)' -> 'sidedelta'."""
    head = text.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"(\.\d+)+$", "", head)


def module_short(name: str) -> str:
    """'jit__dec(1234)' -> 'jit__dec'."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclass
class Trace:
    """Device executions and ops on the host clock, and host annotations."""
    devices: List[List[Interval]]               # per device, merged busy
    ops: List[Op]
    annotations: Dict[str, List[Interval]]
    offset_ns: float = 0.0
    _starts: List[List[float]] = field(default_factory=list, repr=False)

    def __post_init__(self):
        self.devices = [_merge(d) for d in self.devices if d]
        self._starts = [[s for s, _ in d] for d in self.devices]
        self.ops.sort(key=lambda o: o.start)

    # -- windows -------------------------------------------------------
    @property
    def window(self) -> Interval:
        w = self.annotations.get(WINDOW)
        if not w:
            raise ValueError(f"trace has no {WINDOW!r} annotation")
        return w[0][0], w[-1][1]

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    # -- busy time -----------------------------------------------------
    def busy_ns(self, lo: float, hi: float) -> float:
        """Device busy time inside [lo, hi], averaged over devices."""
        if not self.devices or hi <= lo:
            return 0.0
        tot = 0.0
        for d, starts in zip(self.devices, self._starts):
            i = max(bisect.bisect_right(starts, lo) - 1, 0)
            while i < len(d) and d[i][0] < hi:
                s, e = d[i]
                tot += max(0.0, min(e, hi) - max(s, lo))
                i += 1
        return tot / len(self.devices)

    def busy_s(self) -> float:
        return self.busy_ns(*self.window) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def spans(self, name: str) -> List[Interval]:
        """Instances of one annotation inside the window."""
        lo, hi = self.window
        return [(s, e) for s, e in self.annotations.get(name, [])
                if s >= lo and e <= hi]

    def busy_in(self, name: str) -> List[float]:
        """Device busy ns inside each instance of an annotation (none
        where the trace holds no device)."""
        if not self.devices:
            return []
        return [self.busy_ns(s, e) for s, e in self.spans(name)]

    # -- ops -----------------------------------------------------------
    def op_ns(self, pred: Callable[[Op], bool]) -> Tuple[float, int]:
        """(summed device ns, count) of the window's ops matching pred."""
        lo, hi = self.window
        tot, n = 0.0, 0
        for o in self.ops:
            if o.start >= lo and o.end <= hi and pred(o):
                tot += o.end - o.start
                n += 1
        return tot, n

    def top_ops(self, k: int = 10) -> List[list]:
        lo, hi = self.window
        by: Dict[str, float] = {}
        for o in self.ops:
            short = op_short(o.name)
            if o.start >= lo and o.end <= hi and short not in CONTROL_FLOW:
                key = f"{module_short(o.module)}/{short}"
                by[key] = by.get(key, 0.0) + (o.end - o.start)
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The longest gaps with no device busy inside the window, each
        named by the innermost ``bench.*`` annotation the host was in at
        the gap's middle."""
        lo, hi = self.window
        if not self.devices:
            return []
        busy = [(max(s, lo), min(e, hi)) for s, e in self.devices[0]
                if e > lo and s < hi]
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            out.append([self.host_state((s + e) / 2), (e - s) / 1e9])
        return out

    def host_state(self, t: float) -> str:
        best, width = "host", float("inf")
        for name, ivs in self.annotations.items():
            for s, e in ivs:
                if s <= t <= e and e - s < width:
                    best, width = name, e - s
        return best


def clock_offset(lags: Sequence[float]) -> float:
    """The device clock's offset from the host's, from each execution's
    lag (ns) to its completion: the least lag that lies no more than
    ``OFFSET_TOLERANCE_NS`` below the median lag."""
    if not lags:
        return 0.0
    lags = sorted(lags)
    med = lags[len(lags) // 2]
    return min(x for x in lags if x >= med - OFFSET_TOLERANCE_NS)


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(trace_dir: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    modules: Dict[str, List[Tuple[float, float, str, Optional[str]]]] = {}
    ops: Dict[str, List[Tuple[float, float, str]]] = {}
    annotations: Dict[str, List[Interval]] = {}
    completions: Dict[str, float] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name,
                         _stat(e, "run_id")) for e in line.events]
                elif line.name == "XLA Ops":
                    ops[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns,
                                        e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        annotations.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name == "CompleteCallbacks":
                        rid = _stat(e, "run_id")
                        if rid is not None:
                            t = completions.get(str(rid))
                            completions[str(rid)] = (e.start_ns if t is None
                                                     else min(t, e.start_ns))
    return reduce(modules, ops, annotations, completions)


def reduce(modules: Dict[str, List[Tuple[float, float, str, Optional[str]]]],
           ops: Dict[str, List[Tuple[float, float, str]]],
           annotations: Dict[str, List[Interval]],
           completions: Dict[str, float]) -> Trace:
    """A ``Trace`` from what ``load`` reads: per device plane, program
    executions (start, end, name, run_id) and ops (start, end, HLO text)
    on the device's clock; host annotations; and the earliest host
    completion of each run_id."""
    lags = [completions[str(rid)] - end
            for mods in modules.values() for _, end, _, rid in mods
            if rid is not None and str(rid) in completions]
    offset = clock_offset(lags)
    devices, all_ops = [], []
    for plane, mods in sorted(modules.items()):
        devices.append([(s + offset, e + offset) for s, e, _, _ in mods])
        spans = sorted((s + offset, e + offset, n) for s, e, n, _ in mods)
        starts = [s for s, _, _ in spans]
        for s, e, text in ops.get(plane, []):
            i = bisect.bisect_right(starts, s + offset) - 1
            mod = spans[i][2] if i >= 0 else ""
            all_ops.append(Op(s + offset, e + offset, text, mod))
    for ivs in annotations.values():
        ivs.sort()
    return Trace(devices, all_ops, annotations, offset)
