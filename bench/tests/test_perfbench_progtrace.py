"""The readers of the program's own spans: on synthesised intervals, and
end to end on toy traced runs whose program writes the spans."""
from __future__ import annotations

import pytest

import perfbench_tiny as T
import progtrace as P
import tracereduce as TR

MS = 1e6   # ns


def _trace(devices=((0.0, 100 * MS),), **marks):
    ann = {"bench.window": [(0.0, 100 * MS)]}
    ann.update(marks)
    return TR.Trace([list(devices)] if devices else [], [], ann)


def test_host_time_leaves_out_syncs_and_prefill_chunks():
    # step 1 (0-30): sync 20-25 and a chunk 5-15 overlapping a sync 12-18
    # -> 30 - (5-18 and 20-25 = 18) = 12; step 2 (40-60): sync 50-60 -> 10;
    # a step past the window's end is not counted
    t = _trace(**{"bench.prefill_chunk": [(5 * MS, 15 * MS)]})
    ev = {"repro.step": [(0.0, 30 * MS), (40 * MS, 60 * MS),
                         (90 * MS, 110 * MS)],
          "repro.sync": [(12 * MS, 18 * MS), (20 * MS, 25 * MS),
                         (50 * MS, 60 * MS)]}
    assert P.host_ms(ev, t) == pytest.approx((12 + 10) / 2)
    assert P.host_ms({"repro.sync": ev["repro.sync"]}, t) is None


def test_dispatch_is_summed_per_switch():
    # switch 1 (0-40): unload 1-6, load 6-9, wait 9-40 -> 8;
    # switch 2 (50-80): unload 50-52, load 52-56 -> 6
    t = _trace()
    ev = {"repro.switch": [(0.0, 40 * MS), (50 * MS, 80 * MS)],
          "repro.switch.unload": [(1 * MS, 6 * MS), (50 * MS, 52 * MS)],
          "repro.switch.load": [(6 * MS, 9 * MS), (52 * MS, 56 * MS)],
          "repro.switch.wait": [(9 * MS, 40 * MS), (56 * MS, 80 * MS)]}
    assert P.dispatch_ms(ev, t) == pytest.approx((8 + 6) / 2)
    assert P.dispatch_ms({"repro.step": [(0.0, MS)]}, t) is None


def test_stall_counts_only_idle_time_inside_gc_and_compiles():
    # device busy 0-20 and 40-100; gc 10-30 (idle 20-30: 10), a compile
    # 25-35 overlapping it (idle 30-35 more: 5), jax's compile mark 90-120
    # (busy, and past the window); a sync in the idle gap is no stall
    t = _trace(devices=((0.0, 20 * MS), (40 * MS, 100 * MS)))
    ev = {"repro.gc": [(10 * MS, 30 * MS)],
          "repro.compile": [(25 * MS, 35 * MS)],
          "backend_compile_and_load": [(90 * MS, 120 * MS)],
          "repro.sync": [(20 * MS, 40 * MS)]}
    assert P.stall_ms(ev, t) == pytest.approx(15.0)
    assert P.stall_ms({"repro.sync": ev["repro.sync"]}, t) == 0.0


def test_events_reads_only_the_file_of_the_trace(tmp_path):
    import jax
    from repro.analysis import trace
    assert P.events(_trace(), root=tmp_path) is None      # no file
    jax.profiler.start_trace(str(tmp_path / "a"))
    with jax.profiler.TraceAnnotation("bench.window"):
        with trace.span("step"):
            with trace.span("sync"):
                pass
    jax.profiler.stop_trace()
    t = TR.load(str(tmp_path))
    ev = P.events(t, root=tmp_path)
    assert set(ev) >= {"repro.step", "repro.sync", "bench.window"}
    (s, e), = ev["repro.step"]
    assert t.window[0] <= s <= ev["repro.sync"][0][0] <= e <= t.window[1]
    assert P.events(_trace(), root=tmp_path) is None       # another window


def test_a_program_without_spans_reads_nothing(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        pass
    jax.profiler.stop_trace()
    assert P.events(TR.load(str(tmp_path)), root=tmp_path) is None


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return T.make(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell,names", [
    ("sc2-4l.mt-zipf", ["engine.host_ms", "host.stall_ms"]),
    ("dsc33-2l.switch-groups", ["switch.dispatch_ms", "host.stall_ms"]),
])
def test_traced_toy_run_reports_the_span_metrics(tiny, cell, names,
                                                 monkeypatch, capsys):
    monkeypatch.setattr(P, "ROOT", tiny.repo / ".bench_out" / "trace")
    rc, out = T.run_cell(tiny, cell, 2**31 + 7, monkeypatch, capsys, trace=1)
    assert rc == 0 and out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(names) <= set(m)
    tr = TR.load(str(tiny.repo / ".bench_out" / "trace" / cell))
    idle_ms = (tr.window_s - tr.busy_s()) * 1e3
    assert 0.0 <= m["host.stall_ms"] <= idle_ms + 1e-6
    step = "bench.engine_step" if "engine.host_ms" in m else "bench.switch"
    walls = [e - s for s, e in tr.spans(step)]
    mean_wall_ms = sum(walls) / len(walls) / 1e6
    for name in ("engine.host_ms", "switch.dispatch_ms"):
        if name in m:
            assert 0.0 < m[name] < mean_wall_ms
