"""The trace reduction on synthesised traces, and on one the CPU records."""
from __future__ import annotations

import pytest

import perfbench_tiny  # noqa: F401  (puts bench/ on the path)
import tracereduce as TR

MS = 1e6   # ns


def _trace():
    # window 0..100 ms; device busy 10-30 (two overlapping programs),
    # 50-60 and 90-110 (runs past the window's end)
    dev = [(10 * MS, 25 * MS), (20 * MS, 30 * MS), (50 * MS, 60 * MS),
           (90 * MS, 110 * MS)]
    ops = [TR.Op(10 * MS, 30 * MS, "%while.2 = (f32[]) while(...)", "jit_a(1)"),
           TR.Op(12 * MS, 20 * MS,
                 "%sidedelta.3 = f32[8,1,512]{2,1,0} custom-call(%a), "
                 'custom_call_target="tpu_custom_call"', "jit_a(1)"),
           TR.Op(20 * MS, 24 * MS, "%sidedelta.4 = f32[8] custom-call(%b)",
                 "jit_a(1)"),
           TR.Op(50 * MS, 60 * MS, "%fusion.12 = bf16[8] fusion(%c)",
                 "jit_b(2)")]
    ann = {"bench.window": [(0.0, 100 * MS)],
           "bench.step": [(5 * MS, 35 * MS), (45 * MS, 70 * MS)],
           "bench.chunk": [(8 * MS, 22 * MS)]}
    return TR.Trace([dev], ops, ann)


def test_busy_is_the_union_inside_the_window():
    t = _trace()
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s() == pytest.approx((20 + 10 + 10) / 1e3)
    assert t.idle_share() == pytest.approx(0.6)


def test_busy_inside_each_annotation():
    t = _trace()
    assert t.busy_in("bench.step") == pytest.approx([20 * MS, 10 * MS])
    assert t.busy_in("bench.chunk") == pytest.approx([12 * MS])
    assert t.busy_in("bench.absent") == []


def test_kernel_time_sums_its_events():
    t = _trace()
    ns, n = t.op_ns(lambda o: TR.op_short(o.name) == "sidedelta"
                    and "custom-call" in o.name)
    assert (ns, n) == (pytest.approx(12 * MS), 2)


def test_breakdown_skips_loops_and_names_gaps():
    t = _trace()
    top = t.top_ops()
    assert top[0] == ["jit_a/sidedelta", pytest.approx(0.012)]
    assert all("while" not in name for name, _ in top)
    # gaps 60-90 and 30-50 fall outside any step; 0-10 has its middle
    # inside the first step
    assert t.idle_gaps() == [["bench.window", pytest.approx(0.030)],
                             ["bench.window", pytest.approx(0.020)],
                             ["bench.step", pytest.approx(0.010)]]


def test_no_device_reads_nothing():
    t = TR.Trace([], [], {"bench.window": [(0.0, 1e9)]})
    assert t.busy_in("bench.window") == []
    assert t.idle_gaps() == []


def test_clock_offset_ignores_one_mismatched_completion():
    """Executions whose completions come 1.3-1.6 ms after their end, and
    one matched with a completion 52 ms before it: the offset is the
    least of the sound lags, and device time lands inside host spans."""
    lags = [1.3 * MS, 1.4 * MS, 1.6 * MS, 1.5 * MS, -52 * MS]
    mods = [(i * 10 * MS, i * 10 * MS + 5 * MS, "jit_f(1)", str(i))
            for i in range(len(lags))]
    done = {rid: end + lag for (_, end, _, rid), lag in zip(mods, lags)}
    ann = {"bench.window": [(0.0, 60 * MS)],
           "bench.step": [(s + 1.3 * MS, e + 1.3 * MS)
                          for s, e, _, _ in mods]}
    t = TR.reduce({"/device:TPU:0": mods}, {}, ann, done)
    assert t.offset_ns == pytest.approx(1.3 * MS)
    assert t.busy_in("bench.step") == pytest.approx([5 * MS] * len(mods))
    assert TR.clock_offset([]) == 0.0
    assert TR.clock_offset([2 * MS, -40 * MS]) == pytest.approx(2 * MS)


def test_load_reads_annotations_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = TR.load(str(tmp_path))
    assert len(t.annotations["bench.step"]) == 3
    assert t.window_s > 0
    lo, hi = t.window
    assert all(lo <= s <= e <= hi for s, e in t.spans("bench.step"))
