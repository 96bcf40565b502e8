"""bench/run.py end to end on the CPU at toy size: every mix, both kinds
of run, and the refusals (no accelerator, no program)."""
from __future__ import annotations

import json

import pytest

import perfbench_tiny as T
import run

CELLS = [w["name"] for w in
         json.loads((T.REPO / "BENCHMARK.json").read_text())["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return T.make(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_prints_the_result_line(tiny, cell, trace, monkeypatch,
                                       capsys):
    rc, out = T.run_cell(tiny, cell, 2**31 + 99, monkeypatch, capsys,
                         trace=trace)
    assert rc == 0
    assert list(out)[:5] == KEYS and list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["kind"] == "TPU v5 lite"
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name
    if trace:
        assert out["device"]["window_s"] > 0
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        per_layer = {m["name"] for m in tiny.per_layer(cell)}
        assert set(out["metrics"]) <= per_layer
    else:
        want = {m["name"] for m in tiny.end_to_end(cell)}
        assert set(out["metrics"]) == want
        assert all(m["value"] > 0 for m in out["metrics"].values())


def test_same_seed_serves_the_same_tokens(tiny, monkeypatch, capsys):
    """Weights, adapters and traffic come from the seed alone."""
    from repro.core import switching
    seen = []
    orig = switching.SwitchEngine.switch

    def spy(self, pack):
        seen.append(pack.name)
        return orig(self, pack)

    monkeypatch.setattr(switching.SwitchEngine, "switch", spy)
    outs = []
    for _ in range(2):
        seen.clear()
        T.run_cell(tiny, "dsc33-2l.switch-groups", 5, monkeypatch, capsys,
                   seconds=1.5)
        outs.append(list(seen))
    # two warm-up switches, then the window's: as many as its time allows
    k = min(map(len, outs))
    assert k >= 5 and outs[0][:k] == outs[1][:k]


def test_a_request_submitted_in_the_window_is_compared(tiny, monkeypatch,
                                                      capsys):
    """mt-zipf compares, besides finished requests, the request submitted
    inside the window that served the most tokens, finished or not. As in
    the cell, outputs outlast the window: the serving loop's clock moves 1 ms
    per reading, so the window holds 25 steps, in which the shorter first
    request (19 tokens) finishes and the next one (40-48) cannot."""
    import time

    import harness
    import traffic

    class StepClock:
        t = time.perf_counter()

        def perf_counter(self):
            self.t += 1e-3
            return self.t

    monkeypatch.setattr(tiny.driver("paged_closed_loop"), "time", StepClock())
    mix = dict(tiny.mix("mt-zipf"), check_tokens=4,
               check_window_tokens=2,
               output=dict(dist="uniform", min=40, max=48))
    monkeypatch.setattr(tiny, "mix", lambda name: mix)
    in_window, compared = [False], []
    made_in_window = []
    window, request = harness.Run.window, traffic.Traffic.request
    gaps = harness.logit_gaps

    def spy_window(self):
        cm = window(self)

        class Flag:
            def __enter__(_):
                cm.__enter__()
                in_window[0] = True

            def __exit__(_, *exc):
                in_window[0] = False
                return cm.__exit__(*exc)
        return Flag()

    def spy_request(self):
        req = request(self)
        if in_window[0]:
            made_in_window.append(req.prompt)
        return req

    def spy_gaps(cfg, mix, seed, served, control=False):
        compared.extend(served)
        return gaps(cfg, mix, seed, served, control)

    monkeypatch.setattr(harness.Run, "window", spy_window)
    monkeypatch.setattr(traffic.Traffic, "request", spy_request)
    monkeypatch.setattr(harness, "logit_gaps", spy_gaps)
    rc, out = T.run_cell(tiny, "sc2-4l.mt-zipf", 2**31 + 7, monkeypatch,
                         capsys, seconds=0.05)
    assert rc == 0 and out["correct"] is True
    assert made_in_window
    late = [s for s in compared
            if any(s.prompt is p for p in made_in_window)]
    assert late and all(len(s.tokens) > 0 for s in late)


def test_a_request_that_served_nothing_is_not_correct():
    """Nothing came for a compared request: the gap is NaN and fails."""
    import harness
    import numpy as np
    empty = harness.Served(np.arange(4, dtype=np.int32),
                           np.zeros(0, np.int32), 0)
    gaps = harness.logit_gaps({}, {"output": {"max": 4}}, 1, [empty])
    assert np.isnan(gaps["logit_gap"])
    assert harness.judge({"logit_gap": gaps["logit_gap"]},
                         {"logit_gap": {"limit": 1.0}}) is False


def test_no_accelerator_means_no_result(tiny, capsys):
    """On the CPU the real device check refuses: nonzero, no line."""
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
                  spec=tiny)
    assert rc == 3
    assert capsys.readouterr().out == ""


def test_benchmark_alone_refuses(tmp_path, capsys):
    """A checkout holding only BENCHMARK.json and bench/ runs nothing."""
    (tmp_path / "BENCHMARK.json").write_text(
        (T.REPO / "BENCHMARK.json").read_text())
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
                  spec=T.S.Spec(tmp_path, T.BENCH))
    assert rc == 2
    assert capsys.readouterr().out == ""
