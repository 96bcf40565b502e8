"""A broken timed path must come out as not correct.

Each case drives a whole toy run with one fault planted in the program
underneath the harness: a token altered where the model produces it, a
token altered only for requests that reuse a slot, and a switch that
leaves the deployed weights as they were. A control run (the reference
one precision below the program's, in the program's place) must come out
not correct through the same limits, while the program's own numbers
pass.
"""
from __future__ import annotations

import pytest

import perfbench_tiny as T


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return T.make(tmp_path_factory.mktemp("tiny"))


def _shift_decode_tokens(monkeypatch):
    """The engine's greedy pick is one token off."""
    from repro.hub import serving
    orig = serving._EngineCommon._next_tokens

    def off(self, logits, live):
        nxt, bad = orig(self, logits, live)
        return (nxt + 1) % logits.shape[-1], bad

    monkeypatch.setattr(serving._EngineCommon, "_next_tokens", off)


def _shift_recycled_slot_tokens(monkeypatch):
    """Requests after the first wave, which land on freed slots and
    pages, record one token off."""
    from repro.hub import serving
    orig = serving._EngineCommon._emit

    def off(self, slot, token):
        late = self._active[slot].fut.rid >= self.slots
        return orig(self, slot, (token + 1) % self.cfg.vocab_size if late
                    else token)

    monkeypatch.setattr(serving._EngineCommon, "_emit", off)


def _spike_decode_logits(monkeypatch):
    """The model's decode step puts one wrong token first."""
    from repro.models import lm
    orig = lm.decode_step

    def spiked(*a, **k):
        logits, caches = orig(*a, **k)
        return logits.at[:, 7].set(1e4), caches

    monkeypatch.setattr(lm, "decode_step", spiked)


def _switch_does_nothing(monkeypatch):
    """A switch that returns with the weights unchanged."""
    from repro.core import switching
    monkeypatch.setattr(switching.SwitchEngine, "_apply",
                        lambda self, pack, sign: None)


FAULTS = [
    ("sc2-4l.mt-zipf", _shift_decode_tokens, "logit_gap"),
    ("sc2-4l.mt-zipf", _shift_recycled_slot_tokens, "logit_gap"),
    ("dsc33-2l.switch-groups", _spike_decode_logits, "logit_gap"),
    ("dsc33-2l.switch-groups", _switch_does_nothing, "weights_err"),
]


@pytest.mark.parametrize("cell,fault,caught", FAULTS,
                         ids=[f[1].__name__.strip("_") for f in FAULTS])
def test_fault_is_not_correct(tiny, cell, fault, caught, monkeypatch,
                              capsys):
    fault(monkeypatch)
    rc, out = T.run_cell(tiny, cell, 77, monkeypatch, capsys)
    assert rc == 0
    assert out["correct"] is False
    c = out["checks"][caught]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", ["sc2-4l.mt-zipf", "dsc33-2l.switch-groups"])
def test_control_reads_worse_than_the_program(tiny, cell, monkeypatch,
                                              capsys):
    """A control run is judged by the cell's limits on the control's
    numbers and comes out not correct; the program's pass beside it."""
    prog, ctrl = [], []
    for seed in (1, 3):
        rc, out = T.run_cell(tiny, cell, seed, monkeypatch, capsys,
                             control=True)
        assert rc == 0
        assert out["correct"] is False and out["program"]["correct"] is True
        assert list(out)[-1] == "checks"
        assert set(out["checks"]) == set(out["program"]["checks"])
        assert any(c["value"] > c["limit"] for c in out["checks"].values())
        prog.append(out["program"]["checks"]["logit_gap"]["value"])
        ctrl.append(out["checks"]["logit_gap"]["value"])
        if "weights_err" in out["checks"]:
            assert out["checks"]["weights_err"]["value"] > \
                1e3 * out["program"]["checks"]["weights_err"]["value"]
    assert max(ctrl) > max(3 * max(prog), T.LIMITS["logit_gap"]["limit"])
