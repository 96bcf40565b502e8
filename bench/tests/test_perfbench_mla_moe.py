"""The MLA + MoE cell's control on the CPU at toy size (the cell's own
result line is checked with every other cell's in ``test_perfbench_run``;
``conftest.py`` cuts its configuration and mix)."""
from __future__ import annotations

import pytest

import perfbench_tiny as T

CELL = "dsv2l-7l.switch-groups-64"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return T.make(tmp_path_factory.mktemp("tiny"))


def test_mla_moe_control_is_not_correct(tiny, monkeypatch, capsys):
    """The float8 reference in the program's place fails the toy limits."""
    rc, out = T.run_cell(tiny, CELL, 7, monkeypatch, capsys, seconds=3.0,
                         control=True)
    assert rc == 0 and out["correct"] is False
    assert out["program"]["correct"] is True
