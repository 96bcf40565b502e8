"""FLOP and byte functions against hand counts at small shapes."""
from __future__ import annotations

import pytest

import perfbench_tiny  # noqa: F401
import roofline as R

CFG = dict(num_layers=2, d_model=8, num_heads=2, num_kv_heads=1, head_dim=4,
           d_ff=16, vocab_size=32, act="gelu")
PK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_sidedelta_counts_the_operation():
    # 3 tokens, (n, m) = (8, 16), 5 nonzeros, 2 distinct adapters
    flops, nbytes = R.sidedelta_call(3, 8, 16, 5, 2)
    assert flops == 2 * 5 * 3
    assert nbytes == 3 * (8 * 2 + 16 * 4) + 2 * 5 * 4


def test_switch_bytes():
    # unload + load, each: read and write the f32 weight, read the value
    assert R.switch_bytes(10) == 2 * 10 * (4 + 4 + 4)


def test_matmul_params_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, w_up 8x16, w_down 16x8
    assert R.matmul_params(CFG) == 64 + 32 + 32 + 64 + 128 + 128
    gated = dict(CFG, act="silu")
    assert R.matmul_params(gated) == R.matmul_params(CFG) + 128


def test_token_and_prefill_flops():
    per = 2 * R.matmul_params(CFG)
    one = R.token_flops(CFG, context=3, logits=True, adapter_nonzeros=7)
    assert one == 2 * (per + 4 * 3 * 2 * 4 + 2 * 7) + 2 * 8 * 32
    # positions 2, 3, 4: contexts 3, 4, 5; logits for the last one only
    want = sum(R.token_flops(CFG, c, c == 5) for c in (3, 4, 5))
    assert R.prefill_flops(CFG, 2, 5) == pytest.approx(want)


def test_bound_takes_the_larger_and_peaks_name_the_device():
    assert R.bound_s(100.0, 1.0, PK) == 1.0
    assert R.bound_s(1.0, 100.0, PK) == 10.0
    assert R.sum_bounds([(100.0, 1.0), (1.0, 100.0)], PK) == 11.0
    v5e = R.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        R.peaks("cpu")
