"""Traffic draws: determinism by seed, lengths, Zipf and groups."""
from __future__ import annotations

import json

import numpy as np
import pytest

import perfbench_tiny as T
import traffic as TF

MT = json.loads((T.BENCH / "mixes" / "mt-zipf.json").read_text())
SG = json.loads((T.BENCH / "mixes" / "switch-groups.json").read_text())


def _reqs(seed, n=64, mix=MT):
    g = TF.Traffic(mix, 49152, seed)
    return [g.request() for _ in range(n)]


def test_same_seed_same_requests_other_seed_others():
    a, b, c = _reqs(2**33 + 5), _reqs(2**33 + 5), _reqs(6)
    assert all(np.array_equal(x.prompt, y.prompt)
               and (x.max_tokens, x.adapter) == (y.max_tokens, y.adapter)
               for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_lengths_stay_in_bounds_and_cover_every_stratum():
    reqs = _reqs(3, n=MT["strata"] * 8)
    plen = np.array([len(r.prompt) for r in reqs])
    out = np.array([r.max_tokens for r in reqs])
    assert plen.min() >= 128 and plen.max() <= 2048
    assert out.min() >= 16 and out.max() <= 64
    # each block of `strata` draws holds one prompt from each slice, so
    # every block's median sits near the distribution's median
    blocks = plen.reshape(-1, MT["strata"])
    assert np.all(np.abs(np.median(blocks, axis=1) - 512) < 80)


def test_every_seed_serves_the_same_sizes_in_another_order():
    mix = dict(MT, length_order="seed")
    a, b = _reqs(1, n=MT["strata"] * 4, mix=mix), _reqs(2, n=MT["strata"] * 4,
                                                          mix=mix)
    for k in range(0, len(a), MT["strata"]):
        block_a = [(len(r.prompt), r.max_tokens) for r in a[k:k + 8]]
        block_b = [(len(r.prompt), r.max_tokens) for r in b[k:k + 8]]
        assert sorted(x for x, _ in block_a) == sorted(x for x, _ in block_b)
        assert sorted(y for _, y in block_a) == sorted(y for _, y in block_b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    g1, g2 = TF.Traffic(mix, 10, 1), TF.Traffic(mix, 10, 2)
    assert sorted(g1.in_progress(8)) == sorted(g2.in_progress(8))
    assert g1.in_progress(8) != g2.in_progress(8)


def test_fixed_order_serves_the_same_lengths_for_every_seed():
    """mt-zipf: only the tokens and adapters come from the seed."""
    assert MT["length_order"] == "fixed"
    a, b = _reqs(1), _reqs(2**31 + 7)
    assert ([(len(r.prompt), r.max_tokens) for r in a]
            == [(len(r.prompt), r.max_tokens) for r in b])
    assert any(x.adapter != y.adapter for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert (TF.Traffic(MT, 10, 1).in_progress(8)
            == TF.Traffic(MT, 10, 2).in_progress(8))


def test_quantiles():
    ln = MT["prompt"]
    assert TF.quantile(ln, 0.5) == 512
    assert TF.quantile(ln, 1e-9) == 128 and TF.quantile(ln, 1 - 1e-9) == 2048
    un = MT["output"]
    assert [TF.quantile(un, u) for u in (0.0, 0.5, 0.999)] == [16, 40, 64]


def test_zipf_draws_follow_the_weights():
    p = TF.zipf_probs(16, 1.1)
    assert p.sum() == pytest.approx(1.0) and np.all(np.diff(p) < 0)
    g = TF.Traffic(MT, 100, 11)
    counts = np.bincount([g.request().adapter for _ in range(4000)],
                         minlength=16) / 4000
    assert np.abs(counts - p).max() < 0.03


def test_groups_switch_and_fill_a_bucket():
    g = TF.Traffic(SG, 32256, 4)
    active = None
    for _ in range(50):
        grp = g.group(active)
        assert grp.adapter != active
        assert grp.prompts.shape[0] == SG["group"]
        assert grp.prompts.shape[1] in SG["buckets"]
        assert 16 <= grp.max_tokens <= 32
        active = grp.adapter


def test_in_progress_shares_cover_each_slice():
    shares = sorted(TF.Traffic(MT, 10, 1).in_progress(8))
    assert all(k / 8 < s <= (k + 1) / 8 for k, s in enumerate(shares))
