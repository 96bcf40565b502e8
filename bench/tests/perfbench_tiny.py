"""A toy-sized copy of the benchmark for tests on the CPU.

The real BENCHMARK.json, drivers and metric readers; the configurations
cut to toy widths, the mixes to toy lengths, and limits set for toy
sizes. ``run_cell`` drives ``bench/run.py``'s ``main`` with the device
check patched, so that nothing looks for a TPU.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

import run  # noqa: E402
import spec as S  # noqa: E402

TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            head_dim=16, d_ff=128, vocab_size=256)
MIXES = {
    "mt-zipf": dict(clients=2, adapters=3, page_size=8, chunk_size=16,
                    prompt=dict(dist="lognormal", median=24, sigma=0.6,
                                min=8, max=48),
                    output=dict(dist="uniform", min=3, max=6),
                    check_tokens=48, drain_s=30),
    "switch-groups": dict(group=2, adapters=3, buckets=[16, 32],
                          prompt=dict(dist="lognormal", median=20, sigma=0.6,
                                      min=8, max=32),
                          output=dict(dist="uniform", min=3, max=5),
                          check_tokens=20),
}
# toy-size limits: the bf16 program reads 0-0.003 here, the float8
# control 0.015-0.06 (mt-zipf over 48 tokens; over 20 it can read 0.004);
# weights drift ~1e-7, bfloat16 weights ~1e-2
LIMITS = {"logit_gap": {"limit": 0.01}, "weights_err": {"limit": 1e-4}}
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


class TinySpec(S.Spec):
    """Configurations, mixes and limits from a toy tree; the rest from
    the repository's bench directory."""

    def mix(self, name):
        return S._load_json(self.repo / "bench" / "mixes" / f"{name}.json")

    def limits(self, cell):
        return S._load_json(self.repo / "bench" / "limits" / f"{cell}.json")


def make(root: Path) -> TinySpec:
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    for d in ("configs", "mixes", "limits"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    for c in doc["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        cfg.update(TINY)
        (root / c["file"]).write_text(json.dumps(cfg))
    for name, over in MIXES.items():
        mix = json.loads((BENCH / "mixes" / f"{name}.json").read_text())
        mix.update(over)
        (root / "bench" / "mixes" / f"{name}.json").write_text(json.dumps(mix))
    for w in doc["workloads"]:
        have = json.loads((BENCH / "limits" / f"{w['name']}.json").read_text())
        (root / "bench" / "limits" / f"{w['name']}.json").write_text(
            json.dumps({k: LIMITS[k] for k in have}))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    if not (root / "src").exists():
        os.symlink(REPO / "src", root / "src")
    return TinySpec(root, BENCH)


def run_cell(sp, cell, seed, monkeypatch, capsys, trace=0, seconds=1.0,
             control=False):
    """One toy run of ``cell``: (exit code, last stdout line as JSON)."""
    monkeypatch.setattr(run, "device_check", lambda chips: dict(CPU))
    monkeypatch.setattr(run, "enable_cache", lambda repo: "off")
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], spec=sp,
                  control=control)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)
