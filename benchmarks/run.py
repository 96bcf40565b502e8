"""Benchmark harness — one section per paper table/figure.

  adapter_quality   -> Tables 1-3 (LoRA vs SHiRA masks vs DoRA)
  multi_adapter     -> Table 4   (fusion interference, %Drop)
  rapid_switching   -> Fig. 5    (scatter vs fuse)
  train_efficiency  -> Table 6   (memory / step time per adapter)

Each section prints CSV. Run everything:
  PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import sys
import time

from benchmarks import (adapter_quality, multi_adapter, rapid_switching,
                        train_efficiency)

SECTIONS = [
    ("rapid_switching", rapid_switching.main),
    ("train_efficiency", train_efficiency.main),
    ("adapter_quality", adapter_quality.main),
    ("multi_adapter", multi_adapter.main),
]


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    for name, fn in SECTIONS:
        if only and only != name:
            continue
        print(f"\n### {name} " + "#" * (60 - len(name)))
        t0 = time.time()
        fn()          # a failing section ends the run with a nonzero code
        print(f"### {name} done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
