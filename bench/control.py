"""Read a cell's control beside the program, on the chip, at the cell's size.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

Each seed is one run of the cell exactly as ``run.py`` makes it; after the
window, beside the numbers ``correct`` compares for the program, the same
numbers are read for the control: the plain reference put in the
program's place one precision below the configuration's (float8 matmuls
where the program computes in bfloat16; bfloat16 weights where it keeps
float32). Prints one JSON line per seed, whose ``correct`` and ``checks``
are the control's, judged against ``limits/<cell>.json`` as a run judges
the program: a control run must come out not correct. ``program`` holds
the program's verdict and numbers. The lower reading of each limit is the
largest the program gives over a dozen seeds or more, the upper the
smallest the control gives.

Not part of the benchmark's own runs; one process serves every seed.
"""
from __future__ import annotations

import argparse
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    rc = 0
    for seed in args.seeds:
        rc = rc or run.main(["--workload", args.workload, "--seed", str(seed),
                             "--seconds", str(args.seconds), "--trace", "0"],
                            control=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
