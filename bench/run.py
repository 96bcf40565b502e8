"""Run one benchmark cell once, on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration, its traffic mix,
the limits of its correctness check and its per-layer metric readers are
found by name from ``BENCHMARK.json`` (see ``bench/spec.py``). Weights,
adapters and traffic are made from ``--seed``. After set-up the cell is
measured for ``--seconds``; then what it served is compared with the
plain reference, and the last line of standard output is one JSON object:

  {"correct", "attempted", "failed", "metrics", "device",
   ["breakdown",] "checks"}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window (written under
``.bench_out/``), with the device's busy and window seconds and a
breakdown of device time and idle gaps. The numbers compared for
``correct`` are printed with their limits as the last lines of standard
error and under ``checks``.

Exits 3, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for; 2 when the program is not beside the benchmark.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import spec as S  # noqa: E402


class NoAccelerator(RuntimeError):
    pass


def device_check(chips: int) -> dict:
    """The device this run measures: a TPU with at least ``chips`` chips,
    or ``NoAccelerator``. No fallback to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache(repo: Path) -> str:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache``, whatever
    the environment names: a fixed path inside the checkout, so that only
    a cell's first run there compiles and no two checkouts share entries.
    The program's own helper takes it from ``JAX_COMPILATION_CACHE_DIR``."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = str(repo / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    # small eager programs too: every run after the first loads, never
    # compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return enable_compile_cache()


@dataclass
class Ctx:
    """What a per-layer metric reader reads from."""
    trace: object          # tracereduce.Trace
    counters: dict
    config: dict
    peaks: dict


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def beside(values: dict, limits: dict) -> dict:
    """Each compared number beside its limit."""
    return {n: {"value": v, "limit": limits.get(n, {}).get("limit")}
            for n, v in values.items()}


def main(argv=None, spec: S.Spec = None, control: bool = False) -> int:
    """One run; ``control=True`` also reads the cell's control (see
    ``control.py``), which the benchmark's own runs never do. A control
    run's ``correct`` and ``checks`` are then the control's, judged by
    the same limits, and ``program`` holds the program's."""
    args = parse(argv)
    spec = spec or S.Spec()
    cell = spec.workload(args.workload)
    src = spec.repo / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        device = device_check(cell["chips"])
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3

    import harness as H
    import roofline as R
    cache = enable_cache(spec.repo)
    print(f"bench: {cell['name']} seed={args.seed} on {device['kind']} "
          f"x{device['count']}, compile cache {cache}", file=sys.stderr,
          flush=True)

    config = spec.config(cell["config"])
    mix = spec.mix(cell["traffic"])
    limits = spec.limits(cell["name"])
    peaks = R.peaks(device["kind"])
    run = H.Run(cell=cell["name"], config=config, mix=mix, limits=limits,
                seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                t_process=T_PROCESS, control=control,
                trace_dir=str(spec.repo / ".bench_out" / "trace"
                              / cell["name"]))
    res = spec.driver(mix["driver"]).run(run)
    device["memory_peak_bytes"] = res.memory_peak_bytes

    judged = res.control_checks if control else res.checks
    out = {"correct": H.judge(judged, limits),
           "attempted": res.attempted, "failed": res.failed}
    metrics = {}
    if not args.trace:
        values = dict(res.end_to_end, setup_s=run.setup["setup_s"])
        for m in spec.end_to_end(cell["name"]):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        import tracereduce
        tr = tracereduce.load(run.trace_dir)
        ctx = Ctx(tr, res.counters, config, peaks)
        for m in spec.per_layer(cell["name"]):
            v = spec.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        res.notes["clock_offset_ms"] = tr.offset_ns / 1e6
    out["metrics"] = metrics
    out["device"] = device
    if args.trace:
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(10)}
    if control:
        out["program"] = {"correct": H.judge(res.checks, limits),
                          "checks": beside(res.checks, limits)}
        for n, c in out["program"]["checks"].items():
            print(f"program check {n} = {c['value']!r} limit "
                  f"{c['limit']!r}", file=sys.stderr)
    out["checks"] = beside(judged, limits)

    setup = " ".join(f"{k}={v:.3f}" for k, v in run.setup.items())
    print(f"bench: setup {setup}", file=sys.stderr)
    print("bench: " + " ".join(f"{k}={v}" for k, v in res.notes.items()),
          file=sys.stderr)
    for n, c in out["checks"].items():
        print(f"check {n} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
