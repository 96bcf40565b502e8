"""Measured-time sub-chunk (kc) autotuning for the sidedelta kernel.

``kernels/sidedelta.py::plan_tiles`` picks the sub-chunk length of the
block-bucketed tables from a cost model over the block counts — a model,
not a measurement. This module closes the loop: for each ``(n, m, K)``
shape class it sweeps the feasible kc candidates (lane-aligned, within
the same budget), lays random tables out under each, times the real
``sidedelta_rows`` dispatch, and persists the winners in a JSON plan
cache that ``plan_tiles`` consults before falling back to the heuristic
(``sidedelta.install_plan_cache``; invalid entries are rejected at
lookup, so a stale cache degrades to the heuristic instead of producing
a broken kernel).

Typical flow (also what ``python -m repro.analysis.autotune`` runs)::

    from repro.analysis import autotune
    shapes = autotune.observed_shapes()     # shape classes plan_tiles saw
    plans = autotune.autotune(shapes)       # sweep + measure
    autotune.save_cache(plans, "benchmarks/plan_cache.json")
    autotune.install(plans)                 # live in this process

    # later processes:
    autotune.install(autotune.load_cache("benchmarks/plan_cache.json"))

Shape classes are discovered, not guessed: ``observe()`` wraps a
workload (an engine warmup, a bench) and records every distinct
``plan_tiles`` request made under it (one per adapted weight and table
build). Measurements use the engine's own
dispatch path (``sidedelta_rows`` under ``jit``) so the numbers include
exactly what serving pays — XLA-compiled off-TPU, Mosaic on TPU.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

import importlib

# the kernels package re-exports a *function* named ``sidedelta`` (the op),
# shadowing the submodule at package level — resolve the module directly
SD = importlib.import_module("repro.kernels.sidedelta")

PlanKey = SD.PlanKey
Plan = int                                      # kc

_LANE = SD._LANE


# ---------------------------------------------------------------------------
# Shape-class discovery
# ---------------------------------------------------------------------------

_observed: "dict[PlanKey, int]" = {}


@contextlib.contextmanager
def observe():
    """Record every (n, m, K) shape class ``plan_tiles`` is asked to
    plan while the context is open (run your serving warmup inside)."""
    orig = SD.plan_tiles

    def recording(n, m, K, *, counts=None,
                  vmem_budget=SD.DEFAULT_VMEM_BUDGET, x_itemsize=4):
        key = SD.plan_cache_key(n, m, K, vmem_budget, x_itemsize)
        _observed[key] = _observed.get(key, 0) + 1
        return orig(n, m, K, counts=counts, vmem_budget=vmem_budget,
                    x_itemsize=x_itemsize)

    SD.plan_tiles = recording
    try:
        yield
    finally:
        SD.plan_tiles = orig


def observed_shapes() -> List[PlanKey]:
    """Shape classes seen under ``observe()``, most-requested first."""
    return sorted(_observed, key=lambda k: -_observed[k])


def clear_observed() -> None:
    _observed.clear()


# ---------------------------------------------------------------------------
# Candidate enumeration + measurement
# ---------------------------------------------------------------------------

def candidates(key: PlanKey, max_candidates: int = 12) -> List[Plan]:
    """Feasible kc plans for one shape class: the sub-chunk lengths
    ``plan_tiles`` weighs that fit the class's VMEM budget, the static
    plan first."""
    n, m, K, budget, isize = key
    out = [SD.plan_tiles(n, m, K, vmem_budget=budget, x_itemsize=isize)]
    for kc in SD._KCS:
        if kc not in out and SD.plan_is_valid(n, m, K, kc,
                                              vmem_budget=budget,
                                              x_itemsize=isize):
            out.append(kc)
    return out[:max_candidates]


def measure_plan(key: PlanKey, plan: Plan, *, batch: int = 2,
                 adapters: int = 2, reps: int = 3, seed: int = 0,
                 S: int = 1, interpret: bool = False) -> float:
    """Best-of-``reps`` seconds for one jitted ``sidedelta_rows`` step at
    this shape class over random tables laid out with sub-chunk ``plan``
    (one warmup rep compiles)."""
    import jax
    import jax.numpy as jnp

    n, m, K, budget, isize = key
    rng = np.random.default_rng(seed)
    dt = jnp.float32 if isize == 4 else jnp.bfloat16
    x = jnp.asarray(rng.standard_normal((batch, S, n)), dt)
    flat = [rng.choice(n * m, min(max(K, 1), n * m), replace=False)
            for _ in range(adapters)]
    t = SD.sidedelta_table(flat, [rng.standard_normal(len(f)).astype(
        np.float32) for f in flat], n, m, kc=plan, vmem_budget=budget,
        x_itemsize=isize)
    rows, cols, vals, blocks = (jnp.asarray(a) for a in
                                (t.rows, t.cols, t.vals, t.blocks))
    ids = jnp.asarray(rng.integers(0, adapters, (batch,)), jnp.int32)

    fn = jax.jit(lambda xx, ii: SD.sidedelta_rows(
        xx, rows, cols, vals, ii, m, blocks=blocks, interpret=interpret,
        vmem_budget=budget))
    jax.block_until_ready(fn(x, ids))            # compile
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x, ids))
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(shapes: Iterable[PlanKey], *, reps: int = 3, batch: int = 2,
             interpret: bool = False, verbose: bool = False,
             max_candidates: int = 12) -> Dict[PlanKey, Plan]:
    """Sweep each shape class and return the measured-best plan per key.

    Only classes where some candidate actually beat the static plan are
    interesting, but every swept class gets an entry — a cache hit that
    reproduces the static plan is still a skipped heuristic."""
    plans: Dict[PlanKey, Plan] = {}
    for key in shapes:
        best_plan, best_t = None, float("inf")
        for plan in candidates(key, max_candidates=max_candidates):
            t = measure_plan(key, plan, reps=reps, batch=batch,
                             interpret=interpret)
            if verbose:
                n, m, K = key[:3]
                print(f"  (n={n},m={m},K={K}) kc={plan:4d}: "
                      f"{t * 1e6:9.1f} us")
            if t < best_t:
                best_plan, best_t = plan, t
        if best_plan is not None:
            plans[key] = best_plan
    return plans


# ---------------------------------------------------------------------------
# Persistence + installation
# ---------------------------------------------------------------------------

def save_cache(plans: Dict[PlanKey, Plan], path: str,
               meta: Optional[dict] = None) -> str:
    """JSON plan cache: ``{"n,m,K,budget,itemsize": kc, ...}``."""
    body = {",".join(str(x) for x in key): int(kc)
            for key, kc in sorted(plans.items())}
    with open(path, "w") as f:
        json.dump({"schema": 2, "meta": dict(meta or {}), "plans": body},
                  f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_cache(path: str) -> Dict[PlanKey, Plan]:
    with open(path) as f:
        doc = json.load(f)
    plans: Dict[PlanKey, Plan] = {}
    for key, plan in doc.get("plans", {}).items():
        parts = tuple(int(x) for x in key.split(","))
        if len(parts) == 5 and isinstance(plan, int):
            plans[parts] = plan
    return plans


def install(plans: Dict[PlanKey, Plan], replace: bool = False) -> int:
    """Make ``plan_tiles`` consult these plans (process-wide)."""
    return SD.install_plan_cache(plans, replace=replace)


def maybe_install_file(path: str) -> int:
    """Install a plan-cache file if it exists; returns entries installed
    (0 when the file is absent — callers need no existence check)."""
    import os
    if not os.path.exists(path):
        return 0
    return install(load_cache(path))


# ---------------------------------------------------------------------------
# CLI: observe a smoke serving workload, sweep, persist.
# ---------------------------------------------------------------------------

def _collect_smoke_shapes(arch: str, batch: int, prompt_len: int,
                          tokens: int, adapters: int) -> List[PlanKey]:
    """Run small multi-tenant and paged-engine workloads under
    ``observe()`` so the swept shape classes are exactly what the bench
    tier plans for: every adapted weight of both engines' table builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.hub import PagedServingEngine
    from repro.launch.serve import make_adapters
    from repro.models import layers, lm
    from repro.serving import MultiTenantEngine

    cfg = get_smoke_config(arch)
    clear_observed()
    with layers.compute_precision(jnp.float32):
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        packs = make_adapters(cfg, params, adapters, jax.random.PRNGKey(7),
                              multi_tenant=True)
        engine = MultiTenantEngine(cfg, params)
        for p in packs:
            engine.register(p)
        names = [packs[i % adapters].name for i in range(batch)]
        toks = jax.random.randint(jax.random.PRNGKey(1),
                                  (batch, prompt_len), 0, cfg.vocab_size)
        with observe():
            engine.generate({"tokens": toks}, names, tokens)

        pe = PagedServingEngine(cfg, params, slots=4, num_pages=64,
                                page_size=2, max_len=prompt_len + tokens + 2,
                                chunk_size=4)
        for p in packs:
            pe.register(p)
        rng = np.random.default_rng(0)
        with observe():
            for i in range(batch):
                pe.submit(rng.integers(0, cfg.vocab_size, prompt_len),
                          packs[i % adapters].name, max_tokens=tokens)
            pe.run()
    return observed_shapes()


# Representative full-scale serving classes swept alongside whatever the
# smoke warmup observes. (n, m, K).
DEFAULT_EXTRA_SHAPES = ((1024, 1024, 8000), (2048, 2048, 16000))


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Autotune sidedelta kc plans for the smoke "
        "serving shape classes and write the plan cache JSON.")
    ap.add_argument("--arch", default="starcoder2-7b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=4)
    ap.add_argument("--adapters", type=int, default=3)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--shape", action="append", default=[],
                    metavar="n,m,K", help="extra shape class to sweep "
                    "(repeatable; replaces the built-in extras)")
    ap.add_argument("--out", default="benchmarks/plan_cache.json")
    args = ap.parse_args(argv)

    shapes = _collect_smoke_shapes(args.arch, args.batch, args.prompt_len,
                                   args.tokens, args.adapters)
    extras = ([tuple(int(x) for x in s.split(",")) for s in args.shape]
              if args.shape else DEFAULT_EXTRA_SHAPES)
    for n, m, K in extras:
        key = SD.plan_cache_key(n, m, K)
        if key not in shapes:
            shapes.append(key)
    print(f"observed {len(shapes)} shape classes "
          f"(incl. {len(extras)} full-scale extras); sweeping...")
    plans = autotune(shapes, reps=args.reps, verbose=True)
    static = {k: SD.plan_tiles(*k[:3], vmem_budget=k[3], x_itemsize=k[4])
              for k in plans}
    changed = sum(plans[k] != static[k] for k in plans)
    path = save_cache(plans, args.out,
                      meta={"arch": args.arch, "source": "autotune CLI",
                            "changed_vs_static": changed})
    print(f"wrote {path}: {len(plans)} plans "
          f"({changed} differ from the static heuristic)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
