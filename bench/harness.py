"""What every driver shares: the program's config, the window, the checks.

A driver (``drivers/<name>.py``) builds the system under test from a
configuration and a mix, serves the mix's traffic for ``--seconds``, and
returns a ``Result``. This module holds the pieces that do not depend on
the kind of traffic: timing of set-up, the measured window and its trace,
and the comparisons that decide ``correct``.
"""
from __future__ import annotations

import contextlib
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import reference
import weights as W


@dataclass
class Run:
    """One run of one cell, as ``run.py`` hands it to a driver."""
    cell: str
    config: dict
    mix: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float                       # perf_counter at process start
    trace_dir: str = ""
    control: bool = False                  # also read the control
    setup: Dict[str, float] = field(default_factory=dict)

    def annotate(self, name: str):
        """A ``bench.*`` span on the profiler's clock, in traced runs only."""
        if self.trace:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    @contextlib.contextmanager
    def window(self):
        """The measured window. In a traced run the profiler records it
        (host Python tracing off) and ``bench.window`` marks its extent."""
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    yield
            finally:
                jax.profiler.stop_trace()
        else:
            yield


@dataclass
class Result:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]           # name -> value, e2e metrics
    checks: Dict[str, float]               # name -> value compared
    counters: Dict[str, Any] = field(default_factory=dict)
    memory_peak_bytes: int = 0
    notes: Dict[str, Any] = field(default_factory=dict)
    # with ``Run.control``: the same numbers as ``checks``, read for the
    # control
    control_checks: Dict[str, float] = field(default_factory=dict)


class Stopwatch:
    """Named set-up phases, in seconds."""

    def __init__(self, into: Dict[str, float]):
        self.into = into
        self.t = time.perf_counter()

    def lap(self, name: str) -> float:
        now = time.perf_counter()
        self.into[name] = self.into.get(name, 0.0) + now - self.t
        self.t = now
        return now


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------

SIZE_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
             "head_dim", "d_ff", "vocab_size")


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the registry's
    architecture with the file's sizes. Refuses a registry entry whose
    block differs from what the file (and so the reference) states."""
    from repro.configs import get_config
    mc = get_config(cfg["arch"]).replace(**{k: cfg[k] for k in SIZE_KEYS})
    a = cfg["assumed"]
    want = {"family": "dense", "attn_type": "gqa", "act": cfg["act"],
            "qkv_bias": cfg["qkv_bias"], "rope_theta": a["rope_theta"],
            "norm_eps": a["norm_eps"], "tie_embeddings": False,
            "logit_softcap": 0.0, "pad_heads_to": 0, "pad_kv_to": 0,
            "modality": "text", "moe": None}
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise ValueError(f"{cfg['arch']}: the program's block {got} is not "
                         f"the configuration's {want}")
    return mc


def check_tree(params, mc) -> None:
    """The benchmark's tree has the program's layout, shapes and dtypes."""
    from repro.models import lm
    want = jax.eval_shape(lambda: lm.init_params(mc, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or not all(
            (a.shape, a.dtype) == (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")


def model(r: Run, host_packs: bool):
    """Set-up shared by the drivers: the program's config, the weights
    (checked against its layout) and the mix's packs, timed into
    ``r.setup`` as ``params_s`` and ``packs_s``."""
    sw = Stopwatch(r.setup)
    mc = program_config(r.config)
    params = W.make_params(r.config, r.seed)
    jax.block_until_ready(params)
    check_tree(params, mc)
    sw.lap("params_s")
    packs = program_packs(r.config, r.mix, r.seed, host=host_packs)
    sw.lap("packs_s")
    return mc, params, packs


def program_packs(cfg: dict, mix: dict, seed: int, host: bool):
    """The mix's adapters as the program's ``AdapterPack``s, named a0, a1,
    ... Values stay on the device, or come to the host (``host=True``)
    where the program only reads them to build its tables."""
    from repro.core.adapters import AdapterPack
    packs = []
    for a in range(mix["adapters"]):
        entries = W.make_pack(cfg, seed, a, mix["density"], mix["std"],
                              mix["targets"])
        if host:
            entries = {p: (np.asarray(i), np.asarray(v))
                       for p, (i, v) in entries.items()}
        packs.append(AdapterPack(f"a{a}", entries))
    if not host:
        jax.block_until_ready([p.entries for p in packs])
    return packs


def memory_peak_bytes() -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks, default=0)


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

@dataclass
class Served:
    """One request: its prompt, the tokens the program served for it, and
    the adapter index it was served under."""
    prompt: np.ndarray
    tokens: np.ndarray
    adapter: int


def sample(served: List[Served], seed: int, target_tokens: int
           ) -> List[Served]:
    """A sample drawn from the seed: the longest request, then others in
    seed order until ``target_tokens`` served tokens are in it."""
    if not served:
        return []
    longest = max(range(len(served)),
                  key=lambda i: len(served[i].prompt) + len(served[i].tokens))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    order = [longest] + [int(i) for i in rng.permutation(len(served))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if n >= target_tokens:
            break
        out.append(served[i])
        n += len(served[i].tokens)
    return out


def _pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def logit_gaps(cfg: dict, mix: dict, seed: int, served: List[Served],
               control: bool = False) -> Dict[str, float]:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``served``. Requests
    with the same adapter and lengths run through the reference together.
    A request that served no token has nothing to show: the gap is then
    NaN, which ``judge`` fails.

    ``control=True`` also reads the control: the reference computed one
    precision below the program's, whose first choice at each of the same
    positions is judged by the same gap (``control_gap``)."""
    count = int(mix["output"]["max"])
    groups: Dict[tuple, List[Served]] = {}
    for s in served:
        if not len(s.tokens):
            return {"logit_gap": float("nan"), "control_gap": float("nan"),
                    "tokens": 0, "requests": len(served)}
        key = (s.adapter, len(s.prompt), len(s.tokens))
        groups.setdefault(key, []).append(s)
    widest, widest_ctrl, n = 0.0, 0.0, 0
    for (adapter, plen, tlen), reqs in groups.items():
        start = plen - 1
        width = _pad_to(start + count, 256)
        toks = np.zeros((len(reqs), width), np.int32)
        for i, r in enumerate(reqs):
            seq = np.concatenate([r.prompt, r.tokens[:-1]])
            toks[i, :len(seq)] = seq
        chosen = np.stack([r.tokens for r in reqs])
        ref = np.asarray(reference.logits(cfg, seed, adapter, mix, toks,
                                          start, count))[:, :tlen]
        widest = max(widest, float(reference.gaps(ref, chosen).max()))
        n += chosen.size
        if control:
            ctl = np.asarray(reference.logits(cfg, seed, adapter, mix, toks,
                                              start, count, control=True))
            first = ctl[:, :tlen].argmax(-1)
            widest_ctrl = max(widest_ctrl,
                              float(reference.gaps(ref, first).max()))
    out = {"logit_gap": widest if n else float("nan"), "tokens": n,
           "requests": len(served)}
    if control:
        out["control_gap"] = widest_ctrl
    return out


def weights_err(cfg: dict, mix: dict, seed: int, params, adapter: int,
                control: bool = False) -> float:
    """Worst adapted matrix after the last switch: max |program - ref| over
    its entries, as a share of the reference matrix's RMS, where ref is
    the base matrix plus the active adapter's nonzeros, made again from
    the seed in float32. ``control=True`` reads the same for the reference
    computed in bfloat16 instead of the program's weights."""
    key_w = W.root_key(seed, W.WEIGHTS_SALT)
    key_p = W.root_key(seed, W.PACKS_SALT)
    worst = 0.0
    for leaf in W.pack_leaves(cfg, mix["targets"]):
        n, m = leaf.shape

        def err(prog, kw, kp, layer, leaf=leaf):
            base = W.leaf_value(kw, leaf, layer)
            idx, vals = W.pack_layer(kp, adapter, leaf, layer,
                                     mix["density"], mix["std"])
            ref = base.reshape(-1).at[idx].add(vals).reshape(n, m)
            if control:
                prog = base.astype(jnp.bfloat16).reshape(-1).at[idx].add(
                    vals.astype(jnp.bfloat16)).reshape(n, m)
            diff = jnp.max(jnp.abs(prog.astype(jnp.float32) - ref))
            return diff / jnp.sqrt(jnp.mean(ref * ref))

        fn = jax.jit(err)
        stacked = _at_path(params, leaf.path)
        for layer in range(cfg["num_layers"]):
            worst = max(worst, float(fn(stacked[layer], key_w, key_p,
                                        layer)))
    return worst


def _at_path(tree, path: str):
    for p in path.split("/"):
        tree = tree[int(p)] if isinstance(tree, (list, tuple)) else tree[p]
    return tree


def judge(checks: Dict[str, float], limits: dict) -> bool:
    """Each compared number must lie at or under its limit; a number
    without a limit, or a limit without a number, fails."""
    names = set(checks) | set(limits)
    return all(n in checks and n in limits
               and np.isfinite(checks[n]) and checks[n] <= limits[n]["limit"]
               for n in names)
