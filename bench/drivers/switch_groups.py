"""Rapid switching between groups of requests, through ``core.SwitchEngine``.

The paper's fused-mode serving: one adapter at a time is scattered into
the deployed weights, and a group of requests is served under it by the
model's plain jitted prefill and decode, as ``launch/serve.py``'s
sequential path does. Groups come back to back (a closed loop); every
group names another adapter than the active one, so every group switches.

A switch is timed from the ``switch`` call until every leaf of the new
parameter tree is ready. A request's first token is timed from its
group's switch call. Each decode step's token is pulled to the host, as a
server streaming tokens would, and stamped there. Once ``--seconds`` have
passed no new step (switch, prefill or decode) starts.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness as H
import roofline as R
import traffic


def run(r: H.Run) -> H.Result:
    from repro import core
    from repro.models import lm

    cfg, mix = r.config, r.mix
    mc, params, packs = H.model(r, host_packs=False)
    sw_t = H.Stopwatch(r.setup)
    engine = core.SwitchEngine(params)
    del params                       # the engine holds the only reference

    B, buckets = mix["group"], mix["buckets"]
    cache = max(buckets) + mix["output"]["max"] + 8
    prefill = jax.jit(lambda p, b: lm.prefill(p, mc, b, cache))
    decode = jax.jit(lambda p, t, c, pos: lm.decode_step(p, mc, t, c, pos))

    def switch(a: int) -> None:
        engine.switch(packs[a])
        jax.block_until_ready(jax.tree.leaves(engine.params))

    def first_token(prompts):
        logits, caches = prefill(engine.params, {"tokens": jnp.asarray(prompts)})
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return nxt, caches, np.asarray(nxt)[:, 0]

    def next_token(nxt, caches, pos):
        logits, caches = decode(engine.params, nxt, caches, pos)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return nxt, caches, np.asarray(nxt)[:, 0]

    # warm every shape the window uses: load, unload-and-load, one prefill
    # per prompt bucket, one decode step
    switch(0)
    switch(1 % len(packs))
    for S in buckets:
        nxt, caches, _ = first_token(np.zeros((B, S), np.int32))
    next_token(nxt, caches, max(buckets))
    nxt = caches = None
    sw_t.lap("warm_s")

    nz = sum(int(np.prod(i.shape)) for i, _ in packs[0].entries.values())
    gen = traffic.Traffic(mix, cfg["vocab_size"], r.seed)
    active = 1 % len(packs)
    switch_s, ttft, itl, served = [], [], [], []
    tokens, groups = 0, 0
    model_flops, moved = 0.0, 0.0
    with r.window():
        t_start = t_end = time.perf_counter()
        r.setup["setup_s"] = t_start - r.t_process

        def more() -> bool:
            return time.perf_counter() - t_start < r.seconds

        while more():
            g = gen.group(active)
            S = g.prompts.shape[1]
            t0 = time.perf_counter()
            with r.annotate("bench.switch"):
                switch(g.adapter)
            t_end = time.perf_counter()
            switch_s.append(t_end - t0)
            active = g.adapter
            moved += R.switch_bytes(nz)
            if not more():
                break
            with r.annotate("bench.prefill"):
                nxt, caches, tok = first_token(g.prompts)
            t_end = last = time.perf_counter()
            ttft += [last - t0] * B
            out = [tok]
            model_flops += B * R.prefill_flops(cfg, 0, S)
            for i in range(g.max_tokens - 1):
                if not more():
                    break
                with r.annotate("bench.decode"):
                    nxt, caches, tok = next_token(nxt, caches, S + i)
                t_end = time.perf_counter()
                itl += [t_end - last] * B
                last = t_end
                out.append(tok)
                model_flops += B * R.token_flops(cfg, S + i + 1, True)
            tokens += B * len(out)
            groups += 1
            if len(out) == g.max_tokens:
                toks = np.stack(out, axis=1)
                served += [H.Served(g.prompts[b], toks[b], g.adapter)
                           for b in range(B)]
    peak = H.memory_peak_bytes()
    checks = {"weights_err": H.weights_err(cfg, mix, r.seed, engine.params,
                                           active)}
    control = {}
    if r.control:
        control["weights_err"] = H.weights_err(
            cfg, mix, r.seed, engine.params, active, control=True)
    del engine, packs, caches, nxt
    gc.collect()        # free the program's device buffers

    sample = H.sample(served, r.seed, mix["check_tokens"])
    gaps = H.logit_gaps(cfg, mix, r.seed, sample, control=r.control)
    checks["logit_gap"] = gaps["logit_gap"]
    if r.control:
        control["logit_gap"] = gaps["control_gap"]
    return H.Result(
        attempted=groups * B, failed=0,
        end_to_end={"tokens_per_s": tokens / (t_end - t_start),
                    "itl_p95_ms": np.percentile(itl, 95) * 1e3,
                    "ttft_p95_ms": np.percentile(ttft, 95) * 1e3,
                    "switch_p95_ms": np.percentile(switch_s, 95) * 1e3},
        checks=checks, control_checks=control,
        counters={"model_flops": model_flops, "switch_bytes": moved},
        memory_peak_bytes=peak,
        notes={"window_s": t_end - t_start, "groups": groups,
               "switches": len(switch_s), "tokens": tokens,
               "itl_samples": len(itl), "ttft_samples": len(ttft),
               "finished": len(served), "compared_requests": gaps["requests"],
               "compared_tokens": gaps["tokens"]})
