"""Rapid switching between groups of requests on the MLA + MoE block.

The paper's fused-mode serving, as ``switch_groups.py`` drives it, for a
DeepSeek-V2 configuration (``weights_mla_moe.py``): one adapter at a time,
experts included, is scattered into the deployed weights by
``core.SwitchEngine``, and a group of ``group`` requests is served under
it by the model's plain jitted prefill and decode. Groups come back to
back (a closed loop); every group names another adapter than the active
one, so every group switches.

A group's prompts come in ``prefill_batch``-row batches, each with its own
drawn length filled up to a bucket; each batch is prefilled into its rows
of the group's latent cache. Then all rows decode together, each at its
own position (MLA's vector-position decode), for the group's output
length. A switch is timed from the ``switch`` call until every leaf is
ready; a request's first token from its group's switch call; each decode
step's tokens are pulled to the host and stamped there. Once ``--seconds``
have passed no new step starts.

The program returns its routing counts with each step; they are summed on
the device, by phase and prompt bucket, and pulled once after the window
(``moe.record_routed``).
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness as H
import reference_mla_moe as RM
import roofline as R
import roofline_mla_moe as RMM
import traffic
import weights_mla_moe as WM


def program_config(cfg: dict):
    """The program's ModelConfig for the configuration file: the registry's
    architecture with the file's sizes, depth and held experts. Refuses a
    registry block that differs from what the file (and so the reference)
    states: family, attention kind, routing, norm epsilon and YaRN."""
    from repro.configs import get_config
    from repro.configs.base import MLAConfig
    base = get_config(cfg["arch"])
    mc = base.replace(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      q_lora_rank=cfg["q_lora_rank"] or 0,
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=dataclasses.replace(
            base.moe, num_experts=cfg["router_experts"],
            top_k=cfg["num_experts_per_tok"],
            num_shared=cfg["n_shared_experts"],
            d_ff=cfg["moe_intermediate_size"],
            first_dense_layers=cfg["first_k_dense_replace"],
            first_dense_d_ff=cfg["intermediate_size"],
            held_experts=cfg["n_routed_experts"],
            first_held=cfg["first_held_expert"]))
    y = cfg["rope_scaling"]
    want = {"family": "moe", "attn_type": "mla", "act": cfg["hidden_act"],
            "norm_eps": cfg["rms_norm_eps"],
            "rope_theta": float(cfg["rope_theta"]), "qkv_bias": False,
            "tie_embeddings": cfg["tie_word_embeddings"],
            "logit_softcap": 0.0, "modality": "text",
            # the program scales the routed sum by 1, as published
            "routing": (cfg["norm_topk_prob"],
                        float(cfg["routed_scaling_factor"])),
            "yarn": ("yarn", y["factor"], y["original_max_position_embeddings"],
                     y["beta_fast"], y["beta_slow"], y["mscale"],
                     y["mscale_all_dim"])}
    got = {k: getattr(mc, k) for k in want if k not in ("routing", "yarn")}
    got["routing"] = (mc.moe.norm_topk_prob, 1.0)
    ya = mc.yarn
    got["yarn"] = ya and ("yarn", ya.factor, ya.original_max_position,
                          ya.beta_fast, ya.beta_slow, ya.mscale,
                          ya.mscale_all_dim)
    if got != want:
        raise ValueError(f"{cfg['arch']}: the program's block {got} is not "
                         f"the configuration's {want}")
    return mc


def next_group(gen: traffic.Traffic, mix: dict, active: int):
    """(adapter other than ``active``, prompt batches, output length): each
    batch of ``prefill_batch`` rows at its own drawn length, filled up to
    the smallest bucket that holds it."""
    a = gen._adapter()
    while a == active:
        a = gen._adapter()
    batches = []
    for _ in range(mix["group"] // mix["prefill_batch"]):
        n = gen.prompt_len.next()
        length = min(b for b in mix["buckets"] if b >= n)
        batches.append(gen._tokens(mix["prefill_batch"], length))
    return a, batches, gen.output_len.next()


def _counted(acc, counts):
    """Add one call's routed counts (layers, held) to a phase's sums:
    token-choices per held expert, and calls in which it had any."""
    return {"routed": acc["routed"] + counts,
            "hit": acc["hit"] + (counts > 0).astype(jnp.int32)}


def run(r: H.Run) -> H.Result:
    from repro import core
    from repro.core.adapters import AdapterPack
    from repro.models import lm, moe

    cfg, mix = r.config, r.mix
    sw = H.Stopwatch(r.setup)
    mc = program_config(cfg)
    params = WM.make_params(cfg, r.seed)
    jax.block_until_ready(params)
    H.check_tree(params, mc)
    sw.lap("params_s")
    packs = [AdapterPack(f"a{a}", WM.make_pack(cfg, r.seed, a, mix["density"],
                                               mix["std"], mix["targets"]))
             for a in range(mix["adapters"])]
    jax.block_until_ready([p.entries for p in packs])
    sw.lap("packs_s")
    engine = core.SwitchEngine(params)
    del params                       # the engine holds the only reference

    B, Bp, buckets = mix["group"], mix["prefill_batch"], mix["buckets"]
    cache_len = max(buckets) + mix["output"]["max"] + 8

    @functools.partial(jax.jit, donate_argnums=(2,))
    def prefill(p, tokens, cache, row0, acc):
        logits, caches, counts = lm.prefill(p, mc, {"tokens": tokens},
                                            cache_len, expert_counts=True)
        cache = jax.tree.map(
            lambda g, c: jax.lax.dynamic_update_slice_in_dim(g, c, row0, 1),
            cache, caches)
        return (jnp.argmax(logits, -1).astype(jnp.int32), cache,
                _counted(acc, counts))

    @functools.partial(jax.jit, donate_argnums=(2,))
    def decode(p, tok, cache, pos, acc):
        logits, cache, counts = lm.decode_step(p, mc, tok[:, None], cache,
                                               pos, expert_counts=True)
        return (jnp.argmax(logits, -1).astype(jnp.int32), cache,
                _counted(acc, counts))

    def switch(a: int) -> None:
        engine.switch(packs[a])
        jax.block_until_ready(jax.tree.leaves(engine.params))

    n_moe = WM.stage_layers(cfg, WM.MOE)
    held = cfg["n_routed_experts"]

    def zeros():
        z = jnp.zeros((n_moe, held), jnp.int32)
        return {k: {"routed": z, "hit": z}
                for k in ["decode"] + [str(S) for S in buckets]}

    cache = lm.init_cache(mc, B, cache_len)
    acc = zeros()

    # warm every shape the window uses: load, unload-and-load, a prefill per
    # prompt bucket, the gather of first tokens, one decode step
    switch(0)
    switch(1 % len(packs))
    firsts = []
    for j, S in enumerate(buckets):
        nxt, cache, acc[str(S)] = prefill(
            engine.params, np.zeros((Bp, S), np.int32), cache, j * Bp,
            acc[str(S)])
        firsts.append(nxt)
    firsts += [nxt] * (B // Bp - len(firsts))
    nxt = jnp.concatenate(firsts)
    nxt, cache, acc["decode"] = decode(engine.params, nxt, cache,
                                       jnp.full((B,), 8, jnp.int32),
                                       acc["decode"])
    np.asarray(nxt)
    acc = zeros()
    sw.lap("warm_s")

    nz = sum(int(np.prod(i.shape)) for i, _ in packs[0].entries.values())
    gen = traffic.Traffic(mix, cfg["vocab_size"], r.seed)
    active = 1 % len(packs)
    switch_s, ttft, itl, served = [], [], [], []
    tokens, groups = 0, 0
    model_flops, moved = 0.0, 0.0
    # decode FLOPs are linear in the context: summed once after the window
    decoded, contexts = 0, 0
    f0 = RMM.token_flops(cfg, 0, True)
    f_key = RMM.token_flops(cfg, 1, True) - f0
    with r.window():
        t_start = t_end = time.perf_counter()
        r.setup["setup_s"] = t_start - r.t_process

        def more() -> bool:
            return time.perf_counter() - t_start < r.seconds

        while more():
            adapter, batches, max_tokens = next_group(gen, mix, active)
            t0 = time.perf_counter()
            with r.annotate("bench.switch"):
                switch(adapter)
            t_end = time.perf_counter()
            switch_s.append(t_end - t0)
            active = adapter
            moved += R.switch_bytes(nz)
            firsts, first_toks, lens = [], [], []
            for j, prompts in enumerate(batches):
                if not more():
                    break
                S = prompts.shape[1]
                with r.annotate("bench.prefill"):
                    nxt, cache, acc[str(S)] = prefill(
                        engine.params, prompts, cache, j * Bp, acc[str(S)])
                    tok = np.asarray(nxt)
                t_end = last = time.perf_counter()
                ttft += [last - t0] * Bp
                firsts.append(nxt)
                first_toks.append(tok)
                lens += [S] * Bp
                model_flops += Bp * RMM.prefill_flops(cfg, 0, S)
            tokens += Bp * len(firsts)
            if len(firsts) < len(batches):
                break
            pos0 = np.asarray(lens, np.int32)
            nxt = jnp.concatenate(firsts)
            out = [np.concatenate(first_toks)]
            for i in range(max_tokens - 1):
                if not more():
                    break
                with r.annotate("bench.decode"):
                    nxt, cache, acc["decode"] = decode(
                        engine.params, nxt, cache, jnp.asarray(pos0 + i),
                        acc["decode"])
                    tok = np.asarray(nxt)
                t_end = time.perf_counter()
                itl += [t_end - last] * B
                last = t_end
                out.append(tok)
                decoded += B
                contexts += int(pos0.sum()) + B * (i + 1)
            tokens += B * (len(out) - 1)
            groups += 1
            if len(out) == max_tokens:
                toks = np.stack(out, axis=1)
                rows = [row for b in batches for row in b]
                served += [H.Served(rows[b], toks[b], adapter)
                           for b in range(B)]
    peak = H.memory_peak_bytes()

    routed = moe.record_routed(sum(a["routed"] for a in acc.values()))
    calls = [RMM.moe_gmm_calls(cfg, float(np.asarray(a["routed"]).sum()),
                               float(np.asarray(a["hit"]).sum()))
             for a in acc.values()]
    model_flops += (decoded * f0 + contexts * f_key
                    + RMM.routed_flops(cfg, float(routed.sum())))
    checks = {"weights_err": RM.weights_err(cfg, mix, r.seed, engine.params,
                                            active)}
    control = {}
    if r.control:
        control["weights_err"] = RM.weights_err(
            cfg, mix, r.seed, engine.params, active, control=True)
    del engine, packs, cache, nxt, firsts
    gc.collect()        # free the program's device buffers

    sample = H.sample(served, r.seed, mix["check_tokens"])
    gaps = RM.logit_gaps(cfg, mix, r.seed, sample, control=r.control)
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
        counters={"model_flops": model_flops, "switch_bytes": moved,
                  "moe_gmm_calls": calls,
                  "moe_routed_local": float(routed.sum()),
                  "moe_routed_max": float(routed.max(initial=0))},
        memory_peak_bytes=peak,
        notes={"window_s": t_end - t_start, "groups": groups,
               "switches": len(switch_s), "tokens": tokens,
               "itl_samples": len(itl), "ttft_samples": len(ttft),
               "routed_local": int(routed.sum()),
               "finished": len(served), "compared_requests": gaps["requests"],
               "compared_tokens": gaps["tokens"]})
