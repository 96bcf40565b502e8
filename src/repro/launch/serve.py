"""Serving driver: batched decode with per-request SHiRA adapter switching.

Demonstrates the paper's deployment story end to end on this host:
  * prefill a batch of prompts, then decode tokens step by step,
  * swap SHiRA adapters BETWEEN batches via the sparse scatter path
    (SwitchEngine) — no fuse/unfuse stage, base weights patched in place,
  * optionally fuse several adapters (multi-adapter serving).

Multi-tenant serving (``--multi-tenant``): instead of serializing on the
active adapter, every request in the batch names its own adapter and all of
them decode together off ONE shared copy of the base weights
(``repro.serving.MultiTenantEngine``). Each request's SHiRA pack is applied
as a batched sparse side-delta in the forward pass via the Pallas
``sidedelta`` kernel, and a ``FusedLRU`` scheduler fuses the hot adapter
into the shared base (sparse scatter) while cold ones stay in side-delta
form. Flags:
  --multi-tenant        serve mixed-adapter batches in one forward pass
  --batches N           how many request batches to stream
  --skew F              tenant mix skew: fraction of requests routed to
                        adapter_0 (the rest spread uniformly); high skew
                        exercises the scheduler's promote path

Continuous batching (``--continuous``): the request-level path. Instead of
fixed-size batch streams, requests are submitted one by one to
``repro.hub.ServingEngine`` (``submit(prompt, adapter) -> future``): each
decode lane carries its own adapter id and cache position, finished
requests recycle their lane immediately, and all packs are resolved through
a ``repro.hub.AdapterStore`` (``--int8`` stores them quantized, ~3-4x
smaller resident bytes). Extra flags:
  --continuous          serve a request trace through the ServingEngine
  --requests N          how many requests to stream (continuous)
  --slots N             decode lanes (continuous; default --batch)
  --int8                keep adapters int8-quantized in the store

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-7b --smoke \
      --multi-tenant --adapters 3 --tokens 16 --batch 8 --batches 4
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-7b --smoke \
      --continuous --adapters 3 --tokens 16 --slots 4 --requests 12
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import core
from repro.configs import AdapterConfig, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm


def make_adapters(cfg, params, n: int, key, multi_tenant: bool = False) -> list:
    """n random SHiRA packs (stand-ins for independently trained adapters)."""
    packs = []
    targets = AdapterConfig().target_modules
    if multi_tenant:
        from repro.serving.multitenant import UNSUPPORTED_LEAVES
        targets = tuple(t for t in targets if t not in UNSUPPORTED_LEAVES)
    acfg = AdapterConfig(kind="shira", mask="rand", sparsity=0.98,
                         target_modules=targets)
    for i in range(n):
        sub = jax.random.fold_in(key, i)
        values, aux = core.init_adapter(sub, params, acfg)
        values = jax.tree.map(
            lambda v: None if v is None
            else 0.01 * jax.random.normal(sub, v.shape), values,
            is_leaf=lambda x: x is None)
        packs.append(core.pack_from_shira(f"adapter_{i}", values, aux))
    return packs


def tenant_mix(rng, packs, batch: int, skew: float) -> list:
    """Per-request adapter names: ``skew`` of the batch goes to the first
    adapter, the rest spread over the others + the base model (None)."""
    pool = [p.name for p in packs[1:]] + [None]
    return [packs[0].name if rng.random() < skew
            else pool[rng.integers(len(pool))] for _ in range(batch)]


def serve_multi_tenant(cfg, params, packs, args) -> None:
    from numpy.random import default_rng
    from repro.core.switching import FusedLRU
    from repro.serving.multitenant import MultiTenantEngine

    engine = MultiTenantEngine(cfg, params, scheduler=FusedLRU(),
                               table_dtype="int8" if args.int8 else "f32")
    for p in packs:
        engine.register(p)
    rng = default_rng(0)
    B = args.batch
    total, t_total = 0, 0.0
    for step in range(args.batches):
        names = tenant_mix(rng, packs, B, args.skew)
        toks = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(1),
                                                     step),
                                  (B, args.prompt_len), 0, cfg.vocab_size)
        batch = {"tokens": toks}
        if cfg.modality == "vision":
            batch["patch_embeds"] = jnp.zeros(
                (B, cfg.num_prefix_embeds, cfg.d_model))
        out, dt = engine.generate(batch, names, args.tokens)
        total += B * args.tokens
        t_total += dt
        mix = {n or "base": names.count(n) for n in dict.fromkeys(names)}
        print(f"[serve-mt] batch {step}: {mix} fused={engine.fused} "
              f"{B * args.tokens / dt:.1f} tok/s")
    print(f"[serve-mt] {total} tokens in {t_total*1e3:.0f}ms "
          f"({total / t_total:.1f} tok/s), "
          f"{engine.fuse_transitions} fused-state transitions")


def serve_continuous(cfg, params, packs, args) -> None:
    import tempfile

    from numpy.random import default_rng
    from repro.hub import AdapterStore, ServingEngine

    store = AdapterStore(tempfile.mkdtemp(prefix="adapter-store-"))
    for p in packs:
        store.add(p, values="int8" if args.int8 else "f32")
    slots = args.slots or args.batch
    engine = ServingEngine(
        cfg, params, slots=slots, store=store,
        table_dtype="int8" if args.int8 else "f32",
        cache_size=args.prompt_len + args.tokens + 8
        + (cfg.num_prefix_embeds if cfg.modality == "vision" else 0))
    rng = default_rng(0)
    futs = []
    for r in range(args.requests):
        name = tenant_mix(rng, packs, 1, args.skew)[0]
        toks = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(1), r),
                                  (args.prompt_len,), 0, cfg.vocab_size)
        futs.append(engine.submit(toks, name, max_tokens=args.tokens))
    dt = engine.run()
    done = sum(f.done() for f in futs)
    print(f"[serve-cc] {done}/{len(futs)} requests, {engine.tokens_out} "
          f"tokens in {dt*1e3:.0f}ms ({engine.tokens_out/dt:.1f} tok/s), "
          f"{engine.step_count} decode steps, idle-lane steps "
          f"{engine.decode_slot_waste}, store loads={store.loads} "
          f"resident={store.resident_bytes()/1e3:.1f}kB")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-780m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--adapters", type=int, default=2)
    ap.add_argument("--fuse", action="store_true",
                    help="serve with all adapters fused (multi-adapter)")
    ap.add_argument("--multi-tenant", action="store_true",
                    help="per-request adapters batched in one forward pass")
    ap.add_argument("--batches", type=int, default=4,
                    help="request batches to stream (multi-tenant)")
    ap.add_argument("--skew", type=float, default=0.5,
                    help="fraction of requests routed to adapter_0")
    ap.add_argument("--continuous", action="store_true",
                    help="request-level serving via repro.hub.ServingEngine")
    ap.add_argument("--requests", type=int, default=12,
                    help="requests to stream (continuous)")
    ap.add_argument("--slots", type=int, default=0,
                    help="decode lanes (continuous; 0 = --batch)")
    ap.add_argument("--int8", action="store_true",
                    help="int8 adapters: quantized store packs (continuous) "
                    "and int8 device-side delta tables (both paths)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit("encoder-only archs have no decode serving path")
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    packs = make_adapters(cfg, params, args.adapters, jax.random.PRNGKey(7),
                          multi_tenant=args.multi_tenant or args.continuous)
    if args.continuous:
        serve_continuous(cfg, params, packs, args)
        return
    if args.multi_tenant:
        serve_multi_tenant(cfg, params, packs, args)
        return
    engine = core.SwitchEngine(params)

    from repro.serving.multitenant import serving_cache_size
    cache_size = serving_cache_size(cfg, args.prompt_len, args.tokens)
    B = args.batch

    prefill_fn = jax.jit(lambda p, b: lm.prefill(p, cfg, b, cache_size))
    decode_fn = jax.jit(lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos))

    def serve_batch(params, label):
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, args.prompt_len),
                                  0, cfg.vocab_size)
        batch = {"tokens": toks}
        if cfg.modality == "vision":
            batch["patch_embeds"] = jnp.zeros(
                (B, cfg.num_prefix_embeds, cfg.d_model))
        t0 = time.perf_counter()
        logits, caches = prefill_fn(params, batch)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        outs = [nxt]
        pos = args.prompt_len + (cfg.num_prefix_embeds
                                 if cfg.modality == "vision" else 0)
        for i in range(args.tokens - 1):
            logits, caches = decode_fn(params, nxt, caches, pos + i)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            outs.append(nxt)
        jax.block_until_ready(outs[-1])
        dt = time.perf_counter() - t0
        tput = B * args.tokens / dt
        print(f"[serve] {label}: {B}x{args.tokens} tokens in {dt*1e3:.0f}ms "
              f"({tput:.1f} tok/s)")
        return jnp.concatenate(outs, axis=1)

    serve_batch(engine.params, "base model")
    if args.fuse:
        stats = engine.load_fused(packs)
        print(f"[serve] fused {len(packs)} adapters: "
              f"{sum(s.seconds for s in stats)*1e3:.1f}ms, "
              f"{sum(s.entries_written for s in stats)} entries")
        serve_batch(engine.params, "multi-adapter fused")
    else:
        for pack in packs:
            st = engine.switch(pack)
            print(f"[serve] switched to {pack.name}: {st.seconds*1e3:.1f}ms, "
                  f"{st.entries_written} entries "
                  f"({st.bytes_written/1e6:.2f}MB adapter vs "
                  f"{st.weight_bytes_total/1e6:.0f}MB weights)")
            serve_batch(engine.params, pack.name)


if __name__ == "__main__":
    main()
