"""Drive the SHiRA serving path once on one TPU chip, at published widths.

    python chip_smoke.py

Model: starcoder2-7b at its published widths (d_model 4608, 36 heads of
128, 4 KV heads, d_ff 18432, vocab 49152), cut in depth to 4 of its 32
layers so that the f32 weights (about 5.3 GB) plus one switched copy of
the adapted matrices fit the chip's 16 GB. Weights and the three SHiRA
packs (2% density, ``launch/serve.py:make_adapters``) are random, from
fixed PRNG keys.

Phases, in order, each printed with its seconds, tokens and the device's
peak bytes in use so far:

  (c) sequential rapid switching through ``core.SwitchEngine``: the base
      model, then each pack switched in, then all packs fused;
  (d) one mixed-adapter batch through ``serving.MultiTenantEngine``;
  (e) mixed-adapter requests through ``hub.PagedServingEngine``;
  (f) agreement: f32 decode logits of the switched (fused-into-weights)
      path against the side-delta path, same adapter and prompt;
  (g) the compiled decode step of (d) holds the Mosaic kernel
      (``tpu_custom_call``), so neither the Pallas interpreter nor the
      plain jnp reference of ``sidedelta`` served on the chip.

Without a TPU the script exits nonzero before any phase; any phase that
fails exits nonzero. On success the last line of standard output is one
JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PROMPT = 128            # prompt tokens: every prefill runs at S = 128
TOKENS = 8              # generated tokens per request
BATCH = 4               # requests per batch / engine slots
LAYERS = 4              # depth cut (published: 32)
PAGE = 16               # paged engine: tokens per KV page
CHUNK = 64              # paged engine: prefill chunk (two per prompt)
# (f): both paths evaluate the same f32 math in a different order
# (x @ (W + dW) against x @ W + x @ dW). Accumulated f32 rounding over four
# layers stays orders below this; a missing or misrouted adapter moves the
# logits by the adapter's own effect, which must stay far above it.
AGREE_RTOL = 1e-3


def _peak() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _report(phase: str, **fields) -> None:
    parts = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {parts} peak_bytes_in_use={_peak()}", flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def run(cfg, prompt_len: int = PROMPT, tokens: int = TOKENS,
        batch: int = BATCH) -> str:
    """Phases (b)-(f) on the default device. Returns the compiled text of
    the multi-tenant decode step, for (g)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import core
    from repro.hub import PagedServingEngine
    from repro.launch.serve import make_adapters
    from repro.models import layers, lm
    from repro.serving import MultiTenantEngine
    from repro.serving.multitenant import greedy_decode, serving_cache_size

    # (b) model and packs
    params, dt = _timed(lambda: jax.block_until_ready(
        lm.init_params(cfg, jax.random.PRNGKey(0))))
    packs, dt_p = _timed(lambda: make_adapters(
        cfg, params, 3, jax.random.PRNGKey(7), multi_tenant=True))
    jax.block_until_ready([p.entries for p in packs])
    nnz = sum(p.num_params() for p in packs)
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit", -1)
    _report("b:init", layers=cfg.num_layers, d_model=cfg.d_model,
            d_ff=cfg.d_ff, params_s=f"{dt:.2f}", packs_s=f"{dt_p:.2f}",
            pack_nonzeros=nnz, bytes_limit=limit)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len), 0,
                              cfg.vocab_size)
    batch_in = {"tokens": toks}
    tok_spec = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    cs = serving_cache_size(cfg, prompt_len, tokens)

    def compile_step(lower_prefill, decode, p):
        """Ahead-of-time compile of one engine's prefill and decode step."""
        pre = lower_prefill(p)
        _, caches = pre.out_info
        return (pre.compile(),
                decode.lower(p, tok_spec, caches, prompt_len).compile())

    # (c) sequential rapid switching, as launch/serve.py does it
    sw = core.SwitchEngine(params)
    prefill_jit = jax.jit(lambda p, b: lm.prefill(p, cfg, b, cs))
    (prefill, decode), dt = _timed(lambda: compile_step(
        lambda p: prefill_jit.lower(p, batch_in),
        jax.jit(lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos)),
        sw.params))
    _report("c:compile", prefill_and_decode_s=f"{dt:.2f}")

    def serve(p):
        out, _ = greedy_decode(cfg, batch_in, tokens,
                               lambda b: prefill(p, b),
                               lambda t, c, pos: decode(p, t, c, pos))
        return np.asarray(out)

    base_out, dt = _timed(lambda: serve(sw.params))
    _report("c:switch base", run_s=f"{dt:.2f}", tokens=batch * tokens)
    outs = {}
    for pack in packs:
        st = sw.switch(pack)
        outs[pack.name], dt = _timed(lambda: serve(sw.params))
        _report(f"c:switch {pack.name}", switch_s=f"{st.seconds:.3f}",
                entries=st.entries_written, run_s=f"{dt:.2f}",
                tokens=batch * tokens)
    while sw.active:
        sw.unload()
    stats = sw.load_fused(packs)
    fused_out, dt = _timed(lambda: serve(sw.params))
    # how many served outputs the adapters changed (phase (f) checks the
    # adapters' effect on the logits)
    changed = sum(not np.array_equal(o, base_out)
                  for o in [*outs.values(), fused_out])
    _report("c:switch fused", load_s=f"{sum(s.seconds for s in stats):.3f}",
            run_s=f"{dt:.2f}", tokens=batch * tokens,
            outputs_changed_vs_base=f"{changed}/{len(outs) + 1}")
    del sw

    # (d) one mixed-adapter batch, side deltas in one forward pass
    names = [p.name for p in packs] + [None] * (batch - len(packs))
    names = names[:batch]
    mt = MultiTenantEngine(cfg, params)
    for p in packs:
        mt.register(p)
    p, dt = _timed(lambda: jax.block_until_ready(
        mt.wrapped_params(mt.ids_for(names))))
    (_, decode), dt_c = _timed(lambda: compile_step(
        lambda p: mt._prefill.lower(p, batch_in, cs), mt._decode, p))
    decode_text = decode.as_text()      # for (g)
    _report("d:compile", tables_s=f"{dt:.2f}",
            prefill_and_decode_s=f"{dt_c:.2f}")
    _, first = mt.generate(batch_in, names, tokens)
    mt_out, run_s = mt.generate(batch_in, names, tokens)
    mt_out = np.asarray(mt_out)
    _report("d:multi-tenant", first_run_s=f"{first:.2f}",
            run_s=f"{run_s:.2f}", tokens=batch * tokens,
            mix="+".join(n or "base" for n in names))
    if mt_out.shape != (batch, tokens):
        raise AssertionError(f"multi-tenant output shape {mt_out.shape}")
    del mt, p, decode

    # (e) request-level serving over the paged KV pool
    pages = batch * -(-(prompt_len + tokens) // PAGE) + 2
    pe = PagedServingEngine(cfg, params, slots=batch, num_pages=pages,
                            page_size=PAGE, max_len=prompt_len + tokens,
                            chunk_size=CHUNK)
    for p in packs:
        pe.register(p)
    rows = np.asarray(toks)
    warmup = pe.submit(rows[0], packs[0].name, max_tokens=tokens)
    first = pe.run()
    if not warmup.done():
        raise AssertionError("paged warm-up request did not finish")
    futs = [pe.submit(rows[i], names[i], max_tokens=tokens)
            for i in range(batch)]
    warm = pe.run()
    out = [np.asarray(f.result()) for f in futs]
    if not all(f.done() for f in futs) or any(o.shape != (tokens,)
                                             for o in out):
        raise AssertionError("paged requests did not all finish")
    _report("e:paged", warmup_s=f"{first:.2f}", run_s=f"{warm:.2f}",
            requests=len(futs), tokens=sum(o.size for o in out),
            steps=pe.step_count, prefill_chunks=pe.prefill_chunks)
    del pe

    # (f) agreement: fused-into-weights against side-delta, f32 throughout
    pack = packs[0]
    one = {"tokens": toks[:1]}
    with layers.compute_precision(jnp.float32), \
            jax.default_matmul_precision("highest"):
        f_prefill = jax.jit(lambda p, b: lm.prefill(p, cfg, b, cs))
        f_decode = jax.jit(
            lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos))

        def switched(p):
            lg, c = f_prefill(p, one)
            t = jnp.argmax(lg, -1).astype(jnp.int32)[:, None]
            return t, np.asarray(f_decode(p, t, c, prompt_len)[0])

        sw = core.SwitchEngine(params)
        (base_logits, dt_base) = _timed(lambda: switched(sw.params)[1])
        sw.load(pack)
        (tok, sw_logits), dt_sw = _timed(lambda: switched(sw.params))
        del sw
        sd = MultiTenantEngine(cfg, params)
        sd.register(pack)
        p = sd.wrapped_params(sd.ids_for([pack.name]))

        def side():
            _, c = sd._prefill(p, one, cs)
            return np.asarray(sd._decode(p, tok, c, prompt_len)[0])

        sd_logits, dt_sd = _timed(side)
        del sd, p
    scale = float(np.max(np.abs(sw_logits)))
    err = float(np.max(np.abs(sd_logits - sw_logits)))
    effect = float(np.max(np.abs(sw_logits - base_logits)))
    _report("f:agreement", max_abs_err=f"{err:.3e}",
            adapter_effect=f"{effect:.3e}", logit_scale=f"{scale:.3e}",
            rtol=AGREE_RTOL, switched_s=f"{dt_sw:.2f}",
            side_delta_s=f"{dt_sd:.2f}")
    if not err <= AGREE_RTOL * scale:
        raise AssertionError(f"side-delta and switched logits differ by "
                             f"{err:.3e} > {AGREE_RTOL} x {scale:.3e}")
    if not effect > 10 * AGREE_RTOL * scale:
        raise AssertionError(f"adapter moved the logits by only "
                             f"{effect:.3e}: the check cannot tell")
    return decode_text


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device {dev.device_kind} x{len(jax.devices())}, "
          f"compile cache {cache}", flush=True)
    # published widths, depth cut from 32 to 4 layers (see module doc)
    cfg = get_config("starcoder2-7b").replace(num_layers=LAYERS)
    t0 = time.perf_counter()
    decode_text = run(cfg)
    # (g) the Mosaic kernel, not the interpreter or the jnp reference
    if "tpu_custom_call" not in decode_text:
        raise AssertionError("the multi-tenant decode step holds no "
                             "tpu_custom_call: sidedelta did not compile "
                             "through Mosaic")
    _report("g:kernel", tpu_custom_call=True,
            total_s=f"{time.perf_counter() - t0:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
