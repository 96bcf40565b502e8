"""Closed-loop multi-tenant serving through ``hub.PagedServingEngine``.

Each of the mix's ``clients`` holds one request at a time and submits its
next as soon as the last one finishes; there are as many engine slots as
clients. Every adapter is registered with the engine's side-delta tables
(no fused adapter), so each request is served by its own SHiRA pack
through the ``sidedelta`` kernel, in chunked prefill and in decode, over
the paged KV pool.

The loop is observed in its steady state: each client's first request
stands for one already in progress, with a share of its drawn output
still to come (``traffic.in_progress``), so that completions, and the
prefill chunks of the requests that follow, are spread over the window
rather than bunched at its end. Set-up fills every slot (each client's
first request prefilled to its first token) before the window opens.

Inside the window the harness stamps the end of every engine step; a
token's time is the end of the step that emitted it. Once ``--seconds``
have passed no new step starts. To have requests to compare, the engine
then runs on, unmeasured and with no new requests, until the finished
requests hold ``check_tokens`` served tokens and a request submitted
inside the window has served its first ``check_window_tokens`` tokens (or
all of them, if it asks for fewer), or until ``drain_s`` seconds have
passed. That request was admitted to a recycled slot and freed pages,
chunk by chunk beside live decodes: the loop's steady state, which the
first requests, prefilled in set-up into fresh slots, do not show. The
one of them that served the most tokens is compared with the others,
finished or not; if none served a token, nothing came for it, and
``correct`` reads false.
"""
from __future__ import annotations

import gc
import math
import time
from typing import List

import jax
import numpy as np

import harness as H
import roofline as R
import traffic
import weights as W


class _Client:
    """One client's request in flight and the times of its tokens."""

    def __init__(self, fut, req):
        self.fut, self.req = fut, req
        self.times: List[float] = []


def run(r: H.Run) -> H.Result:
    from repro.hub import PagedServingEngine
    from repro.serving.kvcache import pages_for

    cfg, mix = r.config, r.mix
    mc, params, packs = H.model(r, host_packs=True)
    sw = H.Stopwatch(r.setup)

    slots, page = mix["clients"], mix["page_size"]
    max_len = mix["prompt"]["max"] + mix["output"]["max"]
    eng = PagedServingEngine(
        mc, params, slots=slots, page_size=page, max_len=max_len,
        num_pages=slots * pages_for(max_len, page) + 2,
        chunk_size=mix["chunk_size"], table_dtype=mix["table_dtype"])
    del params
    for p in packs:
        eng.register(p)
    names = [p.name for p in packs]
    del packs
    jax.block_until_ready(
        eng.engine.wrapped_params(eng.engine.ids_for(names)))
    sw.lap("tables_s")

    chunk_log: List[tuple] = []
    if r.trace:
        _instrument(r, eng, chunk_log)

    gen = traffic.Traffic(mix, cfg["vocab_size"], r.seed)
    clients: List[_Client] = []
    history: List[_Client] = []          # every request, for the gaps
    served: List[H.Served] = []
    failed = 0

    def submit(slot: int, share: float = 1.0) -> None:
        req = gen.request()
        req.max_tokens = max(1, math.ceil(share * req.max_tokens))
        cl = _Client(eng.submit(req.prompt, f"a{req.adapter}",
                                max_tokens=req.max_tokens), req)
        history.append(cl)
        if slot < len(clients):
            clients[slot] = cl
        else:
            clients.append(cl)

    def step(resubmit: bool = True):
        """One engine step. Returns its end time and the (client, token
        index) of every token it emitted."""
        nonlocal failed
        eng.step()
        t = time.perf_counter()
        emitted = []
        for slot, cl in enumerate(list(clients)):
            if cl is None:
                continue
            for j in range(len(cl.times), len(cl.fut.tokens)):
                emitted.append((cl, j))
                cl.times.append(t)
            if cl.fut.done():
                if cl.fut.error is not None:
                    failed += 1
                else:
                    served.append(H.Served(
                        cl.req.prompt, np.asarray(cl.fut.tokens, np.int32),
                        cl.req.adapter))
                if resubmit:
                    submit(slot)
                else:
                    clients[slot] = None
        clients[:] = [cl for cl in clients if cl is not None]
        return t, emitted

    for slot, share in enumerate(gen.in_progress(slots)):
        submit(slot, share)
    # fill every slot; a step that ran a program for the first time
    # (a compile, or a load from the compile cache) counts as warm-up
    ran_decode = ran_prefill = False
    while not all(cl.times for cl in clients):
        t0 = time.perf_counter()
        decodes = any(cl.times for cl in clients)
        step()
        phase = ("warm_s" if not ran_prefill or (decodes and not ran_decode)
                 else "fill_s")
        ran_prefill, ran_decode = True, ran_decode or decodes
        r.setup[phase] = r.setup.get(phase, 0.0) + time.perf_counter() - t0

    shapes = [(leaf.shape[0], leaf.shape[1], W.nnz(leaf, mix["density"]))
              for leaf in W.pack_leaves(cfg, mix["targets"])]
    layer_nnz = sum(k for _, _, k in shapes)
    calls, model_flops = [], 0.0
    tokens, steps = 0, 0
    first_in_window = len(history)
    with r.window():
        t_start = t_end = time.perf_counter()
        r.setup["setup_s"] = t_start - r.t_process
        while time.perf_counter() - t_start < r.seconds:
            n_chunks = len(chunk_log)
            with r.annotate("bench.engine_step"):
                t_end, emitted = step()
            tokens += len(emitted)
            steps += 1
            if r.trace:
                f, c = _step_work(cfg, layer_nnz, shapes, emitted,
                                  chunk_log[n_chunks:])
                model_flops += f
                calls += c
    itl = [cl.times[j] - cl.times[j - 1] for cl in history
           for j in range(1, len(cl.times)) if t_start < cl.times[j] <= t_end]
    in_window = history[first_in_window:]

    def drained() -> bool:
        finished = sum(len(s.tokens) for s in served)
        return finished >= mix["check_tokens"] and (not in_window or any(
            len(cl.fut.tokens) >= min(cl.req.max_tokens,
                                      mix["check_window_tokens"])
            for cl in in_window))

    t_drain = time.perf_counter()
    while (clients and not drained()
           and time.perf_counter() - t_drain < mix["drain_s"]):
        step(resubmit=False)
    drain_s = time.perf_counter() - t_drain
    late = max((H.Served(cl.req.prompt, np.asarray(cl.fut.tokens, np.int32),
                         cl.req.adapter) for cl in in_window),
               key=lambda s: len(s.tokens), default=None)
    peak = H.memory_peak_bytes()
    attempted = len(history)
    del eng, clients, history, in_window
    gc.collect()        # free the program's device buffers

    sample = H.sample(served, r.seed, mix["check_tokens"])
    if late is not None and not any(s.prompt is late.prompt for s in sample):
        sample.append(late)
    gaps = H.logit_gaps(cfg, mix, r.seed, sample, control=r.control)
    return H.Result(
        attempted=attempted, failed=failed,
        end_to_end={"tokens_per_s": tokens / (t_end - t_start),
                    "itl_p95_ms": np.percentile(itl, 95) * 1e3},
        checks={"logit_gap": gaps["logit_gap"]},
        control_checks=({"logit_gap": gaps["control_gap"]} if r.control
                        else {}),
        counters={"sidedelta_calls": calls, "model_flops": model_flops},
        memory_peak_bytes=peak,
        notes={"window_s": t_end - t_start, "steps": steps, "drain_s": drain_s,
               "tokens": tokens, "itl_samples": len(itl),
               "finished": len(served), "compared_requests": gaps["requests"],
               "compared_tokens": gaps["tokens"],
               "window_request_tokens": late and len(late.tokens)})


def _step_work(cfg, layer_nnz, shapes, emitted, chunks):
    """The operation one engine step did: model FLOPs, and one (flops,
    bytes) side-delta call per adapted matrix per layer and per call."""
    L = cfg["num_layers"]
    flops, calls = 0.0, []
    decode = [(cl, j) for cl, j in emitted if j > 0]
    adapted = [cl.req.adapter for cl, _ in decode]
    for cl, j in decode:
        flops += R.token_flops(cfg, len(cl.req.prompt) + j, True, layer_nnz)
    if adapted:
        for n, m, k in shapes:
            calls += [R.sidedelta_call(len(adapted), n, m, k,
                                       len(set(adapted)))] * L
    final = any(j == 0 for _, j in emitted)
    for start, valid in chunks:
        flops += R.prefill_flops(cfg, start, start + valid, final, layer_nnz)
        for n, m, k in shapes:
            calls += [R.sidedelta_call(valid, n, m, k, 1)] * L
    return flops, calls


def _instrument(r, eng, chunk_log) -> None:
    """Traced runs only: mark each prefill chunk on the profiler's clock
    and wait for it inside the mark, so that its device time can be told
    from the decode's; log its start and valid length."""
    inner = eng._prefill_chunk

    def prefill_chunk(p, toks, c, bt, start, valid):
        with r.annotate("bench.prefill_chunk"):
            out = jax.block_until_ready(inner(p, toks, c, bt, start, valid))
        chunk_log.append((int(start), int(valid)))
        return out

    eng._prefill_chunk = prefill_chunk
