"""SLO load bench: tail latency + goodput under Zipf/bursty/overload traffic.

The fixed-batch and continuous-batching benches measure throughput on
tidy traces. Production multi-adapter serving is judged on *tails*: what
p99 latency and TTFT look like when arrivals are bursty, adapter
popularity is Zipf, and an overload phase floods the queue. This bench
drives the paged engine (``repro.hub.PagedServingEngine``) with
``repro.serving.loadgen`` through a three-phase trace:

    normal -> overload (rate x ``--overload``) -> normal

Only the ``--hot`` Zipf-head adapters are pre-registered; the tail stays
on disk, so the trace carries real *cold* admissions (disk load + table
rebuild + H2D on first touch). The SAME generated schedule is replayed
twice — once through the synchronous path and once with the async
prefetch pipeline (``async_prefetch=True``) — and the cold-adapter TTFT
tail is compared head-to-head. The engines run with ``--slot-pad``
slot-capacity bucketing so a cold registration never changes the device
-table shapes (no prefill/decode recompile inside the measured trace).

Reported via the shared ``_emit`` schema so CI's tier3 gate can track
them (percentiles from ``_emit.percentiles`` — the same math every
latency lane quotes); all latency/throughput lanes come from the async
(measured) run, the sync run contributes the ``*_sync`` comparison
lanes:

  * ``p50/p95/p99_latency_ms`` — end-to-end submit -> final token
    (queue wait included; gate_max lanes in baseline.json)
  * ``p50/p99_ttft_ms`` — submit -> first token
  * ``p50/p99_ttft_cold_ms`` vs ``p99_ttft_cold_sync_ms`` — the cold
    -admission TTFT tail with and without the prefetch pipeline;
    ``p99_ttft_cold_ms`` is a gate_max lane
  * ``prefetch_hit_rate`` / ``prefetch_stall_ms`` — store prefetch
    outcomes and the stall time the pipeline failed to hide
  * ``tokens_per_s`` vs ``goodput_tok_s`` — raw throughput vs tokens from
    requests that met ``--slo-ms``; under overload these diverge, which
    is the number that matters
  * ``slo_violation_rate`` — fraction of completed requests over SLO
  * with ``--chaos``: ``goodput_under_faults`` (chaos-pass goodput as a
    fraction of the fault-free async run; gated floor) plus
    ``shed_rate`` / ``degraded_rate`` ceilings — the chaos pass replays
    the same trace under the seeded fault injector
    (``repro.runtime.faults``) and hard-asserts the chaos contract
    (every future terminal, every failure typed, goodput >= 70%)

``--trace PATH`` installs the serving tracer (``repro.analysis.trace``)
for the measured (async) run, writes the JSONL + Chrome exports, prints
the replay cost model's wall-time attribution and the
``replay.verify_overlap()`` check — how much of the predicted
disk-load/table-build hiding the pipeline actually realized (CI gates
this via ``benchmarks/check_replay.py``).

  PYTHONPATH=src python benchmarks/slo_load.py --smoke --json \
      --trace TRACE_slo_load.jsonl
"""
from __future__ import annotations

import argparse
import tempfile

import jax
import jax.numpy as jnp

import _emit
from repro.analysis import replay, trace
from repro.configs import get_config, get_smoke_config
from repro.hub import AdapterStore, PagedServingEngine
from repro.launch.serve import make_adapters
from repro.models import layers, lm
from repro.serving import loadgen


def build_serving(cfg, params, packs, args, async_mode: bool,
                  chaos: bool = False):
    """A fresh store + paged engine for one pass over the trace.

    Every pack is written to its own store; only the ``--hot`` Zipf-head
    adapters stay resident/registered — the tail is explicitly evicted
    back to the disk tier so its first touch is a true cold admission.
    The chaos pass additionally arms the NaN guard (so an injected
    poisoned slot is quarantined, not emitted as garbage)."""
    store = AdapterStore(tempfile.mkdtemp(prefix="cc-slo-store-"))
    for p in packs:
        store.add(p, values="f32")
    for p in packs[args.hot:]:
        store.evict(p.name)
    engine = PagedServingEngine(
        cfg, params, slots=args.slots, num_pages=args.num_pages,
        page_size=args.page_size, max_len=args.max_len,
        chunk_size=args.chunk_size, store=store,
        async_prefetch=async_mode, slot_pad=args.slot_pad,
        nan_guard=chaos)
    for p in packs[:args.hot]:
        engine.register(p.name)
    return store, engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--adapters", type=int, default=6)
    ap.add_argument("--hot", type=int, default=2,
                    help="Zipf-head adapters pre-registered (warm); the "
                    "rest are cold on first touch")
    ap.add_argument("--slot-pad", type=int, default=8,
                    help="table slot-capacity bucket (keep >= --adapters "
                    "+ 2 so cold admissions never recompile)")
    ap.add_argument("--num-pages", type=int, default=97)
    ap.add_argument("--page-size", type=int, default=2)
    ap.add_argument("--chunk-size", type=int, default=4)
    ap.add_argument("--duration", type=float, default=0.4,
                    help="seconds per traffic phase")
    ap.add_argument("--rate", type=float, default=25.0,
                    help="normal-phase arrival rate (requests/s)")
    ap.add_argument("--overload", type=float, default=8.0,
                    help="overload-phase rate multiplier")
    ap.add_argument("--burst", type=float, default=3.0,
                    help="arrival burstiness (1 = Poisson)")
    ap.add_argument("--zipf", type=float, default=1.1,
                    help="adapter-popularity Zipf exponent")
    ap.add_argument("--slo-ms", type=float, default=1500.0,
                    help="per-request end-to-end latency SLO")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos", action="store_true",
                    help="run a third pass over the SAME trace with the "
                    "fault injector installed (seeded 10%% disk failures, "
                    "injected I/O latency, payload corruption, worker "
                    "deaths, one poisoned slot) and gate goodput-under-"
                    "faults against the fault-free async run")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the serving trace of the async run "
                    "(JSONL) and print replay attribution + overlap "
                    "verification")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="write BENCH_slo_load.json (or PATH) with the "
                    "_emit schema")
    args = ap.parse_args()
    if not 0 < args.hot < args.adapters:
        raise SystemExit("need 0 < --hot < --adapters: the bench measures "
                         "warm AND cold admissions")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    prompt_hi = 12
    gen_max = 8
    args.max_len = args.page_size * (
        (4 + prompt_hi + gen_max) // args.page_size + 2)
    with layers.compute_precision(jnp.float32):
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        packs = make_adapters(cfg, params, args.adapters,
                              jax.random.PRNGKey(7), multi_tenant=True)

        gen = loadgen.LoadGen(
            adapters=[p.name for p in packs], vocab=cfg.vocab_size,
            seed=args.seed, zipf_s=args.zipf,
            phases=[loadgen.Phase(args.duration, args.rate, args.burst),
                    loadgen.Phase(args.duration, args.rate * args.overload,
                                  args.burst),
                    loadgen.Phase(args.duration, args.rate, args.burst)],
            prompt_len=(4, prompt_hi), max_tokens=(2, gen_max),
            shared_prefix=4)
        reqs = gen.schedule()

        if not reqs:
            raise SystemExit("trace generated zero arrivals — raise "
                             "--rate or --duration")

        reports = {}
        engines = {}
        tracer = None
        for mode in ("sync", "async"):
            store, engine = build_serving(cfg, params, packs, args,
                                          async_mode=(mode == "async"))
            # warmup: compile prefill/decode at the padded table capacity
            # and seed the prefix registry, exactly like steady-state
            # production — first-request compile time must not masquerade
            # as queueing latency (cold admissions inside the measured
            # trace reuse these shapes thanks to --slot-pad)
            for p in packs[:args.hot]:
                engine.submit(reqs[0].prompt[:4 + 1], p.name, max_tokens=1)
            engine.run()
            if mode == "async" and args.trace:
                tracer = trace.install()
            reports[mode] = loadgen.run(engine, reqs, slo_ms=args.slo_ms)
            if mode == "async" and tracer is not None:
                trace.uninstall()
            engine.shutdown(include_store=True)
            engines[mode] = (store, engine)

        # chaos pass: the same trace through an engine under injected
        # faults — the robustness twin of the async (measured) run
        chaos_rep, chaos_inj, chaos_engine = None, None, None
        if args.chaos:
            from repro.runtime import faults
            store_c, chaos_engine = build_serving(cfg, params, packs, args,
                                                  async_mode=True,
                                                  chaos=True)
            for p in packs[:args.hot]:
                chaos_engine.submit(reqs[0].prompt[:4 + 1], p.name,
                                    max_tokens=1)
            chaos_engine.run()          # compile outside the fault window
            chaos_inj = faults.install(faults.FaultPlan(
                seed=args.seed, disk_fail_p=0.10, io_latency_s=0.002,
                corrupt_p=0.05, worker_death_p=0.05,
                poison_step=chaos_engine.step_count + 8, poison_slot=0))
            try:
                chaos_rep = loadgen.run(
                    chaos_engine, reqs, slo_ms=args.slo_ms,
                    deadline_s=4.0 * args.slo_ms / 1e3)
            finally:
                faults.uninstall()
            chaos_engine.shutdown(include_store=True)

    rep = reports["async"]          # the measured run: all primary lanes
    rep_sync = reports["sync"]
    store, engine = engines["async"]

    per_phase = {pi: len(v) for pi, v in
                 sorted(rep.per_phase_latencies_ms.items())}
    print(f"arch={cfg.name} slots={args.slots} adapters={args.adapters} "
          f"(hot {args.hot}) pages={args.num_pages}x{args.page_size} "
          f"slot_pad={args.slot_pad}")
    print(f"offered {rep.offered} requests over "
          f"{3 * args.duration:.1f}s of trace (per phase: {per_phase}); "
          f"completed {rep.completed} in {rep.wall_s:.2f}s wall, "
          f"{rep.steps} steps")
    lat = _emit.percentiles(rep.latencies_ms, (50, 95, 99), "latency_ms")
    ttft = _emit.percentiles(rep.ttfts_ms, (50, 99), "ttft_ms")
    print(f"latency p50/p95/p99: {lat['p50_latency_ms']:.1f} / "
          f"{lat['p95_latency_ms']:.1f} / {lat['p99_latency_ms']:.1f} ms   "
          f"TTFT p50/p99: {ttft['p50_ttft_ms']:.1f} / "
          f"{ttft['p99_ttft_ms']:.1f} ms")
    print(f"throughput {rep.tokens_per_s:.1f} tok/s; goodput "
          f"(SLO {args.slo_ms:.0f}ms) {rep.goodput_tok_s:.1f} tok/s; "
          f"violations {rep.slo_violation_rate:.1%}")
    print(f"paged: {engine.prefill_chunks} prefill chunks, "
          f"{engine.pool.prefix_hits} prefix hits, "
          f"{engine.pool.cow_copies} COW copies")

    # cold-admission comparison: same schedule, sync vs async pipeline
    if not rep.ttfts_cold_ms or not rep_sync.ttfts_cold_ms:
        raise SystemExit("trace produced no cold admissions — lower --hot "
                         "or raise --duration/--rate")
    cold = _emit.percentiles(rep.ttfts_cold_ms, (50, 99), "ttft_cold_ms")
    cold_sync = _emit.percentiles(rep_sync.ttfts_cold_ms, (50, 99),
                                  "ttft_cold_ms", "_sync")
    hits, misses = store.prefetch_hits, store.prefetch_misses
    hit_rate = hits / max(hits + misses, 1)
    mt = engine.engine
    print(f"cold admissions: {len(rep.ttfts_cold_ms)} async / "
          f"{len(rep_sync.ttfts_cold_ms)} sync of {rep.offered} requests")
    print(f"cold TTFT p50/p99: async {cold['p50_ttft_cold_ms']:.1f} / "
          f"{cold['p99_ttft_cold_ms']:.1f} ms   sync "
          f"{cold_sync['p50_ttft_cold_ms_sync']:.1f} / "
          f"{cold_sync['p99_ttft_cold_ms_sync']:.1f} ms   "
          f"(p99 gain {cold_sync['p99_ttft_cold_ms_sync'] / max(cold['p99_ttft_cold_ms'], 1e-9):.2f}x)")
    print(f"prefetch: {hits} hits / {misses} misses "
          f"(hit rate {hit_rate:.1%}); table builds: "
          f"{mt.async_builds} kicked, {mt.async_adopted} adopted, "
          f"{mt.async_stale} stale")

    assert rep.completed == rep.offered, \
        f"dropped requests: {rep.completed}/{rep.offered}"
    assert rep_sync.completed == rep_sync.offered, \
        f"sync pass dropped requests: {rep_sync.completed}/{rep_sync.offered}"

    goodput_under_faults = None
    if chaos_rep is not None:
        goodput_under_faults = (chaos_rep.goodput_tok_s
                                / max(rep.goodput_tok_s, 1e-9))
        health = chaos_engine.health()
        print(f"chaos: completed {chaos_rep.completed}/{chaos_rep.offered} "
              f"(failed {chaos_rep.failed}, shed {chaos_rep.shed}, "
              f"degraded {chaos_rep.degraded}); goodput "
              f"{chaos_rep.goodput_tok_s:.1f} tok/s = "
              f"{goodput_under_faults:.1%} of fault-free; injected "
              f"{chaos_inj.counts}; errors {chaos_rep.errors_by_type}; "
              f"quarantined {health['quarantined']}")
        # the chaos contract (zero unhandled exceptions is implied by
        # reaching this line: loadgen.run drives step() bare)
        assert chaos_rep.completed + chaos_rep.failed == chaos_rep.offered, \
            (f"untracked requests under faults: {chaos_rep.completed} + "
             f"{chaos_rep.failed} != {chaos_rep.offered}")
        typed = {"StoreError", "AdapterUnavailable", "RequestShed",
                 "SlotPoisoned", "TableBuildError"}
        untyped = set(chaos_rep.errors_by_type) - typed
        assert not untyped, f"untyped failures under faults: {untyped}"
        assert goodput_under_faults >= 0.70, \
            (f"goodput under faults {goodput_under_faults:.1%} < 70% of "
             f"the fault-free run")

    stall_ms = 0.0
    realized = None
    if tracer is not None:
        events = list(tracer.events())
        stall_ms = sum(e.get("dur", 0.0) for e in events
                       if e.get("ph") == "X"
                       and e.get("name") == "prefetch.stall") / 1e3
        jsonl = tracer.to_jsonl(args.trace)
        att = replay.attribute(tracer, wall_us=rep.wall_s * 1e6)
        print(f"trace: {len(tracer)} events -> {jsonl}; "
              f"spans cover {att['coverage']:.1%} of wall")
        for row in replay.critical_path(tracer, top=5):
            print(f"  {row['name']:<16} {row['self_us'] / 1e3:9.2f} ms "
                  f"({row['frac']:.1%})")
        vo = replay.verify_overlap(events)
        realized = vo["realized_frac"]
        print(f"overlap: {vo['async_spans']} worker spans, "
              f"{vo['async_us'] / 1e3:.1f} ms async work; hidden "
              f"{vo['measured_hidden_us'] / 1e3:.1f} of "
              f"{vo['predicted_hidden_us'] / 1e3:.1f} ms predicted "
              f"({realized:.1%} realized); stalls {stall_ms:.1f} ms")

    if args.json is not None:
        metrics = {
            **lat, **ttft, **cold, **cold_sync,
            "tokens_per_s": rep.tokens_per_s,
            "goodput_tok_s": rep.goodput_tok_s,
            "slo_violation_rate": rep.slo_violation_rate,
            "completed": rep.completed,
            "offered": rep.offered,
            "steps": rep.steps,
            "cold_requests": len(rep.ttfts_cold_ms),
            "cold_ttft_p99_gain": (cold_sync["p99_ttft_cold_ms_sync"]
                                   / max(cold["p99_ttft_cold_ms"], 1e-9)),
            "prefetch_hit_rate": hit_rate,
            "prefetch_stall_ms": stall_ms,
            "async_builds": mt.async_builds,
            "async_adopted": mt.async_adopted,
            "prefix_hits": engine.pool.prefix_hits,
            "cow_copies": engine.pool.cow_copies,
        }
        if realized is not None:
            metrics["overlap_realized_frac"] = realized
        if chaos_rep is not None:
            metrics["goodput_under_faults"] = goodput_under_faults
            metrics["shed_rate"] = chaos_rep.shed_rate
            metrics["degraded_rate"] = chaos_rep.degraded_rate
            metrics["chaos_completed"] = chaos_rep.completed
            metrics["chaos_failed"] = chaos_rep.failed
            metrics["chaos_shed"] = chaos_rep.shed
            metrics["chaos_degraded"] = chaos_rep.degraded
        res = _emit.result(
            "slo_load", cfg.name,
            metrics=metrics,
            meta={"smoke": args.smoke, "slots": args.slots,
                  "adapters": args.adapters, "hot": args.hot,
                  "slot_pad": args.slot_pad, "seed": args.seed,
                  "slo_ms": args.slo_ms, "rate": args.rate,
                  "overload": args.overload, "burst": args.burst,
                  "zipf": args.zipf, "duration": args.duration,
                  "num_pages": args.num_pages, "page_size": args.page_size,
                  "trace": args.trace,
                  "chaos_injected": (dict(chaos_inj.counts)
                                     if chaos_inj is not None else None),
                  "chaos_errors": (dict(chaos_rep.errors_by_type)
                                   if chaos_rep is not None else None)})
        print(f"wrote {_emit.emit(res, args.json or None)}")


if __name__ == "__main__":
    main()
