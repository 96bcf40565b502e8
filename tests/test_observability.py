"""Trace-driven observability: tracer, replay cost model, autotune cache,
load generator (src/repro/analysis/{trace,replay,autotune}.py +
src/repro/serving/loadgen.py).

The contracts pinned here are the subsystem's acceptance criteria:

  * tracing OFF is a no-op — the hooks return a shared null singleton and
    the per-call cost is far under 1% of a decode step;
  * a traced paged-engine run decomposes: >= 90% of run() wall time lands
    in spans and ``step_timeline`` reproduces the scheduling loop;
  * ``plan_tiles`` consults the autotune cache — a cache hit changes the
    chosen kc with kernel parity intact, an over-budget cached plan is
    rejected back to the static heuristic;
  * ``plan_tiles`` never plans over the VMEM budget (property test);
  * the load generator is deterministic per seed and drives the paged
    engine to completion with wall-clock latency/TTFT stamps.
"""
import importlib
import json
import os
import time
import timeit

import numpy as np
import pytest

from repro.analysis import autotune, replay, trace

SD = importlib.import_module("repro.kernels.sidedelta")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracer_and_cache():
    trace.uninstall()
    SD.clear_plan_cache()
    yield
    trace.uninstall()
    SD.clear_plan_cache()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_args():
    tr = trace.install()
    with trace.span("outer", cat="t", a=1):
        time.sleep(0.001)
        with trace.span("inner", cat="t") as sp:
            sp.set(found=42)
        trace.instant("marker", cat="t", note="x")
        trace.counter("gauge", 7.0, cat="t")
    evs = tr.events()
    by = {e["name"]: e for e in evs}
    assert by["outer"]["depth"] == 0 and by["inner"]["depth"] == 1
    assert by["outer"]["args"] == {"a": 1}
    assert by["inner"]["args"] == {"found": 42}
    assert by["marker"]["ph"] == "i" and by["gauge"]["ph"] == "C"
    assert by["gauge"]["args"]["value"] == 7.0
    # inner nests inside outer's interval
    o, i = by["outer"], by["inner"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    assert o["dur"] >= 1000.0          # the 1ms sleep, in microseconds


def test_ring_buffer_drops_oldest():
    tr = trace.install(trace.Tracer(capacity=4))
    for k in range(10):
        trace.instant(f"e{k}")
    assert len(tr) == 4 and tr.dropped == 6
    assert [e["name"] for e in tr.events()] == ["e6", "e7", "e8", "e9"]


def test_jsonl_and_chrome_export(tmp_path):
    """The JSONL export round-trips every event through the replay loader
    (Perfetto reads the profiler's trace, which carries the same spans)."""
    tr = trace.install()
    with trace.span("work", cat="c", k=1):
        trace.instant("tick")
    jsonl = tr.to_jsonl(str(tmp_path / "t.jsonl"))
    loaded = replay.load_trace(jsonl)
    assert [e["name"] for e in loaded] == [e["name"] for e in tr.events()]
    phs = {e["name"]: e for e in loaded}
    assert phs["work"]["ph"] == "X" and phs["work"]["dur"] >= 0
    assert phs["work"]["cat"] == "c" and phs["work"]["args"] == {"k": 1}
    assert phs["tick"]["ph"] == "i" and phs["tick"]["depth"] == 1
    assert [json.loads(line)["name"] for line in open(jsonl)] == \
        ["work", "tick"]


def test_disabled_tracing_is_noop():
    assert not trace.enabled()
    s1 = trace.span("x", arg=1)
    s2 = trace.span("y")
    assert s1 is s2                        # shared null singleton: no alloc
    with s1 as s:
        assert s.set(a=1) is s             # .set is a no-op, chains fine
    trace.instant("z")
    trace.counter("c", 1.0)
    tr = trace.install()
    assert len(tr) == 0                    # nothing leaked into the buffer


def test_disabled_call_cost_is_negligible():
    """The acceptance bar is <1% overhead on a traced workload with
    tracing off. A decode step is milliseconds; pin the disabled hook at
    <20us/call (it is ~100ns — the bound is generous to stay unflaky)."""
    assert not trace.enabled()

    def hot():
        with trace.span("step", engine="paged"):
            pass

    per_call = min(timeit.repeat(hot, number=1000, repeat=5)) / 1000
    assert per_call < 20e-6, f"disabled span() costs {per_call * 1e6:.1f}us"


# ---------------------------------------------------------------------------
# Replay cost model (synthetic traces)
# ---------------------------------------------------------------------------

def _ev(name, ts, dur, depth=0, cat="serving", **args):
    return {"ph": "X", "name": name, "cat": cat, "ts": ts, "dur": dur,
            "depth": depth, "args": args}


def test_attribute_self_time_and_coverage():
    # step [0, 100) wraps decode [10, 70): decode self 60, step self 40
    evs = [_ev("step", 0, 100, step=1), _ev("decode", 10, 60, depth=1)]
    att = replay.attribute(evs, wall_us=200.0)
    assert att["by_name"]["step"] == pytest.approx(40.0)
    assert att["by_name"]["decode"] == pytest.approx(60.0)
    assert att["coverage"] == pytest.approx(0.5)   # 100 of 200 top-level
    ranked = replay.critical_path(evs)
    assert ranked[0]["name"] == "decode"
    assert ranked[0]["frac"] == pytest.approx(0.6)


def test_step_timeline_reconstructs_loop():
    evs = [_ev("step", 0, 100, step=1), _ev("decode", 10, 60, depth=1),
           _ev("step", 120, 80, step=2), _ev("prefill_chunk", 125, 30,
                                             depth=1)]
    tl = replay.step_timeline(evs)
    assert [r["step"] for r in tl] == [1, 2]
    assert tl[0]["phases"] == {"decode": 60}
    assert tl[1]["phases"] == {"prefill_chunk": 30}


def test_what_if_overlap_and_scale():
    # 100us wall: decode self 60, table_rebuild self 30, 10 uncovered
    evs = [_ev("decode", 0, 60), _ev("table_rebuild", 60, 30)]
    wi = replay.what_if(evs, overlap=("table_rebuild",), under="decode",
                        wall_us=100.0)
    # rebuild (30) hides fully under decode (60): 100 -> 70
    assert wi["baseline_us"] == pytest.approx(100.0)
    assert wi["hidden_us"] == pytest.approx(30.0)
    assert wi["replayed_us"] == pytest.approx(70.0)
    # a 2x faster decode: 60 -> 30, hiding capped at the new budget
    wi = replay.what_if(evs, overlap=("table_rebuild",), under="decode",
                        scale={"decode": 0.5}, wall_us=100.0)
    assert wi["replayed_us"] == pytest.approx(30 + 30 + 10 - 30)
    # nothing hidden without overlap: only scaling applies
    wi = replay.what_if(evs, scale={"decode": 0.5}, wall_us=100.0)
    assert wi["replayed_us"] == pytest.approx(70.0)
    assert wi["speedup"] == pytest.approx(100.0 / 70.0)


def test_join_costs_roofline_ratio():
    from repro.analysis.roofline import HW
    hw = HW()
    evs = [_ev("decode", 0, 1000), _ev("decode", 1000, 3000)]
    cost = {"flops": 1e9, "bytes_accessed": 1e6}
    out = replay.join_costs(evs, {"decode": cost}, hw)["decode"]
    assert out["count"] == 2 and out["measured_us_mean"] == 2000
    model = max(1e9 / hw.peak_flops, 1e6 / hw.hbm_bw) * 1e6
    assert out["model_us"] == pytest.approx(model)
    assert out["ratio"] == pytest.approx(2000 / model)


# ---------------------------------------------------------------------------
# Autotune plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_hit_changes_plan_with_parity():
    """plan_tiles consults the cache; a hit changes the chosen kc and the
    kernel's output over tables laid out under either plan agrees."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from sidedelta_tables import sidedelta as raw_sidedelta
    S, n, m, K = 4, 256, 256, 300
    static = SD.plan_tiles(n, m, K)
    alt = 256 if static == 128 else 128
    assert SD.plan_is_valid(n, m, K, alt,
                            vmem_budget=SD.DEFAULT_VMEM_BUDGET, x_itemsize=4)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, S, n)), jnp.float32)
    rows = np.asarray(rng.integers(0, n, (2, K)), np.int32)
    cols = np.asarray(rng.integers(0, m, (2, K)), np.int32)
    vals = np.asarray(rng.standard_normal((2, K)), np.float32)
    ids = jnp.asarray([0, 1], jnp.int32)

    want = jax.device_get(raw_sidedelta(x, rows, cols, vals, ids, m=m,
                                        interpret=True))
    assert SD.plan_cache_stats["misses"] >= 1

    key = SD.plan_cache_key(n, m, K)
    SD.install_plan_cache({key: alt})
    assert SD.plan_tiles(n, m, K) == alt           # the hit changes the plan
    assert SD.plan_cache_stats["hits"] >= 1
    t = ops.sidedelta_table([r.astype(np.int64) * m + c
                             for r, c in zip(rows, cols)], vals, n, m)
    assert t.rows.shape[1] // t.blocks.shape[1] == alt
    got = jax.device_get(raw_sidedelta(x, rows, cols, vals, ids, m=m,
                                       interpret=True))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)

    SD.clear_plan_cache()
    assert SD.plan_tiles(n, m, K) == static        # and clears back


def test_plan_cache_rejects_invalid_entries():
    n, m, K = 256, 256, 300
    static = SD.plan_tiles(n, m, K)
    key = SD.plan_cache_key(n, m, K)
    # over-budget and misaligned entries fall back to the heuristic
    for bad in (1 << 20, 100, 0):
        SD.clear_plan_cache()
        SD.install_plan_cache({key: bad})
        assert SD.plan_tiles(n, m, K) == static
        assert SD.plan_cache_stats["rejected"] == 1
        assert SD.plan_cache_stats["hits"] == 0


def test_autotune_save_load_install_roundtrip(tmp_path):
    key = SD.plan_cache_key(256, 256, 300)
    plans = {key: 128}
    path = autotune.save_cache(plans, str(tmp_path / "plans.json"),
                               meta={"host": "test"})
    loaded = autotune.load_cache(path)
    assert loaded == {key: 128}
    assert autotune.install(loaded) == 1
    assert SD.plan_cache() == {key: 128}
    assert autotune.maybe_install_file(str(tmp_path / "absent.json")) == 0


def test_observe_records_shape_classes():
    autotune.clear_observed()
    with autotune.observe():
        SD.plan_tiles(256, 256, 300)
        SD.plan_tiles(256, 256, 300)
        SD.plan_tiles(64, 64, 80)
    shapes = autotune.observed_shapes()
    assert shapes[0] == SD.plan_cache_key(256, 256, 300)  # most requested
    assert len(shapes) == 2
    autotune.clear_observed()
    assert autotune.observed_shapes() == []


def test_checked_in_plan_cache_changes_a_plan():
    """benchmarks/plan_cache.json must contain >= 1 measured plan that
    differs from the static heuristic — otherwise the autotune tier is
    decorative. Every entry must be valid for its budget."""
    path = os.path.join(REPO, "benchmarks", "plan_cache.json")
    plans = autotune.load_cache(path)
    assert plans, "plan cache is empty"
    changed = 0
    for key, kc in plans.items():
        n, m, K, budget, isize = key
        assert SD.plan_is_valid(n, m, K, kc, vmem_budget=budget,
                                x_itemsize=isize), key
        if kc != SD.plan_tiles(n, m, K, vmem_budget=budget,
                               x_itemsize=isize):
            changed += 1
    assert changed >= 1


def _check_plan_within_budget(n, m, K, budget):
    """A returned plan is lane-aligned and fits the budget at every S
    tile; plan_tiles raises exactly when not even kc = 128 fits."""
    try:
        kc = SD.plan_tiles(n, m, K, vmem_budget=budget, x_itemsize=4)
    except ValueError:
        assert SD.vmem_estimate(SD._ANY_S, n, m, SD._LANE,
                                vmem_budget=budget) > budget
        return
    assert kc % SD._LANE == 0 and kc >= SD._LANE
    assert SD.vmem_estimate(SD._ANY_S, n, m, kc,
                            vmem_budget=budget) <= budget


def test_plan_tiles_budget_random_sweep():
    """Seeded fallback for the hypothesis property below: the invariant
    still runs where hypothesis is not installed."""
    rng = np.random.default_rng(0)
    for _ in range(200):
        n = int(rng.integers(1, 4097))
        m = int(rng.integers(1, 8193))
        K = int(rng.integers(1, 65537))
        budget = int(rng.integers(1 << 18, 1 << 24))
        _check_plan_within_budget(n, m, K, budget)


def test_plan_tiles_respects_vmem_budget_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(n=st.integers(1, 4096),
           m=st.integers(1, 8192), K=st.integers(1, 65536),
           budget=st.integers(1 << 18, 1 << 24),
           cached=st.booleans())
    def prop(n, m, K, budget, cached):
        SD.clear_plan_cache()
        if cached:     # a hostile cache must never break the budget either
            SD.install_plan_cache(
                {SD.plan_cache_key(n, m, K, budget, 4): 1 << 17})
        _check_plan_within_budget(n, m, K, budget)

    prop()
    SD.clear_plan_cache()


# ---------------------------------------------------------------------------
# Load generator
# ---------------------------------------------------------------------------

def test_loadgen_schedule_deterministic_and_shaped():
    from repro.serving import loadgen
    phases = [loadgen.Phase(1.0, 10.0), loadgen.Phase(1.0, 80.0, burst=3.0),
              loadgen.Phase(1.0, 10.0)]
    gen = loadgen.LoadGen(adapters=["a", "b", "c"], vocab=100, seed=3,
                          zipf_s=1.5, phases=phases, shared_prefix=4)
    r1, r2 = gen.schedule(), gen.schedule()
    assert len(r1) == len(r2) and all(
        a.t == b.t and a.adapter == b.adapter
        and np.array_equal(a.prompt, b.prompt) for a, b in zip(r1, r2))
    # arrival times are monotone and inside the trace window
    ts = [r.t for r in r1]
    assert ts == sorted(ts) and 0 <= ts[0] and ts[-1] < 3.0
    # the overload phase dominates arrivals
    per_phase = {p: sum(r.phase == p for r in r1) for p in (0, 1, 2)}
    assert per_phase[1] > per_phase[0] and per_phase[1] > per_phase[2]
    # Zipf: the rank-0 adapter is the most popular
    counts = {a: sum(r.adapter == a for r in r1) for a in "abc"}
    assert counts["a"] == max(counts.values())
    # every prompt opens with the shared prefix
    p0 = r1[0].prompt[:4]
    assert all(np.array_equal(r.prompt[:4], p0) for r in r1)


def test_zipf_probs():
    from repro.serving.loadgen import zipf_probs
    p = zipf_probs(5, 1.2)
    assert p.sum() == pytest.approx(1.0)
    assert all(p[i] > p[i + 1] for i in range(4))


# ---------------------------------------------------------------------------
# Traced serving run: attribution coverage + timeline (the subsystem's
# end-to-end acceptance test)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged_setup():
    import jax
    from repro.configs import get_smoke_config
    from repro.launch.serve import make_adapters
    from repro.models import lm
    cfg = get_smoke_config("starcoder2-7b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    packs = make_adapters(cfg, params, 2, jax.random.PRNGKey(7),
                          multi_tenant=True)
    return cfg, params, packs


def _paged_engine(cfg, params, packs):
    from repro.hub import PagedServingEngine
    engine = PagedServingEngine(cfg, params, slots=2, num_pages=33,
                                page_size=2, max_len=24, chunk_size=4)
    for p in packs:
        engine.register(p)
    return engine


def test_traced_paged_run_coverage_and_timeline(paged_setup):
    import jax
    cfg, params, packs = paged_setup
    engine = _paged_engine(cfg, params, packs)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, rng.integers(3, 8))
               for _ in range(5)]
    # warmup compiles prefill/decode so the traced window measures the
    # serving loop, not XLA compilation
    engine.submit(prompts[0], packs[0].name, max_tokens=2)
    engine.run()

    tr = trace.install()
    futs = [engine.submit(p, packs[i % 2].name, max_tokens=3)
            for i, p in enumerate(prompts)]
    wall = engine.run()
    trace.uninstall()
    assert all(f.done() for f in futs)
    assert all(f.finish_time is not None and f.finish_time > f.submit_time
               for f in futs)

    names = {e["name"] for e in tr.events()}
    assert {"step", "admit", "prefill_chunk", "decode"} <= names
    att = replay.attribute(tr, wall_us=wall * 1e6)
    assert att["coverage"] >= 0.90, \
        f"spans cover only {att['coverage']:.1%} of run() wall"
    # the timeline reproduces the engine's scheduling loop tick by tick
    tl = replay.step_timeline(tr)
    counted = [r for r in tl if r["step"] is not None]
    assert [r["step"] for r in counted] == \
        list(range(counted[0]["step"], counted[0]["step"] + len(counted)))
    assert counted[-1]["step"] == engine.step_count
    assert any("decode" in r["phases"] for r in counted)
    assert any("prefill_chunk" in r["phases"] for r in counted)
    del jax


def test_loadgen_drives_paged_engine(paged_setup):
    from repro.serving import loadgen
    cfg, params, packs = paged_setup
    engine = _paged_engine(cfg, params, packs)
    gen = loadgen.LoadGen(adapters=[p.name for p in packs],
                          vocab=cfg.vocab_size, seed=1,
                          phases=[loadgen.Phase(0.2, 40.0, burst=2.0)],
                          prompt_len=(3, 6), max_tokens=(2, 4),
                          shared_prefix=2)
    reqs = gen.schedule()
    assert reqs
    rep = loadgen.run(engine, reqs, slo_ms=60_000.0)
    assert rep.completed == rep.offered == len(reqs)
    assert len(rep.latencies_ms) == rep.completed
    assert len(rep.ttfts_ms) == rep.completed
    assert all(np.isfinite(x) and x >= 0 for x in rep.latencies_ms)
    assert rep.tokens_out > 0 and rep.steps > 0
    assert rep.goodput_tok_s <= rep.tokens_per_s + 1e-9
    assert rep.slo_violation_rate == 0.0       # SLO is 60s: all met


# ---------------------------------------------------------------------------
# The profiler as a second sink
# ---------------------------------------------------------------------------

def _host_events(trace_dir):
    """name -> [(start_ns, end_ns, stats)] of the ``repro.*`` host events
    in the newest profiler trace under ``trace_dir``."""
    import glob
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        out.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns,
                             dict(e.stats)))
    return out


def _inside(iv, outer):
    return any(s <= iv[0] and iv[1] <= e for s, e, _ in outer)


def test_profiler_session_records_program_spans(paged_setup, tmp_path):
    """Under a profiler session the engine's and the switch's spans land
    on the host plane as ``repro.*`` events, properly nested, with no
    tracer installed (steps) and with one (the switch, which also fills
    the ring buffer); so do a garbage collection and a compile."""
    import gc

    import jax
    import jax.numpy as jnp
    from repro import core
    cfg, params, packs = paged_setup
    engine = _paged_engine(cfg, params, packs)
    prompt = np.arange(3, 9) % cfg.vocab_size
    engine.submit(prompt, packs[0].name, max_tokens=2)
    engine.run()                                   # compile off the record
    sw = core.SwitchEngine(params)
    sw.load(packs[0])
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.submit(prompt, packs[1].name, max_tokens=3)
        for _ in range(4):          # two chunks, then two decode steps
            engine.step()
        tr = trace.install()
        sw.switch(packs[1])
        trace.uninstall()
        gc.collect()
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    steps, decodes, syncs = ev["repro.step"], ev["repro.decode"], \
        ev["repro.sync"]
    assert len(steps) == 4 and len(decodes) == 2
    assert all(_inside(d, steps) for d in decodes)
    assert all(_inside(s, steps) for s in syncs)
    assert all(any(d[0] <= s[0] and s[1] <= d[1] for s in syncs)
               for d in decodes)
    assert any(e[2].get("step") for e in steps)    # span.set() reaches it
    (switch,) = ev["repro.switch"]
    for name in ("repro.switch.unload", "repro.switch.load",
                 "repro.switch.wait"):
        assert len(ev[name]) == 1 and _inside(ev[name][0], [switch])
    assert {e["name"] for e in tr.events()} == {
        "switch", "switch.unload", "switch.load", "switch.wait"}
    assert any(e[2].get("generation") == 2 for e in ev["repro.gc"])
    assert {e[2].get("stage") for e in ev["repro.compile"]} == {
        "lower", "backend"}
    assert ev["repro.jaxpr"]


def test_blocking_switch_waits_for_every_leaf(paged_setup):
    import jax
    from repro import core
    _, params, packs = paged_setup
    sw = core.SwitchEngine(params)
    sw.load(packs[0])
    st = sw.switch(packs[1])
    assert all(x.is_ready() for x in jax.tree.leaves(sw.params))
    assert [p.name for p in sw.active] == [packs[1].name]
    assert st.entries_written == (packs[0].num_params()
                                  + packs[1].num_params())
    assert sw.unload().name == "-" + packs[1].name and not sw.active


def test_span_opens_an_annotation_only_while_a_session_records(tmp_path):
    import jax
    assert trace.span("x") is trace.span("y")          # nothing records
    jax.profiler.start_trace(str(tmp_path))
    try:
        sp = trace.span("x", a=1)
        assert sp is not trace.span("y")
        with sp as s:
            assert s.set(b=2) is s
    finally:
        jax.profiler.stop_trace()
    assert trace.span("x") is trace.span("y")
    (x,) = _host_events(tmp_path)["repro.x"]
    assert x[2] == {"a": 1, "b": 2}
