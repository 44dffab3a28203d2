"""Engine tracing tests (serving/trace.py).

The two contracts that make tracing safe to leave wired into the
engine:

* **no-op fast path** — with ``tracer=None`` the engine's token streams
  and ServingSummary are bit-identical to a traced run (every
  instrumentation site is behind one ``is not None`` guard, including
  the request-id list construction);
* **accounting invariants** — slot spans balance (every non-idle state
  is closed by exactly one transition), timestamps are finite and
  per-track ordered, and each completed request's latency segments
  (queue_wait + select + load_stall + prefill + decode + preempted)
  sum to its end-to-end latency — across policies, KV backends, swap
  modes, chunked prefill, and preemption churn.

Plus the jit-recompile watchdog (legal runs pass the documented shape
bound; an out-of-grid key fails loudly) and the metrics registry.
"""
import dataclasses
import json
import math

import pytest

from repro.configs import get_config, reduced_config
from repro.serving.engine import EdgeLoRAEngine, EngineConfig
from repro.serving.metrics_registry import MetricsRegistry
from repro.serving.trace import (BREAKDOWN_SEGMENTS, EngineTracer,
                                 JitRecompileError, busiest_spans,
                                 jit_cache_report, span_utilization)
from repro.serving.workload import WorkloadConfig, generate_trace

POLICIES = ("edgelora", "edgelora_no_aas", "llamacpp", "dlora")


def _cfg(n_adapters=5):
    cfg = reduced_config(get_config("qwen2-0.5b"))
    return dataclasses.replace(
        cfg, lora=dataclasses.replace(cfg.lora, n_adapters=n_adapters))


def _trace(cfg, seed=0, rate=4.0, duration=3.0, input_range=(4, 20),
           output_range=(3, 6)):
    return generate_trace(WorkloadConfig(
        n_adapters=cfg.lora.n_adapters, request_rate=rate,
        duration=duration, input_range=input_range,
        output_range=output_range, vocab_size=cfg.vocab_size, seed=seed))


def _ecfg(policy, kv="dense", **kw):
    base = dict(n_slots=2, max_ctx=48, prompt_buckets=(16, 32),
                policy=policy, kv_backend=kv)
    if policy == "llamacpp":
        base["memory_budget"] = 1e12
    base.update(kw)
    return EngineConfig(**base)


def _tokens_by_id(trace):
    return {r.request_id: r.tokens for r in trace}


def _summary_fields(summary):
    """Summary as a canonical string, minus the tracing-only field.
    JSON canonicalization makes NaN compare equal to itself (attainment
    fields are NaN when nothing carries an SLO) while any bitwise float
    difference still shows."""
    d = dict(summary.__dict__)
    d.pop("latency_breakdown")
    return json.dumps(d, default=float, sort_keys=True)


def _check_invariants(tracer, trace):
    """Span balance, ordering, and breakdown-sums-to-e2e."""
    assert tracer.open_spans() == []
    by_track = {}
    for ev in tracer.events:
        assert math.isfinite(ev["t"]) and ev["t"] >= 0.0
        assert ev.get("dur", 0.0) >= -1e-12
        by_track.setdefault(ev["track"], []).append(ev)
    # state spans on one slot never overlap (each closes before the next)
    for track, evs in by_track.items():
        if not track.startswith("slot"):
            continue
        spans = [e for e in evs if e["kind"] == "state"]
        for a, b in zip(spans, spans[1:]):
            assert a["t"] + a.get("dur", 0.0) <= b["t"] + 1e-9
    breakdowns = tracer.request_breakdowns()
    completed = {r.request_id for r in trace if r.finish_time is not None}
    assert set(breakdowns) == completed
    for rid, bd in breakdowns.items():
        total = sum(bd[seg] for seg in BREAKDOWN_SEGMENTS)
        assert all(bd[seg] >= -1e-9 for seg in BREAKDOWN_SEGMENTS)
        assert abs(total - bd["e2e"]) < 1e-6, (rid, bd)
    return breakdowns


# ---------------------------------------------------------------------------
# no-op fast path: tracer on/off is bit-identical
# ---------------------------------------------------------------------------


class _FakeTime:
    """Deterministic step timer: ``perf_counter`` advances a fixed tick
    per call, so the measured jit durations — and everything downstream
    on the virtual clock — are identical across runs. That lets the
    bit-identical test compare the *full* summary, timing fields
    included: any extra timing call or clock perturbation the tracer
    introduced would shift the traced run's virtual timeline and fail
    the comparison. (Under the real clock, wall-time jitter makes even
    two untraced runs differ in timing fields.)"""

    def __init__(self, tick=5e-4):
        self.t = 0.0
        self.tick = tick

    def perf_counter(self):
        self.t += self.tick
        return self.t


@pytest.fixture
def det_clock(monkeypatch):
    """Install a *fresh* fake timer (call before each serve, so both
    runs see the exact same absolute perf_counter sequence — repeated
    float accumulation makes tick deltas differ in the last ulp at
    different absolute offsets)."""
    def reset():
        monkeypatch.setattr("repro.serving.engine.time", _FakeTime())
    return reset


# every policy on both KV backends under the default (einsum) LoRA
# backend, plus sgmv cells on the one policy that exercises the
# unmerged batched-LoRA path (llamacpp is merged and never runs it;
# edgelora_no_aas / unmerged dlora share edgelora's sgmv compute);
# host spans on, plus one case with them off
_BIT_CASES = ([pytest.param(p, kv, None, True, id=f"{p}-{kv}-None")
               for p in POLICIES for kv in ("dense", "paged")]
              + [pytest.param("edgelora", kv, "sgmv", True,
                              id=f"edgelora-{kv}-sgmv")
                 for kv in ("dense", "paged")]
              + [pytest.param("edgelora", "paged", None, False,
                              id="edgelora-paged-None-spans_off")])


@pytest.mark.parametrize("policy,kv,lora,spans", _BIT_CASES)
def test_tracing_bit_identical(policy, kv, lora, spans, det_clock):
    cfg = _cfg()
    extra = {"lora_backend": lora} if lora else {}
    det_clock()
    t_off = _trace(cfg)
    eng_off = EdgeLoRAEngine(cfg, _ecfg(policy, kv, **extra))
    s_off = eng_off.serve(t_off)
    assert s_off.latency_breakdown is None
    assert eng_off.manager.on_event is None  # hooks never wired untraced

    tracer = EngineTracer()
    tracer.record_spans = spans
    det_clock()
    t_on = _trace(cfg)
    eng_on = EdgeLoRAEngine(cfg, _ecfg(policy, kv, **extra), tracer=tracer)
    s_on = eng_on.serve(t_on)
    assert bool(tracer.spans) == spans

    assert _tokens_by_id(t_off) == _tokens_by_id(t_on)
    assert _summary_fields(s_off) == _summary_fields(s_on)
    assert s_on.latency_breakdown is not None
    assert s_on.latency_breakdown["n"] == s_on.n_completed
    assert tracer.watchdog_report["ok"], tracer.watchdog_report
    _check_invariants(tracer, t_on)
    # hooks are unwired once the traced serve returns
    assert eng_on.manager.on_event is None


# ---------------------------------------------------------------------------
# breakdown invariants under synchronous swap-in
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv", ["dense", "paged"])
@pytest.mark.parametrize("policy", POLICIES)
def test_breakdown_invariants_sync_swap(policy, kv):
    cfg = _cfg()
    tracer = EngineTracer()
    eng = EdgeLoRAEngine(
        cfg, _ecfg(policy, kv, async_swap=False), tracer=tracer)
    trace = _trace(cfg, seed=1)
    summary = eng.serve(trace)
    assert summary.n_completed == len(trace)
    _check_invariants(tracer, trace)
    assert tracer.watchdog_report["ok"]


# ---------------------------------------------------------------------------
# preemption churn: preempted time is its own segment, sums still hold
# ---------------------------------------------------------------------------


def test_preemption_breakdown(det_clock):
    cfg = _cfg(n_adapters=4)
    tracer = EngineTracer()
    det_clock()  # preemption timing must not depend on wall jitter
    # arena of 10 x 8-token pages can hold one max-ctx sequence plus
    # change: four slots decoding long outputs must preempt (this seed
    # yields 4 preemptions over 15 requests under the fake clock)
    eng = EdgeLoRAEngine(cfg, EngineConfig(
        n_slots=4, max_ctx=64, prompt_buckets=(16, 32), policy="edgelora",
        kv_backend="paged", kv_block_size=8, kv_arena_blocks=10),
        tracer=tracer)
    trace = _trace(cfg, seed=1, rate=16.0, duration=1.0,
                   input_range=(8, 24), output_range=(24, 39))
    summary = eng.serve(trace)
    assert summary.n_completed == len(trace)
    breakdowns = _check_invariants(tracer, trace)

    sched = {}
    for ev in tracer.events:
        if ev["kind"] == "sched":
            sched[ev["name"]] = sched.get(ev["name"], 0) + 1
    assert sched.get("preempt", 0) > 0
    assert sched.get("requeue", 0) == sched["preempt"]

    preempted = [bd for bd in breakdowns.values() if bd["preempted"] > 0]
    assert preempted, "no request recorded preempted time"
    for bd in preempted:
        assert bd["admits"] >= 2  # requeued and re-admitted
    # arena instants were recorded through the kvpool hook
    arena = [ev for ev in tracer.events if ev["track"] == "arena"]
    assert {"alloc", "free"} <= {ev["name"] for ev in arena}


# ---------------------------------------------------------------------------
# chunked prefill: per-request chunk counts
# ---------------------------------------------------------------------------


def test_chunked_prefill_chunk_counts():
    cfg = _cfg()
    tracer = EngineTracer()
    eng = EdgeLoRAEngine(
        cfg, _ecfg("edgelora", "paged", prefill_chunk=8), tracer=tracer)
    trace = _trace(cfg, seed=2, input_range=(12, 30))
    eng.serve(trace)
    breakdowns = _check_invariants(tracer, trace)
    plen = {r.request_id: r.prompt_len for r in trace}
    assert any(bd["prefill_chunks"] >= 2 for bd in breakdowns.values())
    for rid, bd in breakdowns.items():
        # chunked prefill bounds each call to <= 8 prompt tokens
        assert bd["prefill_chunks"] >= math.ceil((plen[rid] - 1) / 8) - 1
        assert bd["prefill_chunks"] >= 1
    assert tracer.watchdog_report["ok"], tracer.watchdog_report


# ---------------------------------------------------------------------------
# jit-recompile watchdog
# ---------------------------------------------------------------------------


def test_watchdog_flags_rogue_shape_strict():
    cfg = _cfg()
    eng = EdgeLoRAEngine(cfg, _ecfg("edgelora"), tracer=EngineTracer())
    # a non-bucket prefill width: some call site stopped bucketing
    eng._durations[("prefill", 33, 3)] = 1e-3
    with pytest.raises(JitRecompileError, match="prefill"):
        eng.serve(_trace(cfg))


def test_watchdog_records_without_raising_when_lenient():
    cfg = _cfg()
    tracer = EngineTracer(strict_watchdog=False)
    eng = EdgeLoRAEngine(cfg, _ecfg("edgelora"), tracer=tracer)
    eng._durations[("prefill", 33, 3)] = 1e-3
    trace = _trace(cfg)
    summary = eng.serve(trace)
    assert summary.n_completed == len(trace)
    assert not tracer.watchdog_report["ok"]
    assert tracer.watchdog_report["violations"]


def test_jit_cache_report_unit():
    buckets, n_slots = (16, 32, 48), 4
    ok_keys = [("prefill", 16, 1), ("prefill", 48, 4), ("router", 32, 2),
               ("decode",), ("decode_merged",), ("prefill_merged", 32, 1)]
    rep = jit_cache_report(ok_keys, buckets=buckets, n_slots=n_slots)
    assert rep["ok"] and not rep["violations"]
    assert rep["prefill_bound"] == len(buckets) * 3  # {1,2,4} batches

    for bad in [("prefill", 33, 1),     # width off the bucket grid
                ("prefill", 16, 3),     # non-pow2 batch
                ("mystery", 1, 1),      # unknown kind
                ("prefill_sfx", 32, 8, 1)]:  # suffix w/o chunk or prefix
        rep = jit_cache_report(ok_keys + [bad], buckets=buckets,
                               n_slots=n_slots)
        assert not rep["ok"], bad

    # with the prefix cache on, suffix starts are data-dependent:
    # arbitrary (in-range) starts are legal and the bound is None
    sfx = [("prefill_sfx", 32, 7, 1), ("prefill_sfx_dense", 48, 19, 2)]
    rep = jit_cache_report(ok_keys + sfx, buckets=buckets, n_slots=n_slots,
                           prefix_cache=True, max_ctx=48)
    assert rep["ok"], rep["violations"]
    assert rep["bounds"]["prefill_sfx"] is None
    # but out-of-range starts are still structural violations
    rep = jit_cache_report([("prefill_sfx", 32, 32, 1)], buckets=buckets,
                           n_slots=n_slots, prefix_cache=True, max_ctx=48)
    assert not rep["ok"]


# ---------------------------------------------------------------------------
# metrics: per-step series
# ---------------------------------------------------------------------------


def test_metrics_series_from_traced_serve():
    cfg = _cfg()
    tracer = EngineTracer()
    eng = EdgeLoRAEngine(cfg, _ecfg("edgelora", "paged"), tracer=tracer)
    eng.serve(_trace(cfg))
    series = tracer.metrics.as_dict()
    expected = {"queue_depth", "active_slots", "decode_batch",
                "resident_adapters", "loading_adapters",
                "arena_blocks_used"}
    assert expected <= set(series)
    for name, pts in series.items():
        assert pts, name
        ts = [t for t, _ in pts]
        assert ts == sorted(ts)
        assert len(ts) == len(set(ts))  # duplicate-t collapsed
        assert all(math.isfinite(v) for _, v in pts)
    assert max(v for _, v in series["arena_blocks_used"]) > 0
    assert max(v for _, v in series["active_slots"]) > 0


def test_metrics_registry_unit():
    reg = MetricsRegistry()
    c = reg.counter("done")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("done")  # name bound to Counter
    g = reg.gauge("depth")
    g.set(7)
    reg.sample(1.0)
    g.set(9)
    reg.sample(1.0)  # same t replaces, not appends
    reg.sample(2.0)
    assert reg.series["depth"] == [(1.0, 9.0), (2.0, 9.0)]


# ---------------------------------------------------------------------------
# host spans (wall clock, mirrored as jax.profiler annotations)
# ---------------------------------------------------------------------------


def _burst_serve(tracer):
    """A tiny serve with more tenants than pool slots, every request due
    at once (so the prefetcher has a queue to warm): demand loads and
    prefetches both happen. The tracer is attached after construction,
    so the pool prefill at engine start records nothing."""
    cfg = _cfg(n_adapters=12)
    cfg = dataclasses.replace(
        cfg, lora=dataclasses.replace(cfg.lora, max_resident=3))
    trace = _trace(cfg, rate=6.0, duration=2.0)
    for r in trace:
        r.arrival_time = 0.0
    eng = EdgeLoRAEngine(cfg, _ecfg("edgelora_no_aas"))
    stats = eng.manager.stats
    before = (stats.misses, stats.prefetch_issued)
    eng.tracer = tracer
    eng.serve(trace)
    return trace, eng, before


def test_host_spans_nest_close_and_count_loads():
    tracer = EngineTracer()
    trace, eng, (misses0, prefetch0) = _burst_serve(tracer)
    spans = tracer.spans
    by_id = {s[0]: s for s in spans}
    assert [s[0] for s in spans] == list(range(len(spans)))
    assert tracer._open_spans == []
    for sid, parent, name, t0, t1, args in spans:
        assert t1 is not None and t1 >= t0, name      # every span closed
        if parent is None:
            assert name == "serve"
            continue
        outer = by_id[parent]
        assert outer[3] <= t0 <= t1 <= outer[4]
        want = {"iter": {"serve"},
                "admit": {"iter"}, "select": {"iter"},
                "adapter.prefetch": {"iter"}, "prefill": {"iter"},
                "decode": {"iter"},
                "adapter.acquire": {"select"},
                "adapter.load": {"adapter.acquire", "adapter.prefetch"},
                "wait": {"prefill", "decode", "select"}}[name]
        assert outer[2] in want, (name, outer[2])
    names = [s[2] for s in spans]
    assert names.count("serve") == 1
    # each finished request was admitted in exactly one admit span
    admitted = [rid for s in spans if s[2] == "admit"
                for rid in s[5]["requests"]]
    finished = [r.request_id for r in trace if r.finish_time is not None]
    assert finished and sorted(admitted) == sorted(finished)
    # the load counters agree with the manager's own accounting, and
    # each load span sits under the call that caused it
    stats = eng.manager.stats
    demand = tracer.metrics.counter("loads.demand").value
    prefetch = tracer.metrics.counter("loads.prefetch").value
    assert demand == stats.misses - misses0 > 0
    assert prefetch == stats.prefetch_issued - prefetch0 > 0
    loads = [s for s in spans if s[2] == "adapter.load"]
    assert len(loads) == demand + prefetch
    assert sum(by_id[s[1]][2] == "adapter.acquire" for s in loads) == demand
    assert tracer.metrics.counter("load.bytes").value == \
        len(loads) * eng.adapter_bytes
    assert all(s[5]["bytes"] == eng.adapter_bytes
               and s[5]["pool_bytes"] > s[5]["bytes"] for s in loads)


def test_host_spans_match_profiler_annotations(tmp_path):
    """The spans land in a jax.profiler trace as ``edgelora.<name>``
    host events, one for one, in the same order."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tracer = EngineTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _burst_serve(tracer)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("edgelora."):
                    events.append((e.start_ns, -e.duration_ns,
                                   e.name[len("edgelora."):]))
    assert [name for _, _, name in sorted(events)] == \
        [s[2] for s in tracer.spans]


# ---------------------------------------------------------------------------
# tracer unit behaviour
# ---------------------------------------------------------------------------


class _Req:
    def __init__(self, rid, arrival=0.0):
        self.request_id = rid
        self.arrival_time = arrival


def test_transition_unbalance_raises():
    tr = EngineTracer()
    tr.begin(0.0, 2, {})
    tr.transition(0.1, 0, "idle", "selecting", _Req(1))
    with pytest.raises(ValueError, match="unbalanced"):
        tr.transition(0.2, 0, "prefill", "generate", _Req(1))


def test_tracer_is_single_use():
    tr = EngineTracer()
    tr.begin(0.0, 1, {})
    with pytest.raises(RuntimeError, match="fresh"):
        tr.begin(0.0, 1, {})


def test_manual_breakdown_accounting():
    """A hand-driven request lifecycle: queue, select, load, prefill,
    decode, preempt, requeue, finish — segments sum to e2e."""
    tr = EngineTracer()
    tr.begin(0.0, 1, {})
    r = _Req(7, arrival=1.0)
    tr.transition(2.0, 0, "idle", "selecting", r)        # 1s queue_wait
    tr.transition(2.5, 0, "selecting", "loading", r)     # 0.5s select
    tr.transition(4.0, 0, "loading", "prefill", r)       # 1.5s load
    tr.transition(5.0, 0, "prefill", "generate", r)      # 1s prefill
    tr.transition(6.0, 0, "generate", "idle", r, preempted=True)
    tr.transition(8.0, 0, "idle", "prefill", r)          # 2s queue again
    tr.transition(9.0, 0, "prefill", "generate", r)
    tr.transition(10.0, 0, "generate", "idle", r)        # finish
    tr.finish(10.0)
    bd = tr.request_breakdowns()[7]
    assert bd["e2e"] == pytest.approx(9.0)
    assert bd["queue_wait"] == pytest.approx(3.0)
    assert bd["preempted"] == pytest.approx(4.0)  # first pass folded in
    assert bd["select"] == 0.0 and bd["load_stall"] == 0.0
    assert bd["prefill"] == pytest.approx(1.0)
    assert bd["decode"] == pytest.approx(1.0)
    assert bd["admits"] == 2
    assert sum(bd[s] for s in BREAKDOWN_SEGMENTS) == pytest.approx(9.0)


def test_span_helpers():
    events = [
        {"t": 0.0, "track": "compute", "kind": "compute",
         "name": "decode", "dur": 2.0},
        {"t": 2.0, "track": "compute", "kind": "compute",
         "name": "decode", "dur": 1.0},
        {"t": 0.0, "track": "compute", "kind": "compute",
         "name": "prefill 16 1", "dur": 5.0},
        {"t": 0.0, "track": "channel", "kind": "transfer",
         "name": "load a1", "dur": 1.0},
        {"t": 0.0, "track": "scheduler", "kind": "sched", "name": "admit"},
    ]
    assert span_utilization(events, 10.0, "channel") == pytest.approx(0.1)
    assert span_utilization(events, 10.0, "compute") == pytest.approx(0.8)
    rows = busiest_spans(events, top=5)
    assert rows[0]["name"] == "prefill 16 1"
    assert rows[1] == {"name": "decode", "count": 2, "total": 3.0,
                       "mean": 1.5}
