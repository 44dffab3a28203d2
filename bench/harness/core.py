"""One benchmark run: set-up, warm-up, the measured window, the readers,
the comparison that decides ``correct``, and the result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file and model module, its traffic mix file, and one
reader under ``bench/metrics/`` per per-layer metric.
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import sys
import time
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench.harness import correct, devtrace, stats, traffic
from bench.harness.engine import (WallObserver, check_layout, engine_config,
                                  make_engine, model_config)
from bench.models import module_of

SALT_ENGINE = 0xE9
WARMUP_ID = 10 ** 9


class CompileCounter:
    """Counts the programs JAX compiles (or loads from its persistent
    cache) while the ``with`` block runs, eager operations included."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.count = 0

    def _on(self, event: str, duration: float, **kwargs: Any) -> None:
        if event == self.EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc: Any) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def load_cell(root: str, workload: str) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return {"spec": spec, "cell": cell, "cfg": cfg, "mix": mix}


def _requests(dicts: List[Dict]) -> List[Any]:
    from repro.core.slots import Request

    return [Request(request_id=d["request_id"], arrival_time=0.0,
                    prompt_len=d["prompt_len"], output_len=d["output_len"],
                    adapter_id=d["adapter_id"], true_adapter=d["true_adapter"],
                    prompt_tokens=d["prompt_tokens"]) for d in dicts]


def warm_up(engine: Any, mix: Dict, n_adapters: int, vocab: int) -> int:
    """Serve one burst per prompt bucket the mix uses and per power-of-two
    group size up to n_slots (2-token outputs, resident adapters, so each
    burst prefills as one group), then one request whose adapter is not
    resident (the load path), where the mix has more tenants than the pool
    holds slots. Every step shape of the window then exists before it.
    Returns the number of requests served."""
    ecfg = engine.ecfg
    rng = np.random.default_rng(0)
    resident = list(engine.manager.resident.keys())
    sizes = sorted({min(1 << i, ecfg.n_slots)
                    for i in range(ecfg.n_slots.bit_length() + 1)})
    top = traffic.max_prompt_len(mix)
    rid = WARMUP_ID
    served = 0
    for b in traffic.buckets_used(mix, engine._buckets):
        plen = min(b, top)
        for k in sizes:
            reqs = [{"request_id": rid + i, "prompt_len": plen,
                     "output_len": 2, "adapter_id": resident[i % len(resident)],
                     "true_adapter": resident[i % len(resident)],
                     "prompt_tokens": rng.integers(0, vocab, plen,
                                                   dtype=np.int32)}
                    for i in range(k)]
            rid += k
            engine.serve(_requests(reqs))
            served += k
    cold = [a for a in range(n_adapters) if a not in engine.manager]
    if cold:
        plen = int(mix["input_range"][0])
        engine.serve(_requests([{
            "request_id": rid, "prompt_len": plen, "output_len": 2,
            "adapter_id": cold[0], "true_adapter": cold[0],
            "prompt_tokens": rng.integers(0, vocab, plen, dtype=np.int32)}]))
        served += 1
    return served


# the engine's jitted steps; engines built for further seeds in one
# process reuse the first engine's, so they compile nothing again
_STEP_ATTRS = ("_prefill", "_decode", "_prefill_merged", "_decode_merged",
               "_write_slots")


def build(c: Dict, seed: int, require_tpu: bool = True,
          hook: Optional[Callable[[Any], None]] = None,
          steps_from: Optional[SimpleNamespace] = None) -> SimpleNamespace:
    """Weights from the seed, the engine, and the warm-up (or, with
    ``steps_from``, the compiled steps of an earlier set-up: ``steps_of``)."""
    cfg, mix = c["cfg"], c["mix"]
    model = module_of(cfg)
    eseed = int(np.random.SeedSequence([seed, SALT_ENGINE])
                .generate_state(1)[0]) & 0x3FFFFFFF
    mcfg = model_config(cfg, mix.get("n_adapters"))
    ecfg = engine_config(mix, eseed)
    params = model.make_params(cfg, seed)
    adapter_of = model.adapter_fn(cfg, seed)
    if steps_from is None:
        check_layout(mcfg, params, adapter_of(0))
    engine = make_engine(mcfg, ecfg, params, adapter_of)
    del params
    if require_tpu and (engine.lora_backend != "sgmv"
                        or engine._sgmv_interpret):
        raise RuntimeError(f"the engine runs lora_backend="
                           f"{engine.lora_backend} interpret="
                           f"{engine._sgmv_interpret}, not compiled SGMV")
    if hook is not None:
        hook(engine)
    env = SimpleNamespace(c=c, seed=seed, model=model, engine=engine,
                          n_adapters=mcfg.lora.n_adapters,
                          vocab=cfg["vocab_size"], n_warm=0)
    if steps_from is None:
        env.n_warm = warm_up(engine, mix, env.n_adapters, env.vocab)
    else:
        for attr in _STEP_ATTRS:
            setattr(engine, attr, getattr(steps_from, attr))
        engine._durations = dict(steps_from._durations)
    return env


def steps_of(engine: Any) -> SimpleNamespace:
    """The compiled steps of a warmed engine, without its weights, pool or
    caches (``build(..., steps_from=...)`` takes them)."""
    return SimpleNamespace(_durations=dict(engine._durations),
                           **{a: getattr(engine, a) for a in _STEP_ATTRS})


def window(env: SimpleNamespace, seconds: float, traced_index: int = -1,
           min_waves: int = 1) -> SimpleNamespace:
    """Waves back to back until one ends ``seconds`` after the first
    started (at least ``min_waves``, and past ``traced_index``, the wave
    served under the profiler)."""
    import jax

    engine, mix = env.engine, env.c["mix"]
    prompts = traffic.system_prompts(mix, env.n_adapters, env.vocab, env.seed)
    loaded0 = len(engine.loaded)
    loads0 = engine.manager.stats.loads
    waves: List[Dict] = []
    red = None
    with CompileCounter() as compiles:
        t0 = time.perf_counter()
        while True:
            index = len(waves)
            dicts = traffic.wave(mix, env.n_adapters, env.vocab, env.seed, index,
                                 index * int(mix["wave_requests"]), prompts)
            reqs = _requests(dicts)
            obs = WallObserver(annotate=index == traced_index)
            engine.tracer = obs
            start = time.perf_counter()
            if index == traced_index:
                def serve_wave() -> Any:
                    with jax.profiler.TraceAnnotation(devtrace.WAVE):
                        return engine.serve(reqs)
                _, rec = devtrace.capture(serve_wave, os.environ.get("TMPDIR"))
                red = devtrace.reduce(rec)
            else:
                engine.serve(reqs)
            end = time.perf_counter()
            waves.append({"start": start, "end": end, "observer": obs,
                          "requests": dicts, "reqs": reqs,
                          "tokens": sum(r.generated for r in reqs),
                          **stats.wave_latencies(start, dicts, obs)})
            if end - t0 >= seconds and len(waves) >= min_waves \
                    and len(waves) > traced_index:
                break
    engine.tracer = None
    return SimpleNamespace(
        t0=t0, waves=waves, red=red,
        backend_compiles=compiles.count,
        step_compiles=sum(w["observer"].compiles for w in waves),
        loaded=engine.loaded[loaded0:],
        loads=engine.manager.stats.loads - loads0)


def served(win: SimpleNamespace, vocab: int) -> SimpleNamespace:
    """The requests the window finished in full, as the comparison takes
    them, and the count of those it did not."""
    done: List[Dict] = []
    attempted = failed = 0
    for w in win.waves:
        for d, r in zip(w["requests"], w["reqs"]):
            attempted += 1
            stamps = w["observer"].token_stamps(d["request_id"])
            if not (r.finish_time is not None
                    and r.generated == r.output_len == len(r.tokens)
                    == len(stamps) and all(0 <= t < vocab for t in r.tokens)):
                failed += 1
                continue
            done.append({"prompt_tokens": d["prompt_tokens"],
                         "prompt_len": r.prompt_len, "tokens": list(r.tokens),
                         "adapter": int(r.selected_adapter)})
    return SimpleNamespace(done=done, attempted=attempted, failed=failed)


def _device_info(devices: List[Any], chips: int) -> Dict:
    d = devices[0]
    mem = d.memory_stats() or {}
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t_process: float, require_tpu: bool = True,
        hook: Optional[Callable[[Any], None]] = None,
        out=None, err=None) -> int:
    """One run. Returns the exit code; prints the result line last on
    ``out`` and the compared numbers last on ``err``. ``hook(engine)``
    runs on the built engine before the warm-up (the fault tests break the
    timed path with it); ``require_tpu=False`` skips the look for a chip
    (the CPU tests)."""
    out = out or sys.stdout
    err = err or sys.stderr
    c = load_cell(root, workload)
    spec, cell, cfg = c["spec"], c["cell"], c["cfg"]

    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        print(f"bench: {workload} needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=err)
        return 2
    peaks = None
    if require_tpu:
        from bench.peaks import peaks as peaks_of
        peaks = peaks_of(devices[0].device_kind)

    env = build(c, seed, require_tpu, hook)
    traced_index = 1 if trace else -1
    win = window(env, seconds, traced_index)
    setup_s = win.t0 - t_process
    print(f"window: waves={len(win.waves)} seconds="
          f"{win.waves[-1]['end'] - win.t0!r} "
          f"step_compiles={win.step_compiles} "
          f"backend_compiles={win.backend_compiles} "
          f"warmup_requests={env.n_warm}", file=err)
    if win.step_compiles or win.backend_compiles:
        print("bench: programs compiled inside the measured window; the "
              "warm-up does not cover every shape the window uses", file=err)
        return 4
    device = _device_info(devices, cell["chips"])
    got = served(win, env.vocab)

    if trace:
        ctx = SimpleNamespace(cfg=cfg, n_slots=env.engine.ecfg.n_slots,
                              waves=win.waves, loads=win.loads,
                              requests=got.attempted, dev=win.red,
                              traced=win.waves[traced_index], peaks=peaks)
        metrics = {}
        for m in spec["per_layer"]:
            if not _applies(m, workload):
                continue
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = win.red["busy_ns"] * 1e-9
        device["window_s"] = win.red["window_ns"] * 1e-9
    else:
        e2e = stats.end_to_end(win.waves)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"] if _applies(m, workload)}

    # ---- correct: the reference, once the program's state is freed ----
    model = env.model
    loaded = win.loaded
    del env, win
    gc.collect()
    rows = correct.sample(got.done, seed, loaded)
    numbers = (correct.numbers(correct.gaps(model, cfg, seed, rows)["served"])
               if rows else {})
    checks = {name: {"value": numbers.get(name), "limit": limit}
              for name, limit in cfg["correct"].items()}
    checks["failed_requests"] = {"value": got.failed, "limit": 0}
    ok = bool(rows) and all(
        chk["value"] is not None and chk["limit"] is not None
        and chk["value"] <= chk["limit"] for chk in checks.values())
    result = {"correct": ok, "attempted": got.attempted,
              "failed": got.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = devtrace.breakdown(ctx.dev)
    result["checks"] = checks
    print(f"sample: requests={len(rows)} served_tokens="
          f"{sum(len(r['tokens']) for r in rows)} adapters="
          f"{sorted({r['adapter'] for r in rows})}", file=err)
    for name, chk in checks.items():
        print(f"check {name}: {chk['value']!r} limit {chk['limit']!r}",
              file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return 0
