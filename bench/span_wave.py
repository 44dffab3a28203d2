"""One traced wave of a cell, with the device's idle time put down to the
program's host spans.

    python bench/span_wave.py --workload <cell> --seed <n> [--spans 0|1]
        [--dump PATH --dump-ms MS]

Builds the cell as ``bench/run.py`` does, serves wave 0, then serves wave
1 under the profiler (as a ``--trace 1`` run does) and prints one JSON
line: the wave's length and idle share, the idle time by innermost host
span (``spantrace.idle_by_span``), the part of it inside a device
module's execution (bubbles within a step), the idle gaps by the span
open as each gap closed and the device module that closed it, and the
span readers' numbers. ``--spans 0`` turns the program's host spans off
(the cost of spans is the difference between the two). ``--dump`` writes
the first ``--dump-ms`` of the wave's trace record, with its host spans
and the program's span list, as JSON.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("host_ms_per_iter", "queue_wait_p95_ms", "adapter_load_ms",
           "demand_loads_per_req")


def setup(workload, seed):
    """The built cell, with wave 0 served; returns the engine and wave
    1's requests (as dicts)."""
    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.harness import core, traffic
    from bench.harness.engine import WallObserver

    c = core.load_cell(ROOT, workload)
    env = core.build(c, seed)
    mix = c["mix"]
    prompts = traffic.system_prompts(mix, env.n_adapters, env.vocab, env.seed)
    waves = [traffic.wave(mix, env.n_adapters, env.vocab, env.seed, index,
                          index * int(mix["wave_requests"]), prompts)
             for index in (0, 1)]
    env.engine.tracer = WallObserver()
    env.engine.serve(core._requests(waves[0]))
    env.engine.tracer = None
    return env.engine, waves[1]


def traced_wave(engine, dicts, spans):
    """Serve one wave under the profiler with a fresh observer, host spans
    on or off; returns (trace record, host spans from the profile,
    observer, wall seconds)."""
    import jax
    from jax.profiler import ProfileData

    from bench.harness import core, devtrace, spantrace
    from bench.harness.engine import WallObserver

    reqs = core._requests(dicts)
    obs = WallObserver(annotate=True)
    obs.record_spans = bool(spans)
    engine.tracer = obs
    d = tempfile.mkdtemp(prefix="span-wave-", dir=os.environ.get("TMPDIR"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(devtrace.WAVE):
                engine.serve(reqs)
        finally:
            wall = time.perf_counter() - start
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        profile = ProfileData.from_file(path)
        rec = devtrace.record(profile)
        host = spantrace.spans(profile)
    finally:
        shutil.rmtree(d, ignore_errors=True)
        engine.tracer = None
    return rec, host, obs, wall


def idle_split(rec, host):
    """Idle ns by innermost span, and the part of each inside a device
    module's execution (the device idle between the ops of a step)."""
    from bench.harness import spantrace

    by_span = spantrace.idle_by_span(rec, host)
    between = spantrace.idle_by_span(
        dict(rec, ops=rec["ops"] + rec["modules"]), host)
    return by_span, {k: v - between.get(k, 0.0) for k, v in by_span.items()}


def gaps_by_span(rec, host):
    """Idle gaps keyed by the innermost span open as each gap closed and
    the device module that closed it: {key: [ns, count]}."""
    from bench.harness import devtrace, spantrace

    t0, t1 = rec["window"]
    ops = devtrace._clip(rec["ops"], t0, t1)
    busy = devtrace._union([(a, b) for _, a, b in ops])
    mods = sorted(devtrace._clip(rec["modules"], t0, t1), key=lambda m: m[1])
    starts = [m[1] for m in mods]
    pieces = spantrace._innermost(host)
    ends = [p[1] for p in pieces]
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    out = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        k = bisect.bisect_left(starts, b)
        after = devtrace.module_kind(mods[k][0]) if k < len(mods) else "end"
        j = bisect.bisect_left(ends, b)
        span = (pieces[j][2] if j < len(pieces) and pieces[j][0] < b
                else spantrace.NONE)
        g = out.setdefault(f"{span} -> {after}", [0.0, 0])
        g[0] += b - a
        g[1] += 1
    return out


def summary(rec, host, obs, dicts):
    """The numbers of one traced wave, as a JSON-able dict."""
    from bench.harness import devtrace, spantrace

    red = devtrace.reduce(rec)
    window = red["window_ns"]
    by_span, in_step = idle_split(rec, host)
    ctx = SimpleNamespace(traced={"observer": obs, "requests": dicts})
    readers = {m: importlib.import_module(f"bench.metrics.{m}").read(ctx)
               for m in READERS}
    n_iter = sum(s[2] == "iter" for s in obs.spans)
    out = {
        "wave_s": window * 1e-9,
        "idle_share": 100.0 * (1.0 - red["busy_ns"] / window),
        "host_idle_share": 100.0 * spantrace.host_idle_ns(by_span) / window,
        "host_span_events": len(host), "program_spans": len(obs.spans),
        "iters": n_iter,
        "idle_by_span_s": {k: v * 1e-9 for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "idle_in_step_s": sum(in_step.values()) * 1e-9,
        "idle_in_step_by_span_s": {k: v * 1e-9 for k, v in sorted(
            in_step.items(), key=lambda kv: -kv[1]) if v > 0},
        "gaps_by_span": {k: [v[0] * 1e-9, v[1]] for k, v in sorted(
            gaps_by_span(rec, host).items(), key=lambda kv: -kv[1][0])[:20]},
        "idle_gaps": devtrace.breakdown(red)["idle_gaps"],
        "readers": readers,
    }
    if readers["host_ms_per_iter"] is not None:
        out["host_s_all_iters"] = readers["host_ms_per_iter"] * 1e-3 * n_iter
    return out


def dump(path, rec, host, obs, dicts, ms):
    """The first ``ms`` of the wave: its trace record and host spans, and
    the program's spans up to the first iteration that starts past
    ``ms`` (a prefix by start, so ids stay positions and parents stay)."""
    cut = rec["window"][0] + ms * 1e6
    serve_t0 = obs.spans[0][3]
    n = next((s[0] for s in obs.spans if s[2] == "iter"
              and s[3] - serve_t0 > ms * 1e6), len(obs.spans))
    out = {"window": [rec["window"][0], cut],
           "modules": [m for m in rec["modules"] if m[1] < cut],
           "ops": [o for o in rec["ops"] if o[1] < cut],
           "host": [h for h in rec["host"] if h[1] < cut],
           "spans": [s for s in host if s[1] < cut],
           "program_spans": obs.spans[:n],
           "requests": len(dicts)}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--spans", type=int, choices=(0, 1), default=1)
    p.add_argument("--dump", default=None)
    p.add_argument("--dump-ms", type=float, default=300.0)
    args = p.parse_args(argv)
    engine, dicts = setup(args.workload, args.seed)
    import jax

    rec, host, obs, wall = traced_wave(engine, dicts, args.spans)
    out = {"workload": args.workload, "seed": args.seed, "spans": args.spans,
           "device": jax.devices()[0].device_kind, "serve_wall_s": wall,
           **summary(rec, host, obs, dicts)}
    if args.dump:
        dump(args.dump, rec, host, obs, dicts, args.dump_ms)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
