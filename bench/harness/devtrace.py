"""A profiler window around one wave, and its reduction to device numbers.

``capture`` runs a function under ``jax.profiler`` (Python tracer off, so
the host runs near its untraced speed), reads the ``.xplane.pb`` it wrote,
keeps only what the reduction needs as plain lists (``record``), and
deletes the files. ``reduce`` turns a record into the numbers the
per-layer readers take. A record is JSON-able, so a small one kept under
``bench/tests/data`` checks the reduction without a chip.

Record layout (times in ns on the profiler's clock)::

    {"window": [t0, t1],                  # the bench.wave host annotation
     "modules": [[name, start, dur], ...],  # device "XLA Modules" line
     "ops": [[name, start, dur], ...],      # "XLA Ops", names by op_name
     "host": [[name, start, dur], ...]}     # bench.* host annotations
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
from typing import Any, Callable, Dict, List, Optional, Tuple

WAVE = "bench.wave"
_MODULE_RE = re.compile(r"^(?:jit_)?([A-Za-z_][A-Za-z0-9_]*?)(?:[.(]\d.*)?$")
# an "XLA Ops" event is named by its whole HLO instruction:
# "%sgmv.22 = f32[4096,16]{...} custom-call(...), custom_call_target=..."
_OP_RE = re.compile(r"^(%?[^ ]+) = .*? ([a-z][a-z0-9_\-]*)\(")
PALLAS = "tpu_custom_call"


def op_name(text: str) -> str:
    """A short name for an op event: the instruction's name and opcode,
    with ``tpu_custom_call`` for a Pallas kernel (``%sgmv.22
    tpu_custom_call``)."""
    m = _OP_RE.match(text)
    if not m:
        return text[:80]
    kind = PALLAS if f'custom_call_target="{PALLAS}"' in text else m.group(2)
    return f"{m.group(1)} {kind}"


def capture(fn: Callable[[], Any], tmp_root: Optional[str] = None
            ) -> Tuple[Any, Dict]:
    import jax
    from jax.profiler import ProfileData

    d = tempfile.mkdtemp(prefix="bench-trace-", dir=tmp_root)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            result = fn()
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        rec = record(ProfileData.from_file(paths[0]))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return result, rec


def record(profile: Any) -> Dict:
    """Plain lists of the device's module and op events (first TPU plane)
    and the benchmark's own host annotations."""
    rec: Dict[str, Any] = {"window": None, "modules": [], "ops": [],
                           "host": []}
    device_done = False
    for plane in profile.planes:
        is_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            events = list(line.events)
            if is_device and not device_done and line.name in (
                    "XLA Modules", "XLA Ops"):
                if line.name == "XLA Modules":
                    rec["modules"] = [[e.name, float(e.start_ns),
                                       float(e.duration_ns)] for e in events]
                else:
                    rec["ops"] = [[op_name(e.name), float(e.start_ns),
                                   float(e.duration_ns)] for e in events]
            elif not is_device:
                for e in events:
                    if e.name.startswith("bench."):
                        rec["host"].append([e.name, float(e.start_ns),
                                            float(e.duration_ns)])
        if is_device and rec["modules"]:
            device_done = True
    waves = [h for h in rec["host"] if h[0] == WAVE]
    if waves:
        w = max(waves, key=lambda h: h[2])
        rec["window"] = [w[1], w[1] + w[2]]
    return rec


def module_kind(name: str) -> str:
    """``jit_decode_fn(12)`` -> ``decode_fn``."""
    m = _MODULE_RE.match(name)
    return m.group(1) if m else name


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(events: List, t0: float, t1: float) -> List[Tuple[str, float, float]]:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b))
    return out


def reduce(rec: Dict) -> Dict:
    """Device numbers of the traced window: its length, busy time (union
    of op intervals), device time and call count per module kind, total
    time per (module kind, op name), and the idle gaps, each named by the
    benchmark's host annotation as it opened and the module after it."""
    if not rec.get("window"):
        raise ValueError("trace record has no bench.wave window")
    t0, t1 = rec["window"]
    mods = _clip(rec["modules"], t0, t1)
    ops = _clip(rec["ops"], t0, t1) or mods
    busy_iv = _union([(a, b) for _, a, b in ops])
    busy = sum(b - a for a, b in busy_iv)
    module_time: Dict[str, float] = {}
    module_calls: Dict[str, int] = {}
    for name, a, b in mods:
        k = module_kind(name)
        module_time[k] = module_time.get(k, 0.0) + (b - a)
        module_calls[k] = module_calls.get(k, 0) + 1
    mods_sorted = sorted(mods, key=lambda m: m[1])
    starts = [m[1] for m in mods_sorted]
    op_time: Dict[str, float] = {}
    for name, a, b in ops:
        i = bisect.bisect_right(starts, a) - 1
        owner = (module_kind(mods_sorted[i][0])
                 if i >= 0 and mods_sorted[i][2] >= b else "?")
        key = f"{owner}:{name}"
        op_time[key] = op_time.get(key, 0.0) + (b - a)
    host = sorted(((n, s) for n, s, _ in rec.get("host", [])
                   if n != WAVE), key=lambda h: h[1])
    host_starts = [h[1] for h in host]
    gaps: Dict[str, List[float]] = {}
    edges = [t0] + [x for iv in busy_iv for x in iv] + [t1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        # the annotation the host made as the gap opened (the observer
        # notes each finished step), else the last one before it
        j = bisect.bisect_left(host_starts, a)
        if j >= len(host) or host[j][1] >= b:
            j -= 1
        before = host[j][0][len("bench."):] if j >= 0 else "wave start"
        k = bisect.bisect_left(starts, b)
        after = (module_kind(mods_sorted[k][0]) if k < len(mods_sorted)
                 else "wave end")
        name = f"{before} -> {after}"
        g = gaps.setdefault(name, [0.0, 0])
        g[0] += b - a
        g[1] += 1
    return {"window_ns": t1 - t0, "busy_ns": busy,
            "module_ns": module_time, "module_calls": module_calls,
            "op_ns": op_time, "gaps": gaps}


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The ``breakdown`` of a traced result line: the device operations
    that took most time and the idle time by what came before and after,
    in seconds."""
    ops = sorted(red["op_ns"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["gaps"].items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[f"{k} (x{n})", s * 1e-9] for k, (s, n) in gaps]}
