"""Shared schema check for ``BENCH_*.json`` artifacts.

Every benchmark that persists machine-readable results writes a flat
list of records. CI's benchmark-smoke job (and the smoke runner) holds
them all to one contract so a silently-broken benchmark script — one
that writes an empty list, NaNs, or a malformed record — fails loudly
instead of poisoning the perf trajectory:

* the file parses as JSON and is a non-empty list of flat dicts
* every record carries a ``kind`` string (the record's table/figure id)
* every record carries at least one numeric field, and every numeric
  field is finite (no NaN/inf — wall-time math on a broken engine run
  produces exactly those)
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Any, List, Union


def _is_number(value: Any) -> bool:
    return isinstance(value, (int, float)) and not isinstance(value, bool)


def _bad_floats(record: dict) -> List[str]:
    bad: List[str] = []
    for key, value in record.items():
        leaves = value.items() if isinstance(value, dict) else [(None, value)]
        for sub, leaf in leaves:
            name = key if sub is None else f"{key}.{sub}"
            if isinstance(leaf, float) and not math.isfinite(leaf):
                bad.append(name)
    return bad


def validate_bench_records(records: Any, name: str = "<records>") -> List[str]:
    """Return a list of schema violations (empty == valid)."""
    if not isinstance(records, list):
        got = type(records).__name__
        return [f"{name}: top level is {got}, expected a list of records"]
    if not records:
        return [f"{name}: empty record list"]
    errors: List[str] = []
    for i, rec in enumerate(records):
        where = f"{name}[{i}]"
        if not isinstance(rec, dict):
            errors.append(f"{where}: {type(rec).__name__}, expected dict")
            continue
        kind = rec.get("kind")
        if not isinstance(kind, str) or not kind:
            errors.append(f"{where}: missing/empty 'kind' field")
        if not any(_is_number(v) for v in rec.values()):
            errors.append(f"{where}: no numeric fields")
        for field in _bad_floats(rec):
            errors.append(f"{where}: non-finite value in {field}")
    return errors


def validate_bench_file(path: Union[str, Path]) -> List[str]:
    """Schema-check one ``BENCH_*.json``; returns violations."""
    path = Path(path)
    if not path.exists():
        return [f"{path}: missing"]
    try:
        records = json.loads(path.read_text())
    except json.JSONDecodeError as exc:
        return [f"{path}: invalid JSON ({exc})"]
    return validate_bench_records(records, name=path.name)


# ---- TRACE_*.json (serving/trace.py Perfetto export) -------------------

_TRACE_PHASES = {"X", "i", "I", "C", "M", "B", "E"}
_TRACE_SEGMENTS = (
    "queue_wait",
    "select",
    "load_stall",
    "prefill",
    "decode",
    "preempted",
)


def _check_chrome_events(events: Any, name: str) -> List[str]:
    errors: List[str] = []
    if not isinstance(events, list) or not events:
        return [f"{name}: traceEvents missing or empty"]
    for i, ev in enumerate(events):
        where = f"{name}.traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: {type(ev).__name__}, expected dict")
            continue
        ph = ev.get("ph")
        if ph not in _TRACE_PHASES:
            errors.append(f"{where}: unknown phase {ph!r}")
        if ph != "M":
            ts = ev.get("ts")
            if not _is_number(ts) or not math.isfinite(ts) or ts < 0:
                errors.append(f"{where}: bad ts {ts!r}")
        if ph == "X":
            dur = ev.get("dur")
            if not _is_number(dur) or not math.isfinite(dur) or dur < 0:
                errors.append(f"{where}: bad dur {dur!r}")
        if not isinstance(ev.get("name"), str) or not ev.get("name"):
            errors.append(f"{where}: missing/empty 'name'")
    return errors


def _check_host_spans(spans: Any, where: str) -> List[str]:
    """Host spans: ``[id, parent_id, name, t0_ns, t1_ns, args]`` in
    start order, ids equal to positions, each closed and inside its
    parent's interval (an empty list: spans were off)."""
    if not isinstance(spans, list):
        return [f"{where}: missing 'spans' list"]
    errors: List[str] = []
    for i, rec in enumerate(spans):
        sw = f"{where}.spans[{i}]"
        if not isinstance(rec, list) or len(rec) != 6:
            errors.append(f"{sw}: not a 6-item list")
            continue
        sid, parent, name, t0, t1, args = rec
        if sid != i:
            errors.append(f"{sw}: id {sid!r} != position")
        if not isinstance(name, str) or not name:
            errors.append(f"{sw}: missing/empty name")
        if not isinstance(args, dict):
            errors.append(f"{sw}: args not a dict")
        if not (_is_number(t0) and _is_number(t1)
                and math.isfinite(t0) and math.isfinite(t1) and t1 >= t0):
            errors.append(f"{sw}: bad interval [{t0!r}, {t1!r}]")
            continue
        if parent is None:
            continue
        if not isinstance(parent, int) or not 0 <= parent < i:
            errors.append(f"{sw}: bad parent {parent!r}")
            continue
        outer = spans[parent]
        if (isinstance(outer, list) and len(outer) == 6
                and _is_number(outer[3]) and _is_number(outer[4])
                and not outer[3] <= t0 <= t1 <= outer[4]):
            errors.append(f"{sw}: interval escapes parent {parent}")
    return errors


def _check_trace_section(section: Any, name: str) -> List[str]:
    errors: List[str] = []
    where = f"{name}.edgelora"
    if not isinstance(section, dict):
        return [f"{where}: missing or not a dict"]
    if section.get("version") != 1:
        errors.append(f"{where}: version != 1")
    if not isinstance(section.get("meta"), dict):
        errors.append(f"{where}: missing 'meta' dict")
    duration = section.get("duration")
    if not _is_number(duration) or not math.isfinite(duration):
        errors.append(f"{where}: non-finite duration {duration!r}")
    events = section.get("events")
    if not isinstance(events, list) or not events:
        errors.append(f"{where}: raw event log missing or empty")
    else:
        for i, ev in enumerate(events):
            ew = f"{where}.events[{i}]"
            if not isinstance(ev, dict):
                errors.append(f"{ew}: not a dict")
                continue
            t = ev.get("t")
            if not _is_number(t) or not math.isfinite(t):
                errors.append(f"{ew}: non-finite t {t!r}")
            for field in ("kind", "track", "name"):
                if not isinstance(ev.get(field), str) or not ev.get(field):
                    errors.append(f"{ew}: missing/empty '{field}'")
    errors.extend(_check_host_spans(section.get("spans"), where))
    metrics = section.get("metrics")
    if not isinstance(metrics, dict):
        errors.append(f"{where}: missing 'metrics' dict")
    breakdowns = section.get("breakdowns")
    if not isinstance(breakdowns, dict):
        errors.append(f"{where}: missing 'breakdowns' dict")
    else:
        for rid, bd in breakdowns.items():
            bw = f"{where}.breakdowns[{rid}]"
            if not isinstance(bd, dict):
                errors.append(f"{bw}: not a dict")
                continue
            total = 0.0
            ok = True
            for seg in _TRACE_SEGMENTS:
                v = bd.get(seg)
                if not _is_number(v) or not math.isfinite(v) or v < -1e-9:
                    errors.append(f"{bw}: bad segment {seg}={v!r}")
                    ok = False
                else:
                    total += v
            e2e = bd.get("e2e")
            if not _is_number(e2e) or not math.isfinite(e2e):
                errors.append(f"{bw}: bad e2e {e2e!r}")
            elif ok and abs(total - e2e) > 1e-6:
                errors.append(f"{bw}: sum {total:.9f} != e2e {e2e:.9f}")
    watchdog = section.get("watchdog")
    if watchdog is not None and not isinstance(watchdog, dict):
        errors.append(f"{where}: watchdog is {type(watchdog).__name__}")
    return errors


def validate_trace_json(data: Any, name: str = "<trace>") -> List[str]:
    """Schema-check one exported engine trace (already-parsed JSON).

    Contract (see docs/observability.md): a Chrome-trace object with a
    non-empty ``traceEvents`` list of well-formed events (known phases,
    finite non-negative timestamps/durations) plus an ``edgelora``
    section carrying the raw event log, the host spans (closed, nested
    inside their parents), metrics series, per-request latency
    breakdowns whose segments sum to e2e, and the watchdog report.
    Returns violations (empty == valid).
    """
    if not isinstance(data, dict):
        got = type(data).__name__
        return [f"{name}: top level is {got}, expected an object"]
    errors = _check_chrome_events(data.get("traceEvents"), name)
    errors.extend(_check_trace_section(data.get("edgelora"), name))
    return errors


def validate_trace_file(path: Union[str, Path]) -> List[str]:
    """Schema-check one ``TRACE_*.json``; returns violations."""
    path = Path(path)
    if not path.exists():
        return [f"{path}: missing"]
    try:
        data = json.loads(path.read_text())
    except json.JSONDecodeError as exc:
        return [f"{path}: invalid JSON ({exc})"]
    return validate_trace_json(data, name=path.name)
