"""The program's host spans in a device trace, and the device's idle time
put down to them.

The serving engine emits each host span (``EngineTracer.span``) as a
``jax.profiler`` annotation named ``edgelora.<span>``, so the spans sit
in the profile on the device events' own clock. ``spans`` keeps them as
plain lists; ``idle_by_span`` splits every idle interval of a traced
window (the complement of the device's op intervals, as
``devtrace.reduce`` computes them) at span edges and credits each piece
to the innermost span open over it, or to ``none``. Time the host spends
inside ``wait`` (``block_until_ready``) is the device's own bubbles
between ops of a step; the rest is idle the host caused.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from bench.harness import devtrace

PREFIX = "edgelora."
NONE = "none"
WAIT = "wait"


def spans(profile: Any) -> List[List]:
    """``[name, start_ns, dur_ns]`` of every host event named
    ``edgelora.*`` (prefix dropped), in start order."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append([e.name[len(PREFIX):], float(e.start_ns),
                                float(e.duration_ns)])
    out.sort(key=lambda s: (s[1], -s[2]))
    return out


def _innermost(host: List[List]) -> List[Tuple[float, float, str]]:
    """The timeline as pieces ``(start, end, name)`` of the innermost
    open span (spans of one thread nest; a child that outlasts its
    parent by a rounding is cut at the parent's end)."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []
    cur = float("-inf")

    def close_until(t: float) -> None:
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                pieces.append((cur, end, name))
                cur = end

    for name, s, d in sorted(host, key=lambda x: (x[1], -x[2])):
        close_until(s)
        if s > cur and stack:
            pieces.append((cur, s, stack[-1][1]))
        cur = max(cur, s)
        end = s + d
        if stack:
            end = min(end, stack[-1][0])
        stack.append((end, name))
    close_until(float("inf"))
    return pieces


def idle_by_span(rec: Dict, host: List[List]) -> Dict[str, float]:
    """Idle ns of the record's window, by the innermost span open over
    each piece (``none`` where no span is open)."""
    t0, t1 = rec["window"]
    ops = devtrace._clip(rec["ops"], t0, t1) \
        or devtrace._clip(rec["modules"], t0, t1)
    busy = devtrace._union([(a, b) for _, a, b in ops])
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    out: Dict[str, float] = {}
    pieces = _innermost(host)
    j = 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        t, k = a, j
        while t < b:
            if k < len(pieces) and pieces[k][0] <= t:
                end, name = min(b, pieces[k][1]), pieces[k][2]
                k += 1
            else:
                end = min(b, pieces[k][0]) if k < len(pieces) else b
                name = NONE
            if end > t:
                out[name] = out.get(name, 0.0) + (end - t)
            t = end
    return out


def host_idle_ns(by_span: Dict[str, float]) -> float:
    """Idle time outside ``wait``: the chip waiting on the host."""
    return sum(v for k, v in by_span.items() if k != WAIT)
