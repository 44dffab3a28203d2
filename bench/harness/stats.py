"""End-to-end metrics from the waves' host-clock stamps.

Every tail is taken over all requests (or all gaps between tokens) of the
whole window at once, never as a median of per-wave figures.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (linear interpolation) of all values."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def wave_latencies(start: float, requests: Sequence[Dict], observer
                   ) -> Dict[str, List[float]]:
    """Time to first token (from the wave's start, when every request of
    the wave was due) and every gap between consecutive tokens, seconds."""
    ttft: List[float] = []
    gaps: List[float] = []
    for r in requests:
        stamps = observer.token_stamps(r["request_id"])
        if not stamps:
            continue
        ttft.append(stamps[0] - start)
        gaps.extend(np.diff(stamps).tolist())
    return {"ttft": ttft, "gaps": gaps}


def end_to_end(waves: Sequence[Dict]) -> Dict[str, float]:
    """waves: dicts with ``start``, ``end``, ``tokens`` (output tokens
    served), ``ttft`` and ``gaps`` lists. Tokens per second are all the
    output tokens over the wall time from the first wave's start to the
    last wave's end."""
    tokens = sum(w["tokens"] for w in waves)
    span = waves[-1]["end"] - waves[0]["start"]
    ttft = [t for w in waves for t in w["ttft"]]
    gaps = [g for w in waves for g in w["gaps"]]
    return {"tokens_per_s": tokens / span,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "itl_p95_ms": 1e3 * percentile(gaps, 95)}
