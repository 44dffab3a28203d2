"""Tails are taken over all requests and all gaps of the window at once."""
import numpy as np

from bench.harness import stats


class _Obs:
    def __init__(self, stamps):
        self.stamps = stamps

    def token_stamps(self, rid):
        return self.stamps.get(rid, [])


def test_percentile_is_over_all_values():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50.5
    assert stats.percentile(v, 95) == np.percentile(v, 95)


def test_end_to_end_pools_waves_not_medians_of_waves():
    # wave A: fast requests; wave B: one slow wave. A median of per-wave
    # p95s would sit between them; the pooled p95 must come from B.
    a = {"start": 0.0, "end": 1.0, "tokens": 100,
         "ttft": [0.01] * 19 + [0.02], "gaps": [0.001] * 100}
    b = {"start": 1.0, "end": 3.0, "tokens": 100,
         "ttft": [1.0] * 20, "gaps": [0.05] * 10}
    e = stats.end_to_end([a, b])
    ttft = a["ttft"] + b["ttft"]
    gaps = a["gaps"] + b["gaps"]
    assert e["tokens_per_s"] == 200 / 3.0
    assert e["ttft_p50_ms"] == 1e3 * np.percentile(ttft, 50)
    assert e["ttft_p95_ms"] == 1e3 * np.percentile(ttft, 95)
    assert e["itl_p95_ms"] == 1e3 * np.percentile(gaps, 95)
    assert e["ttft_p95_ms"] == 1000.0


def test_wave_latencies_from_stamps():
    obs = _Obs({0: [10.5, 10.6, 10.8], 1: [10.2], 2: []})
    reqs = [{"request_id": 0}, {"request_id": 1}, {"request_id": 2}]
    lat = stats.wave_latencies(10.0, reqs, obs)
    assert np.allclose(lat["ttft"], [0.5, 0.2])
    assert np.allclose(lat["gaps"], [0.1, 0.2])
