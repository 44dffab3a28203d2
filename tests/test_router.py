"""Adaptive Adapter Selection (Algorithm 1) properties."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.adapter_cache import AdapterMemoryManager
from repro.core.router import OracleRouter, select_adapter
from repro.core.slots import Request


def test_select_prefers_cached_topk():
    m = AdapterMemoryManager(2)
    m.acquire(3)
    scores = np.array([0.9, 0.1, 0.2, 0.8])  # best=0, second=3 (cached)
    aid, cached = select_adapter(scores, m, top_k=2)
    assert aid == 3 and cached


def test_select_falls_back_to_best_when_none_cached():
    m = AdapterMemoryManager(2)
    scores = np.array([0.1, 0.9, 0.3])
    aid, cached = select_adapter(scores, m, top_k=2)
    assert aid == 1 and not cached


def test_select_best_cached_beats_second_cached():
    m = AdapterMemoryManager(4)
    m.acquire(2)
    m.acquire(1)
    scores = np.array([0.5, 0.8, 0.7, 0.1])
    aid, cached = select_adapter(scores, m, top_k=3)
    assert aid == 1 and cached  # highest-scored cached adapter wins


@settings(max_examples=40, deadline=None)
@given(n=st.integers(2, 12), k=st.integers(1, 5),
       cached=st.sets(st.integers(0, 11), max_size=6),
       seed=st.integers(0, 999))
def test_select_properties(n, k, cached, seed):
    """Always returns a top-k adapter; returns a cached one iff the
    top-k set intersects the cache."""
    rng = np.random.default_rng(seed)
    scores = rng.uniform(size=n)
    cached = {c for c in cached if c < n}
    m = AdapterMemoryManager(max(len(cached), 1))
    for c in cached:
        m.acquire(c)
    k = min(k, n)
    aid, was_cached = select_adapter(scores, m, top_k=k)
    topk = set(np.argsort(-scores)[:k].tolist())
    assert aid in topk
    if topk & cached:
        assert was_cached and aid in cached
    else:
        assert not was_cached and aid == int(np.argmax(scores))


def test_oracle_router_accuracy_dial():
    r_hi = OracleRouter(8, accuracy=1.0, seed=0)
    r_lo = OracleRouter(8, accuracy=0.0, seed=0)
    reqs = [Request(i, 0.0, 8, 8, true_adapter=5) for i in range(50)]
    hits_hi = sum(int(np.argmax(r_hi.scores(r)) == 5) for r in reqs)
    hits_lo = sum(int(np.argmax(r_lo.scores(r)) == 5) for r in reqs)
    assert hits_hi == 50
    assert hits_lo < 25


def test_oracle_router_call_order_independent():
    """Scores are a pure function of (seed, request_id): scheduling
    reorders (batching, prefix-cache timing shifts) must not re-roll
    selections — the stream-parity suites depend on this."""
    reqs = [Request(i, 0.0, 8, 8, true_adapter=i % 4) for i in range(6)]
    a = OracleRouter(4, accuracy=0.5, seed=3)
    b = OracleRouter(4, accuracy=0.5, seed=3)
    fwd = [a.scores(r) for r in reqs]
    rev = [b.scores(r) for r in reversed(reqs)][::-1]
    for sa, sb in zip(fwd, rev):
        np.testing.assert_array_equal(sa, sb)
