"""Heterogeneous memory manager: LRU/LFU + pool invariants (hypothesis)."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.adapter_cache import AdapterMemoryManager


def test_basic_hit_miss():
    m = AdapterMemoryManager(2)
    s0, loaded0 = m.acquire(10)
    assert loaded0 and s0 in (0, 1)
    s1, loaded1 = m.acquire(10)
    assert not loaded1 and s1 == s0
    assert m.stats.hits == 1 and m.stats.misses == 1


def test_lru_eviction_order():
    m = AdapterMemoryManager(2, policy="lru")
    m.acquire(1)
    m.acquire(2)
    m.acquire(1)        # 1 is now most-recent
    m.acquire(3)        # evicts 2
    assert 1 in m and 3 in m and 2 not in m


def test_lfu_eviction_order():
    m = AdapterMemoryManager(2, policy="lfu")
    m.acquire(1); m.acquire(1); m.acquire(1)
    m.acquire(2)
    m.acquire(3)        # evicts 2 (freq 1) not 1 (freq 3)
    assert 1 in m and 3 in m and 2 not in m


def test_pinned_never_evicted():
    m = AdapterMemoryManager(2)
    m.acquire(1); m.pin(1)
    m.acquire(2); m.pin(2)
    with pytest.raises(RuntimeError):
        m.acquire(3)
    m.unpin(2)
    m.acquire(3)
    assert 1 in m and 3 in m and 2 not in m


def test_prefill_random():
    loads = []
    m = AdapterMemoryManager(3, load_fn=lambda a, s: loads.append((a, s)))
    m.prefill_random([5, 6, 7, 8])
    assert m.n_resident == 3 and len(loads) == 3


@settings(max_examples=40, deadline=None)
@given(cap=st.integers(1, 6),
       policy=st.sampled_from(["lru", "lfu"]),
       seq=st.lists(st.integers(0, 12), min_size=1, max_size=120))
def test_invariants(cap, policy, seq):
    """Across arbitrary access patterns:
    * residency never exceeds the pool size,
    * pool blocks are conserved (free + resident == cap, no slot reuse
      while occupied),
    * an acquire always lands the adapter in the cache,
    * hits+misses == total accesses.
    """
    slots_in_use = {}
    m = AdapterMemoryManager(cap, policy=policy)
    for a in seq:
        slot, _ = m.acquire(a)
        assert a in m
        assert m.slot_of(a) == slot
        assert m.n_resident <= cap
        assert m.n_resident + len(m.free_slots) == cap
        # no two resident adapters share a slot
        used = list(m.resident.values())
        assert len(used) == len(set(used))
    assert m.stats.hits + m.stats.misses == len(seq)


@settings(max_examples=20, deadline=None)
@given(seq=st.lists(st.integers(0, 3), min_size=10, max_size=80))
def test_small_working_set_always_hits_after_warmup(seq):
    """If distinct adapters ≤ capacity, everything after first touch hits."""
    m = AdapterMemoryManager(4)
    first = set()
    for a in seq:
        _, loaded = m.acquire(a)
        assert loaded == (a not in first)
        first.add(a)
