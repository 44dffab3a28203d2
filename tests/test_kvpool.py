"""Paged KV pool: allocation invariants (hypothesis) + gather reference."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving.kvpool import (OutOfBlocksError, PagedKVPool, gather_kv,
                                  write_kv)


def test_basic_lifecycle():
    p = PagedKVPool(n_blocks=4, block_size=8)
    p.register(0)
    assert p.append_tokens(0, 1) != []      # first token allocates a page
    assert p.append_tokens(0, 7) == []      # fills page 0
    assert len(p.append_tokens(0, 1)) == 1  # token 9 -> page 2
    assert p.used_blocks == 2
    p.release(0)
    assert p.used_blocks == 0


def test_out_of_blocks():
    p = PagedKVPool(n_blocks=2, block_size=4)
    p.register(0)
    p.append_tokens(0, 8)
    p.register(1)
    with pytest.raises(OutOfBlocksError):
        p.append_tokens(1, 1)
    p.release(0)
    p.append_tokens(1, 1)  # freed blocks are reusable


def test_overcommit_vs_fixed():
    """The pool's point: γ slots × max_ctx would need 8×16 blocks; with
    short actual contexts the arena holds many more sequences."""
    p = PagedKVPool(n_blocks=16, block_size=16)
    for s in range(8):           # 8 sequences × 32 tokens = 16 blocks
        p.register(s)
        p.append_tokens(s, 32)
    assert p.used_blocks == 16   # fully, but exactly, used


@settings(max_examples=30, deadline=None)
@given(ops=st.lists(
    st.tuples(st.integers(0, 3), st.integers(1, 12)), min_size=1,
    max_size=60))
def test_invariants(ops):
    """No block double-use; free+used == total; lengths consistent."""
    p = PagedKVPool(n_blocks=24, block_size=4)
    alive = set()
    for seq, n in ops:
        if seq not in alive:
            p.register(seq)
            alive.add(seq)
        try:
            p.append_tokens(seq, n)
        except OutOfBlocksError:
            victim = next(iter(alive))
            p.release(victim)
            alive.remove(victim)
        # invariants
        used = [b for t in p.tables.values() for b in t]
        assert len(used) == len(set(used)), "double-booked block"
        assert len(used) + len(p.free) == 24
        for s in alive & set(p.tables):
            need = -(-p.lengths[s] // 4) if p.lengths[s] else 0
            assert len(p.tables[s]) == need


def test_write_gather_roundtrip():
    rng = np.random.default_rng(0)
    p = PagedKVPool(n_blocks=8, block_size=4)
    arena = np.zeros((8, 4, 2, 3), np.float32)  # [blocks, bs, kh, hd]
    p.register(7)
    ref = []
    for pos in range(11):
        p.append_tokens(7, 1)
        v = rng.normal(size=(2, 3)).astype(np.float32)
        write_kv(arena, p, 7, pos, v)
        ref.append(v)
    table = p.block_table(7, max_blocks=8)
    got = gather_kv(arena, table, 11)
    np.testing.assert_array_equal(got, np.stack(ref))


def test_capacity_gates_and_oom_stats():
    """can_allocate/can_append are side-effect-free admission gates; a
    gated caller never trips oom_events, an ungated append does."""
    p = PagedKVPool(n_blocks=4, block_size=8)
    assert p.blocks_for(0) == 0 and p.blocks_for(1) == 1
    assert p.blocks_for(8) == 1 and p.blocks_for(9) == 2
    assert p.can_allocate(32) and not p.can_allocate(33)
    assert 0 not in p.tables  # the gate registered nothing
    p.register(0)
    p.append_tokens(0, 30)
    assert p.can_append(0, 2) and not p.can_append(0, 3)
    assert p.stats.oom_events == 0
    with pytest.raises(OutOfBlocksError):
        p.append_tokens(0, 3)
    assert p.stats.oom_events == 1
    assert p.lengths[0] == 30  # all-or-nothing: length unchanged
    d = p.stats.as_dict()
    assert d == {"allocs": 4, "frees": 0, "peak_used": 4, "oom_events": 1}


def test_free_blocks_property_tracks_free_list():
    p = PagedKVPool(n_blocks=6, block_size=4)
    assert p.free_blocks == 6
    p.register(1)
    p.append_tokens(1, 9)
    assert p.free_blocks == 3 and p.used_blocks == 3
    p.release(1)
    assert p.free_blocks == 6


def test_unknown_seq_raises_clear_keyerror():
    """release/can_append/append_tokens on a never-registered (or
    already-released) seq fail with an explanatory KeyError, not a raw
    dict lookup error."""
    p = PagedKVPool(n_blocks=4, block_size=4)
    for op in (lambda: p.release(9), lambda: p.can_append(9),
               lambda: p.append_tokens(9, 1)):
        with pytest.raises(KeyError, match="not registered"):
            op()


def test_double_release_is_explicit_error():
    """Both the preemption and the completion path call release; a
    double call (engine bookkeeping bug) must fail loudly — and must
    not double-free blocks into the free list."""
    p = PagedKVPool(n_blocks=4, block_size=4)
    p.register(0)
    p.append_tokens(0, 6)
    p.release(0)
    assert p.free_blocks == 4
    with pytest.raises(KeyError, match="released twice"):
        p.release(0)
    assert p.free_blocks == 4 and p.stats.frees == p.stats.allocs == 2


def test_refcounted_shared_blocks_free_at_zero():
    p = PagedKVPool(n_blocks=8, block_size=4)
    p.register(0)
    shared = p.append_tokens(0, 8)              # two full blocks
    p.register(1)
    p.adopt_prefix(1, shared, 10)               # shares both + 1 private
    assert all(p.refs[b] == 2 for b in shared)
    assert p.used_blocks == 3
    p.release(0)
    assert all(p.refs[b] == 1 for b in shared)  # survive the donor
    assert p.used_blocks == 3
    p.release(1)
    assert p.used_blocks == 0 and p.stats.frees == p.stats.allocs


def test_adopt_prefix_cow_allocates_private_copy():
    p = PagedKVPool(n_blocks=8, block_size=4)
    p.register(0)
    shared = p.append_tokens(0, 8)
    p.register(1)
    pair = p.adopt_prefix(1, shared, 8, cow_last=True)
    src, dst = pair
    assert src == shared[-1] and dst not in shared
    assert p.tables[1] == [shared[0], dst]
    assert p.refs[shared[0]] == 2               # held
    assert p.refs[shared[-1]] == 1              # NOT held (copied)
    assert p.refs[dst] == 1


def test_replace_prefix_swaps_reserved_blocks():
    """The conservative admission path: a fully reserved table swaps its
    leading private blocks for shared ones, returning them to the free
    list (no net footprint growth)."""
    p = PagedKVPool(n_blocks=12, block_size=4)
    p.register(0)
    shared = p.append_tokens(0, 8)
    p.register(1)
    p.append_tokens(1, 11)                      # 3 private blocks
    used_before = p.used_blocks
    p.replace_prefix(1, shared)
    assert p.tables[1][:2] == shared
    assert p.used_blocks == used_before - 2     # two privates freed
    assert all(p.refs[b] == 2 for b in shared)


class _StubReclaimer:
    """Minimal reclaimer contract: a bag of evictable blocks."""

    def __init__(self, pool, blocks):
        self.pool, self.blocks = pool, list(blocks)

    def reclaimable(self):
        return len(self.blocks)

    def reclaim(self, k):
        n = 0
        while self.blocks and n < k:
            self.pool.drop_ref(self.blocks.pop(0))
            n += 1
        return n

    def note_block_ref(self, blk):
        pass


def test_reclaimer_extends_capacity_exactly():
    """can_allocate/can_append/append_tokens count reclaimable blocks
    as available and evict them on demand — never one more."""
    p = PagedKVPool(n_blocks=4, block_size=4)
    p.register(0)
    held = p.append_tokens(0, 16)               # arena full
    for b in held:
        p.add_ref(b)                            # simulate cache holds
    p.release(0)                                # now cache-only
    p.reclaimer = _StubReclaimer(p, held)
    assert p.free_blocks == 0
    assert p.can_allocate(16) and not p.can_allocate(17)
    p.register(1)
    got = p.append_tokens(1, 12)                # 3 blocks via reclaim
    assert len(got) == 3 and p.free_blocks == 0
    assert p.reclaimer.reclaimable() == 1
    assert p.can_append(1, 4) and not p.can_append(1, 5)
    with pytest.raises(OutOfBlocksError):
        p.append_tokens(1, 8)


def test_interleaved_sequences_isolated():
    rng = np.random.default_rng(1)
    p = PagedKVPool(n_blocks=8, block_size=4)
    arena = np.zeros((8, 4, 1), np.float32)
    vals = {0: [], 1: []}
    for s in (0, 1):
        p.register(s)
    for i in range(12):
        s = i % 2
        p.append_tokens(s, 1)
        v = rng.normal(size=(1,)).astype(np.float32)
        write_kv(arena, p, s, len(vals[s]), v)
        vals[s].append(v)
    for s in (0, 1):
        got = gather_kv(arena, p.block_table(s, 8), len(vals[s]))
        np.testing.assert_array_equal(got, np.stack(vals[s]))
