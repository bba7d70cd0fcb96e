"""BlockAllocator refcount edges + prefix-cache/allocator ordering.

The allocator underpins every block-accounting invariant the engine and
the KV tier rely on; these tests pin the edges review keeps circling:
double-free detection, incref of a block that eviction already freed, and
the free-while-prefix-cached ordering (a sequence releasing its blocks
must leave the cache's own reference intact — and vice versa).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_hw_agnostic_inference_tpu.engine import BlockAllocator
from scalable_hw_agnostic_inference_tpu.engine.cache import PagedKVCache


def make_cache(**over):
    kw = dict(n_layers=2, leaves={"k": (2, 4), "v": (2, 4)},
              total_blocks=16, block_size=4, blocks_per_seq=8,
              dtype=jnp.float32, enable_prefix_caching=True)
    kw.update(over)
    return PagedKVCache(**kw)


# -- raw allocator edges ------------------------------------------------------

def test_double_free_detected_at_every_refcount():
    a = BlockAllocator(8)
    [b] = a.alloc(1)
    a.free([b])
    with pytest.raises(ValueError, match="double free"):
        a.free([b])
    # a shared block double-frees only past its LAST reference
    [c] = a.alloc(1)
    a.incref(c)
    a.free([c])
    a.free([c])
    with pytest.raises(ValueError, match="double free"):
        a.free([c])


def test_free_of_reserved_block_zero_rejected():
    a = BlockAllocator(8)
    with pytest.raises(ValueError, match="reserved"):
        a.free([0])


def test_incref_on_freed_block_rejected():
    """Eviction frees a cache-only block; a stale holder increfing it
    afterwards (the use-after-evict class) must fail loudly, not resurrect
    the block with refcount 1 while the free list also owns it."""
    a = BlockAllocator(8)
    [b] = a.alloc(1)
    a.free([b])
    with pytest.raises(ValueError, match="unallocated"):
        a.incref(b)


def test_partial_alloc_failure_leaves_freelist_intact():
    a = BlockAllocator(4)  # 3 usable
    a.alloc(3)
    before = a.n_free
    with pytest.raises(MemoryError):
        a.alloc(1)
    assert a.n_free == before


# -- free-while-prefix-cached ordering ---------------------------------------

def _admit_and_register(cache, seq_id, tokens):
    alloc = cache.admit(seq_id, len(tokens))
    cache.register_prefix(tokens, alloc.blocks)
    return alloc


def test_release_after_register_keeps_cache_reference():
    """Sequence release drops ONE reference; registered blocks survive at
    refcount 1 (the cache's), stay lookup-able, and remain evictable."""
    cache = make_cache()
    tokens = list(range(100, 108))  # 2 full blocks
    alloc = _admit_and_register(cache, 0, tokens)
    full = alloc.blocks[:2]
    assert all(cache.allocator.refcount(b) == 2 for b in full)
    cache.release(0)
    assert all(cache.allocator.refcount(b) == 1 for b in full)
    assert cache.cached_prefix(tokens) == full
    assert cache.n_evictable >= 2


def test_evict_then_stale_reuse_is_detected():
    """After eviction freed a cached block, an incref through the stale
    block id (the ordering bug free-while-prefix-cached protects against)
    raises instead of corrupting the free list."""
    cache = make_cache()
    tokens = list(range(200, 208))
    alloc = _admit_and_register(cache, 0, tokens)
    stale = list(alloc.blocks[:2])
    cache.release(0)
    assert cache._evict(2) == 2
    for b in stale:
        with pytest.raises(ValueError):
            cache.allocator.incref(b)
    assert cache.cached_prefix(tokens) == []


def test_shared_prefix_block_freed_only_after_every_holder():
    """Cache ref + two sequences sharing a block: releases in any order
    leave the block allocated until the LAST holder (the cache) lets go
    via eviction."""
    cache = make_cache()
    tokens = list(range(300, 308))
    alloc = _admit_and_register(cache, 0, tokens)
    shared = alloc.blocks[:2]
    cache.admit(1, len(tokens), reuse_blocks=shared)
    assert all(cache.allocator.refcount(b) == 3 for b in shared)
    cache.release(0)
    cache.release(1)
    assert all(cache.allocator.refcount(b) == 1 for b in shared)
    free_before = cache.allocator.n_free
    assert cache._evict(2) == 2
    assert cache.allocator.n_free == free_before + 2


def test_shrink_never_touches_shared_prefix_blocks():
    """Rollback (speculative shrink) frees only fresh decode-tail blocks;
    the reused prefix at the FRONT of the allocation keeps its refcounts."""
    cache = make_cache()
    tokens = list(range(400, 408))
    alloc = _admit_and_register(cache, 0, tokens)
    shared = alloc.blocks[:2]
    cache.admit(1, len(tokens), reuse_blocks=shared)
    # grow seq 1 by 5 tokens (2 fresh blocks), then roll them back
    cache.extend(1, 5)
    cache.shrink(1, 5)
    assert all(cache.allocator.refcount(b) == 3 for b in shared)
    cache.release(1)
    cache.release(0)
    assert all(cache.allocator.refcount(b) == 1 for b in shared)


# -- copy-on-write fan-out (fork_sequence, SHAI_KV_COW) -----------------------

def test_fork_at_every_refcount():
    """Each fork stacks one reference per shared block — parent, children,
    and a fork-of-a-fork all count; release unwinds exactly."""
    cache = make_cache()
    cache.admit(0, 6)  # 1 full + 1 partial block
    blocks = list(cache.seq(0).blocks)
    for k, child in enumerate((1, 2, 3), start=2):
        cache.fork_sequence(0, child)
        assert all(cache.allocator.refcount(b) == k for b in blocks)
    cache.fork_sequence(3, 4)  # grandchild: forks stack from any holder
    assert all(cache.allocator.refcount(b) == 5 for b in blocks)
    assert cache.cow_forks == 4
    for sid in (4, 3, 2, 1, 0):
        cache.release(sid)
    assert cache.allocator.n_free == 15
    assert cache.leaked_blocks == 0


def test_write_to_shared_tail_triggers_exactly_one_copy():
    """Two writers over one shared partial tail block: the first divergent
    write pays ONE block copy (priced by blocks_to_extend first); the last
    holder then owns the original at refcount 1 and never copies."""
    cache = make_cache()
    cache.admit(0, 6)
    cache.fork_sequence(0, 1)
    tail = cache.seq(0).blocks[1]
    # pricing: position 6 fits the tail block, but the pending CoW fork
    # adds its +1 so the async pipeline's need-check stays truthful
    assert cache.blocks_to_extend(1, 1) == 1
    free_before = cache.allocator.n_free
    cache.extend(1, 1)
    assert cache.cow_copies == 1
    assert cache.allocator.n_free == free_before - 1
    assert cache.seq(1).blocks[1] != tail
    assert cache.seq(0).blocks[1] == tail
    assert cache.allocator.refcount(tail) == 1
    # full leading block stays shared — only the written tail diverged
    assert cache.seq(1).blocks[0] == cache.seq(0).blocks[0]
    # the surviving holder writes in place: no second copy
    assert cache.blocks_to_extend(0, 1) == 0
    cache.extend(0, 1)
    assert cache.cow_copies == 1
    cache.release(0)
    cache.release(1)
    assert cache.allocator.n_free == 15
    assert cache.leaked_blocks == 0


def test_fork_of_prefix_cached_block():
    """Fork over a registered prompt: cache ref + parent + child stack;
    block-aligned growth diverges into FRESH blocks (no copy), and release
    leaves the cache's own reference intact and lookup-able."""
    cache = make_cache()
    tokens = list(range(500, 508))  # 2 full blocks, registered
    alloc = _admit_and_register(cache, 0, tokens)
    shared = list(alloc.blocks)
    cache.fork_sequence(0, 1)
    assert all(cache.allocator.refcount(b) == 3 for b in shared)
    cache.extend(1, 1)  # position 8 opens a new block: no CoW needed
    assert cache.cow_copies == 0
    assert cache.seq(1).blocks[:2] == shared
    cache.release(1)
    cache.release(0)
    assert all(cache.allocator.refcount(b) == 1 for b in shared)
    assert cache.cached_prefix(tokens) == shared
    assert cache.leaked_blocks == 0


def test_fork_release_order_independence():
    """Any release order over a diverged fan-out lands on the same exact
    block accounting — no order leaks or double-frees."""
    for order in ([0, 1, 2], [2, 1, 0], [1, 0, 2]):
        cache = make_cache()
        cache.admit(0, 6)
        cache.fork_sequence(0, 1)
        cache.fork_sequence(0, 2)
        cache.extend(1, 1)  # copy 1 (ref 3 -> writer forks)
        cache.extend(2, 1)  # copy 2 (ref 2 -> writer forks)
        cache.extend(0, 1)  # last holder: writes the original in place
        assert cache.cow_copies == 2
        for sid in order:
            cache.release(sid)
        assert cache.allocator.n_free == 15
        assert cache.leaked_blocks == 0


def test_fork_under_eviction_pressure():
    """A CoW copy allocated from a dry free list must evict cache-only
    blocks — never the shared source it is copying (refcount >= 2 is not
    evictable), and the accounting stays exact."""
    cache = make_cache()
    cached_tokens = list(range(600, 608))
    _admit_and_register(cache, 0, cached_tokens)
    cache.release(0)  # 2 evictable cache-only blocks
    cache.admit(1, 6)
    cache.fork_sequence(1, 2)
    shared = list(cache.seq(1).blocks)
    n_fill = cache.allocator.n_free
    for i in range(n_fill):  # drain the free list completely
        cache.admit(10 + i, cache.block_size)
    assert cache.allocator.n_free == 0
    assert cache.n_evictable == 2
    cache.extend(2, 1)  # CoW copy evicts exactly one cached block
    assert cache.cow_copies == 1
    assert cache.n_evictable == 1
    assert all(cache.allocator.refcount(b) >= 1 for b in shared)
    assert cache.seq(1).blocks == shared  # source survived the eviction
    for sid in [1, 2] + [10 + i for i in range(n_fill)]:
        cache.release(sid)
    assert cache.leaked_blocks == 0
