"""Paged KV pool: block-allocator aliasing/conservation property tests and
the fragmentation regression vs the slot pool at fixed memory."""
import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import given, settings

import jax
import jax.numpy as jnp

from repro.serve.cache import (PageAllocator, PagedKVPool, SlotKVPool,
                               pages_for)


# ---------------------------------------------------------------------------
# PageAllocator properties
# ---------------------------------------------------------------------------


def test_allocator_basics():
    a = PageAllocator(8)                  # pages 1..7 allocatable
    assert a.n_free == 7
    p0 = a.alloc("r0", 3)
    assert p0 is not None and len(p0) == 3 and 0 not in p0
    assert a.alloc("r1", 5) is None       # all-or-nothing
    p1 = a.alloc("r1", 4)
    assert a.n_free == 0
    assert not set(p0) & set(p1)          # no aliasing
    assert a.append("r0") is None         # exhausted
    a.free("r0")
    assert a.n_free == 3
    with pytest.raises(ValueError):       # double free
        a.free("r0")
    with pytest.raises(ValueError):       # double alloc for one owner
        a.alloc("r1", 1)
    a.check_invariants()


def test_allocator_null_page_reserved():
    a = PageAllocator(4)
    pages = a.alloc("r", 3)
    assert 0 not in pages                 # page 0 is the null sink
    a.check_invariants()
    with pytest.raises(ValueError):
        PageAllocator(1)                  # must fit at least null + 1


@settings(max_examples=30)
@given(ops=st.lists(st.integers(min_value=0, max_value=5),
                    min_size=1, max_size=60),
       n_pages=st.integers(min_value=2, max_value=24))
def test_allocator_never_aliases_live_pages(ops, n_pages):
    """Random alloc/append/free interleavings: at every step the live pages
    of distinct owners are disjoint, page 0 never escapes, and free+live
    always partition the pool."""
    rng = np.random.default_rng(len(ops) * 1000 + n_pages)
    a = PageAllocator(n_pages)
    owners: dict[int, set] = {}
    next_owner = 0
    for op in ops:
        if op <= 2:                       # alloc a new owner
            n = int(rng.integers(0, max(n_pages // 2, 1)))
            got = a.alloc(next_owner, n)
            if got is not None:
                assert len(got) == n
                for prev in owners.values():
                    assert not prev & set(got), "aliased a live page"
                owners[next_owner] = set(got)
            next_owner += 1
        elif op <= 3 and owners:          # append to a random live owner
            o = int(rng.choice(list(owners)))
            p = a.append(o)
            if p is not None:
                for oo, pages in owners.items():
                    assert p not in pages, f"append aliased owner {oo}"
                owners[o].add(p)
        elif owners:                      # free a random owner
            o = int(rng.choice(list(owners)))
            freed = a.free(o)
            assert set(freed) == owners.pop(o)
        a.check_invariants()
        live = set().union(*owners.values()) if owners else set()
        assert a.n_live == len(live)
        assert a.n_free == (n_pages - 1) - len(live)


@settings(max_examples=20)
@given(seq=st.lists(st.integers(min_value=1, max_value=40),
                    min_size=1, max_size=12))
def test_pool_admit_release_roundtrip(seq):
    """Admitting and releasing arbitrary token demands conserves pages and
    never hands two slots overlapping block-table entries."""
    ps, n_slots, n_pages = 8, 4, 33
    avals = {"k": jax.ShapeDtypeStruct((n_pages, ps, 1, 2), jnp.float32)}
    pool = PagedKVPool(avals, n_slots, ps, n_pages, max_pages_per_slot=5)
    held = []
    for need in seq:
        slot = pool.admit(need)
        if slot is None:
            if held:
                pool.release(held.pop(0))
            continue
        held.append(slot)
        row = pool.block_tables[slot]
        live = row[row > 0]
        assert len(set(live)) == len(live)
        for other in held[:-1]:
            orow = pool.block_tables[other]
            assert not set(live) & set(orow[orow > 0]), "block tables alias"
        pool.allocator.check_invariants()
    for slot in held:
        pool.release(slot)
    assert pool.allocator.n_live == 0
    assert pool.n_free == n_slots
    assert (pool.block_tables == 0).all()


@settings(max_examples=20)
@given(ops=st.lists(st.integers(min_value=0, max_value=9),
                    min_size=1, max_size=40))
def test_pool_multi_token_append_rollback_properties(ops):
    """Random admit / multi-token advance / rollback / release
    interleavings (the speculative decode lifecycle): page conservation
    holds at every step, block tables never alias, lengths never exceed
    the block table's reach, and rollback — being pure length bookkeeping
    — leaves the allocator's high-water mark untouched."""
    ps, n_slots, n_pages = 4, 3, 13
    avals = {"k": jax.ShapeDtypeStruct((n_pages, ps, 1, 2), jnp.float32)}
    pool = PagedKVPool(avals, n_slots, ps, n_pages, max_pages_per_slot=4)
    cap = 4 * ps
    rng = np.random.default_rng(sum(ops) * 131 + len(ops))
    held: list[int] = []
    for op in ops:
        if op <= 3:                       # admit a new request
            slot = pool.admit(int(rng.integers(1, cap + 1)))
            if slot is not None:
                held.append(slot)
        elif op <= 6 and held:            # speculative multi-token append
            slot = int(rng.choice(held))
            room = cap - int(pool.lengths[slot])
            n = int(rng.integers(0, room + 1))
            pool.advance(slot, n)
        elif op <= 8 and held:            # roll back a rejected tail
            slot = int(rng.choice(held))
            hw = pool.allocator.high_water
            n = int(rng.integers(0, int(pool.lengths[slot]) + 1))
            pool.rollback(slot, n)
            assert pool.allocator.high_water == hw, \
                "rollback touched the allocator"
        elif held:                        # release a finished request
            slot = held.pop(int(rng.integers(len(held))))
            pool.release(slot)
        pool.allocator.check_invariants()
        rows = {s: set(pool.block_tables[s][pool.block_tables[s] > 0])
                for s in held}
        for a in held:
            assert int(pool.lengths[a]) <= cap
            for b in held:
                if a < b:
                    assert not rows[a] & rows[b], "block tables alias"
    for slot in held:
        pool.release(slot)
    assert pool.allocator.n_live == 0 and pool.n_free == n_slots


@settings(max_examples=20)
@given(ops=st.lists(st.integers(min_value=0, max_value=11),
                    min_size=1, max_size=50))
def test_pool_lazy_grow_preempt_resume_properties(ops):
    """Random lazy-admit / grow / advance / preempt / resume / release
    interleavings (the elastic-memory lifecycle): page conservation holds
    at every step, block tables never alias, a slot's length never
    exceeds the reach of the pages it actually holds, preemption returns
    every page to the free list, and a preempted demand can always be
    re-admitted once enough pages are free (recompute-prefill resume)."""
    ps, n_slots, n_pages = 4, 3, 11
    avals = {"k": jax.ShapeDtypeStruct((n_pages, ps, 1, 2), jnp.float32)}
    pool = PagedKVPool(avals, n_slots, ps, n_pages, max_pages_per_slot=4)
    rng = np.random.default_rng(sum(ops) * 977 + len(ops))
    held: list[int] = []
    preempted_demands: list[int] = []     # page counts awaiting resume
    for op in ops:
        if op <= 2:                       # lazy admit: prompt pages + 1
            n = int(rng.integers(1, 4))
            slot = pool.admit_pages(n)
            if slot is not None:
                held.append(slot)
        elif op <= 4 and preempted_demands:   # resume a preempted request
            n = preempted_demands[0]
            slot = pool.admit_pages(n)
            if slot is not None:
                preempted_demands.pop(0)
                held.append(slot)
        elif op <= 6 and held:            # grow one page at a boundary
            slot = int(rng.choice(held))
            before = len(pool.allocator.pages_of(slot))
            grew = pool.grow(slot)
            after = len(pool.allocator.pages_of(slot))
            assert after == before + (1 if grew else 0)
        elif op <= 8 and held:            # advance within reserved reach
            slot = int(rng.choice(held))
            room = pool.reserved_tokens(slot) - int(pool.lengths[slot])
            if room > 0:
                pool.advance(slot, int(rng.integers(1, room + 1)))
        elif op <= 9 and held:            # preempt a victim
            slot = held.pop(int(rng.integers(len(held))))
            n_held = len(pool.allocator.pages_of(slot))
            free0 = pool.allocator.n_free
            freed = pool.preempt(slot)
            assert freed == n_held
            assert pool.allocator.n_free == free0 + n_held
            assert (pool.block_tables[slot] == 0).all()
            preempted_demands.append(min(n_held + 1, 4))
        elif held:                        # release a finished request
            pool.release(held.pop(int(rng.integers(len(held)))))
        pool.allocator.check_invariants()
        rows = {s: set(pool.block_tables[s][pool.block_tables[s] > 0])
                for s in held}
        for a in held:
            assert int(pool.lengths[a]) <= pool.reserved_tokens(a)
            for b in held:
                if a < b:
                    assert not rows[a] & rows[b], "block tables alias"
    for slot in held:
        pool.release(slot)
    assert pool.allocator.n_live == 0 and pool.n_free == n_slots
    # every preempted demand is re-admittable from an empty pool
    for n in preempted_demands:
        slot = pool.admit_pages(n)
        assert slot is not None
        pool.release(slot)


def test_pool_grow_guards_and_bounds():
    avals = {"k": jax.ShapeDtypeStruct((7, 4, 1, 2), jnp.float32)}
    pool = PagedKVPool(avals, 2, 4, 7, max_pages_per_slot=3)
    with pytest.raises(ValueError):
        pool.grow(0)                      # inactive slot
    slot = pool.admit_pages(1)
    assert pool.reserved_tokens(slot) == 4
    assert pool.grow(slot)                # 2nd page
    assert pool.reserved_tokens(slot) == 8
    other = pool.admit_pages(3)           # holds its max; 1 page left free
    assert other is not None
    assert not pool.grow(other)           # block table full (max 3 pages)
    assert pool.admit_pages(4) is None    # beyond max_pages_per_slot
    assert pool.grow(slot)                # takes the last free page
    assert not pool.grow(slot)            # table full AND allocator dry
    pool.allocator.check_invariants()
    assert pool.preempt(other) == 3
    assert pool.n_preempts == 1
    assert pool.allocator.n_free == 3
    pool.release(slot)
    assert pool.allocator.n_live == 0


def test_allocator_free_run_histogram():
    a = PageAllocator(10)                 # pages 1..9 free: one run of 9
    assert a.free_run_histogram() == {9: 1}
    a.alloc("r0", 3)                      # takes 1,2,3
    a.alloc("r1", 2)                      # takes 4,5
    a.free("r0")                          # free: 1,2,3 + 6..9
    hist = a.free_run_histogram()
    assert hist == {3: 1, 4: 1}
    assert sum(n * c for n, c in hist.items()) == a.n_free
    a.free("r1")
    assert a.free_run_histogram() == {9: 1}
    assert PageAllocator(2).free_run_histogram() == {1: 1}


def test_pool_rollback_guards():
    avals = {"k": jax.ShapeDtypeStruct((9, 4, 1, 2), jnp.float32)}
    pool = PagedKVPool(avals, 2, 4, 9, max_pages_per_slot=2)
    slot = pool.admit(8)
    pool.advance(slot, 5)
    with pytest.raises(ValueError):
        pool.rollback(slot, 6)            # more than is written
    with pytest.raises(ValueError):
        pool.rollback(slot, -1)
    with pytest.raises(ValueError):
        pool.rollback(1 - slot, 1)        # inactive slot
    pool.rollback(slot, 5)
    assert int(pool.lengths[slot]) == 0
    pool.release(slot)


def test_pool_advance_overflow_guarded():
    avals = {"k": jax.ShapeDtypeStruct((9, 4, 1, 2), jnp.float32)}
    pool = PagedKVPool(avals, 2, 4, 9, max_pages_per_slot=2)
    slot = pool.admit(8)
    pool.advance(slot, 8)
    with pytest.raises(ValueError):
        pool.advance(slot, 1)             # beyond the block table
    with pytest.raises(ValueError):
        pool.advance(1 - slot, 1)         # inactive slot


# ---------------------------------------------------------------------------
# Fragmentation regression: in-flight capacity at fixed memory
# ---------------------------------------------------------------------------


def test_paged_admits_more_requests_at_fixed_memory():
    """The headline paged-KV win: at the same KV HBM budget, a mixed-length
    trace fits >= 2x more concurrent requests than whole-cache slots,
    because each request reserves only its own worst case, not max_len."""
    max_len, ps = 256, 16
    kv, hd = 2, 8
    dtype = jnp.float32

    # budget: exactly 4 whole-cache slots
    slot_avals = {"k": jax.ShapeDtypeStruct((1, max_len, kv, hd), dtype),
                  "v": jax.ShapeDtypeStruct((1, max_len, kv, hd), dtype)}
    slot_pool = SlotKVPool(slot_avals, 4)
    budget = slot_pool.hbm_bytes()

    # the same bytes as pages (minus the null page)
    page_avals = {"k": jax.ShapeDtypeStruct((1, ps, kv, hd), dtype),
                  "v": jax.ShapeDtypeStruct((1, ps, kv, hd), dtype)}
    page_bytes = sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                     for s in page_avals.values())
    n_pages = budget // page_bytes + 1
    avals = {k: jax.ShapeDtypeStruct((int(n_pages),) + s.shape[1:], s.dtype)
             for k, s in page_avals.items()}
    pool = PagedKVPool(avals, n_slots=64, page_size=ps,
                       n_pages=int(n_pages),
                       max_pages_per_slot=pages_for(max_len, ps))
    assert pool.hbm_bytes() <= budget + page_bytes

    # staggered mixed-length demands: mostly short, a long tail
    rng = np.random.default_rng(0)
    demands = [int(rng.choice([24, 32, 48, 200], p=[.4, .3, .2, .1]))
               for _ in range(64)]
    slot_admitted = slot_pool.n_slots                 # whole-cache capacity
    paged_admitted = 0
    for need in demands:
        if pool.admit(need) is not None:
            paged_admitted += 1
    assert paged_admitted >= 2 * slot_admitted, (
        f"paged pool admitted {paged_admitted} vs slot {slot_admitted} "
        f"at the same HBM budget")
    pool.allocator.check_invariants()
