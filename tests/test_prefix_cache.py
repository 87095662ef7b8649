"""Cross-request prefix caching: refcounted allocator sharing/CoW
conservation properties, the prefix index lifecycle (register -> lookup
-> reclaim), preempt-of-a-sharer safety, and engine-level bit-identity
of cache-hit serving vs a cold pool."""
import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import given, settings

import jax
import jax.numpy as jnp

from repro.serve.cache import (PageAllocator, PagedKVPool, PrefixIndex,
                               pages_for)
from repro.serve.memory import MemoryGovernor, MemoryPolicy


def _pool(n_pages=17, ps=8, n_slots=4, max_pages=6, prefix=True):
    avals = {"k": jax.ShapeDtypeStruct((n_pages, ps, 1, 2), jnp.float32)}
    pool = PagedKVPool(avals, n_slots, ps, n_pages, max_pages)
    pool.prefix_enabled = prefix
    return pool


# ---------------------------------------------------------------------------
# Refcounted PageAllocator
# ---------------------------------------------------------------------------


def test_allocator_share_refcounts_and_deferred_reclaim():
    a = PageAllocator(8)
    p = a.alloc("r0", 3)
    a.share("r1", p[:2])                  # two owners on pages p0, p1
    assert a.refcount(p[0]) == 2 and a.refcount(p[2]) == 1
    assert a.n_held("r1") == 2
    a.check_invariants()
    # freeing the sharer reclaims nothing (r0 still maps everything)
    assert a.free("r1") == []
    assert a.refcount(p[0]) == 1
    # last reference: everything comes back
    assert set(a.free("r0")) == set(p)
    assert a.n_live == 0 and a.n_free == 7
    a.check_invariants()


def test_allocator_share_guards():
    a = PageAllocator(8)
    p = a.alloc("r0", 2)
    with pytest.raises(ValueError):       # not live
        a.share("r1", [7])
    a.share("r1", p)
    with pytest.raises(ValueError):       # already mapped by this owner
        a.share("r1", [p[0]])
    with pytest.raises(ValueError):       # duplicates in one request
        a.share("r2", [p[0], p[0]])
    a.check_invariants()


def test_allocator_drop_and_replace():
    a = PageAllocator(8)
    p = a.alloc("r0", 3)
    a.share("idx", [p[1]])
    assert a.drop("idx", p[1]) is False   # r0 still maps it
    assert a.refcount(p[1]) == 1
    with pytest.raises(ValueError):
        a.drop("idx", p[1])               # no longer mapped by idx
    # replace = CoW bookkeeping: fresh page lands at the old page's
    # position in the owner's mapping, old reference drops
    a.share("r1", [p[0]])
    new = a.replace("r0", p[0])
    assert new is not None and new != p[0]
    assert a.pages_of("r0")[0] == new     # in place, order kept
    assert a.refcount(p[0]) == 1 and a.refcount(new) == 1
    a.check_invariants()
    # replace with a dry free list reports failure, mutates nothing
    a.alloc("fill", a.n_free)
    assert a.replace("r0", new) is None
    a.check_invariants()


@settings(max_examples=30, deadline=None)
@given(ops=st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3)),
                    min_size=1, max_size=40))
def test_allocator_share_free_sequences_conserve_pages(ops):
    """Random share/free/drop/replace interleavings: refcounts always
    equal the number of owners mapping each page, pages are reclaimed
    exactly at refcount zero, and freeing everyone restores the pool.
    One owner is under incremental solo accounting (track_solo), so
    check_invariants also cross-checks the O(1) counter against a
    recount at every step."""
    a = PageAllocator(12)
    a.track_solo("o1")
    owners = {}
    for i, (op, owner_i) in enumerate(ops):
        name = f"o{owner_i}"
        if op == 0 and name not in owners:
            got = a.alloc(name, min(2, a.n_free))
            if got is not None:
                owners[name] = got
        elif op == 1 and owners and name not in owners:
            src = sorted(owners)[owner_i % len(owners)]
            share = [p for p in owners[src]
                     if p not in a.pages_of(name)][:2]
            if share:
                a.share(name, share)
                owners[name] = a.pages_of(name)
        elif op == 2 and name in owners:
            a.free(name)
            del owners[name]
        elif op == 3 and name in owners and a.pages_of(name):
            new = a.replace(name, a.pages_of(name)[0])
            if new is not None:
                owners[name] = a.pages_of(name)
        a.check_invariants()
    for name in list(owners):
        a.free(name)
        a.check_invariants()
    assert a.n_live == 0 and a.n_free == 11


# ---------------------------------------------------------------------------
# PrefixIndex
# ---------------------------------------------------------------------------


def test_prefix_index_roundtrip_and_divergence():
    idx = PrefixIndex()
    toks = np.arange(40, dtype=np.int32)
    assert idx.register(toks, [3, 5, 7, 9], 8, 4) == [3, 5, 7, 9]
    # full-page prefix lookups walk the chain in order
    assert idx.lookup(toks, 8) == [3, 5, 7, 9]
    assert idx.lookup(toks[:17], 8) == [3, 5]
    # divergence inside page 2 stops the walk after 2 pages
    div = toks.copy()
    div[20] += 1
    assert idx.lookup(div, 8) == [3, 5]
    # a different history sharing page *content* mid-stream never
    # collides: keys hash the whole prefix, not the page chunk
    other = toks + 100
    assert idx.lookup(other, 8) == []
    # re-registering is idempotent (first writer wins)
    assert idx.register(toks, [11, 12, 13, 14], 8, 4) == []
    assert idx.lookup(toks, 8) == [3, 5, 7, 9]


def test_prefix_index_lru_eviction_order():
    idx = PrefixIndex()
    a = np.arange(16, dtype=np.int32)
    b = np.arange(16, dtype=np.int32) + 50
    idx.register(a, [1, 2], 8, 2)
    idx.register(b, [3, 4], 8, 2)
    idx.lookup(a, 8)                      # touch a: b's pages now oldest
    assert idx.lru_pages()[:2] == [3, 4]
    idx.drop_page(3)
    assert idx.lookup(b, 8) == []         # chain broken at page 0
    assert 4 in idx.lru_pages()           # orphaned tail still evictable


# ---------------------------------------------------------------------------
# PagedKVPool sharing lifecycle
# ---------------------------------------------------------------------------


def test_pool_register_lookup_admit_shared_roundtrip():
    pool = _pool()
    toks = np.arange(30, dtype=np.int32)
    s0 = pool.admit_pages(4)
    pool.advance(s0, 29)                  # rows 0..28 written
    assert pool.register_prefix(s0, toks) == 3   # 29 // 8 full pages
    owned = pool.allocator.pages_of(s0)
    pool.release(s0)
    assert pool.allocator.n_live == 3     # index holds the published pages
    # a same-prefix prompt maps them shared; matched is capped at size-1
    shared, matched = pool.prefix_lookup(toks[:25])
    assert shared == owned[:3] and matched == 24
    s1 = pool.admit_shared(1, shared)
    assert pool.reserved_tokens(s1) == 32
    assert [int(p) for p in pool.block_tables[s1, :4]] == shared + \
        [int(pool.block_tables[s1, 3])]
    assert all(pool.allocator.refcount(p) == 2 for p in shared)
    pool.advance(s1, matched)
    pool.allocator.check_invariants()
    # a longer history matches only its full-page run (no mid-page cap:
    # 21 tokens walk 2 full pages, and 16 < 20 leaves suffix to prefill)
    shared2, matched2 = pool.prefix_lookup(toks[:21])
    assert matched2 == 16 and len(shared2) == 2


def test_pool_cow_privatises_shared_page_before_write():
    pool = _pool()
    toks = np.arange(17, dtype=np.int32)
    s0 = pool.admit_pages(3)
    pool.advance(s0, 16)
    pool.register_prefix(s0, toks)
    pool.release(s0)
    shared, matched = pool.prefix_lookup(toks)     # 2 pages, 16 tokens
    s1 = pool.admit_shared(1, shared)
    pool.advance(s1, matched)
    # write device content into the shared page so the copy is checkable
    k = pool.pages["k"].at[shared[1], :, 0, 0].set(7.0)
    pool.pages = {"k": k}
    # next write lands at row 16 = page 2 (fresh): nothing to copy...
    assert pool.cow_for_write(s1, 1) and pool.cow_copies == 0
    # ...but a mid-page adoption must copy.  Rebuild that shape: roll back
    # to 15 via a fresh mapping (rollback itself would CoW — test below)
    pool.release(s1)
    shared2, matched2 = pool.prefix_lookup(toks[:16])   # capped at 15
    assert matched2 == 15
    s2 = pool.admit_shared(1, shared2)
    pool.advance(s2, matched2)
    old = int(pool.block_tables[s2, 1])
    assert pool.cow_for_write(s2, 1)
    new = int(pool.block_tables[s2, 1])
    assert pool.cow_copies == 1 and new != old
    assert pool.allocator.refcount(old) == 1       # back to index-only
    # device rows were copied, content preserved
    assert float(np.asarray(pool.pages["k"])[new, 0, 0, 0]) == 7.0
    pool.allocator.check_invariants()


def test_pool_rollback_defensively_privatises():
    pool = _pool()
    toks = np.arange(17, dtype=np.int32)
    s0 = pool.admit_pages(3)
    pool.advance(s0, 16)
    pool.register_prefix(s0, toks)
    pool.release(s0)
    shared, matched = pool.prefix_lookup(toks[:16])     # 15 tokens, 2 pages
    s1 = pool.admit_shared(1, shared)
    pool.advance(s1, matched)
    old = int(pool.block_tables[s1, 1])
    pool.rollback(s1, 1)                  # truncates into the shared page
    assert int(pool.block_tables[s1, 1]) != old
    assert pool.cow_copies == 1
    pool.allocator.check_invariants()


def test_cow_drops_index_ref_when_free_list_dry():
    """A page shared only with the prefix index, a dry free list and no
    other reclaimable page: reclaim_prefix skips refcount-2 pages so it
    can never unpin the index's reference on the slot's own page — CoW
    must privatise *in place* by dropping the index's reference (no
    device copy) instead of failing and stalling the slot forever."""
    pool = _pool(n_pages=7, ps=8, n_slots=4, max_pages=6)
    toks = np.arange(17, dtype=np.int32)
    s0 = pool.admit_pages(3)
    pool.advance(s0, 16)
    pool.register_prefix(s0, toks)        # 2 pages indexed
    pool.release(s0)
    shared, matched = pool.prefix_lookup(toks[:16])    # capped at 15
    assert matched == 15 and len(shared) == 2
    s1 = pool.admit_shared(1, shared)
    pool.advance(s1, matched)
    assert pool.admit_pages(3) is not None             # free list now dry
    assert pool.allocator.n_free == 0 and pool.n_reclaimable == 0
    old = int(pool.block_tables[s1, 1])
    assert pool.allocator.refcount(old) == 2           # s1 + the index
    # the write into row 15 proceeds: same page, now private, entry gone
    assert pool.cow_for_write(s1, 1)
    assert int(pool.block_tables[s1, 1]) == old
    assert pool.allocator.refcount(old) == 1
    assert pool.cow_copies == 0 and pool.prefix_evictions == 1
    assert pool.prefix_lookup(toks[:16])[0] == shared[:1]   # chain broken
    pool.allocator.check_invariants()


def test_pool_preempt_of_sharer_never_frees_survivor_pages():
    pool = _pool()
    toks = np.arange(25, dtype=np.int32)
    s0 = pool.admit_pages(4)
    pool.advance(s0, 24)
    pool.register_prefix(s0, toks)
    shared, matched = pool.prefix_lookup(toks)
    s1 = pool.admit_shared(1, shared)     # survivor maps s0's pages
    pool.advance(s1, matched)
    live0 = pool.allocator.n_live
    freed = pool.preempt(s0)              # victim shares 3 of its 4 pages
    assert freed == 1                     # only the private page reclaimed
    assert pool.allocator.n_live == live0 - 1
    for p in shared:
        assert pool.allocator.refcount(p) == 2     # survivor + index
    pool.allocator.check_invariants()
    # survivor's reach unchanged; its block table still points at the run
    assert pool.reserved_tokens(s1) == 32
    assert [int(p) for p in pool.block_tables[s1, :3]] == shared


def test_pool_reclaims_index_only_pages_for_admission_and_growth():
    pool = _pool(n_pages=9, ps=8, max_pages=8)     # 8 allocatable
    toks = np.arange(33, dtype=np.int32)
    s0 = pool.admit_pages(5)
    pool.advance(s0, 32)
    pool.register_prefix(s0, toks)                 # 4 pages indexed
    pool.release(s0)
    assert pool.n_reclaimable == 4 and pool.allocator.n_free == 4
    # admission needing 6 fresh pages evicts LRU index pages to fit
    s1 = pool.admit_pages(6)
    assert s1 is not None
    assert pool.prefix_evictions == 2
    # growth with a dry free list reclaims one more
    assert pool.allocator.n_free == 0
    assert pool.grow(s1)
    assert pool.prefix_evictions == 3
    # LRU eviction took the chain's *front*: the surviving page is an
    # orphaned tail — unreachable by lookup, but still reclaimable
    pool.release(s1)
    assert pool.prefix_lookup(toks) == ([], 0)
    assert pool.n_reclaimable == 1
    pool.allocator.check_invariants()


def test_admit_shared_never_sacrifices_its_own_hit():
    pool = _pool(n_pages=5, ps=8, max_pages=4)     # 4 allocatable
    toks = np.arange(9, dtype=np.int32)
    s0 = pool.admit_pages(2)
    pool.advance(s0, 8)
    pool.register_prefix(s0, toks)                 # 1 page indexed
    pool.release(s0)                               # 3 free, 1 index-only
    shared, matched = pool.prefix_lookup(toks)
    assert len(shared) == 1 and matched == 8
    # demand 4 fresh pages with 3 free: the only reclaimable page is the
    # hit itself -> admission fails rather than evicting what it shares
    assert pool.admit_shared(4, shared) is None
    assert pool.prefix_evictions == 0
    s1 = pool.admit_shared(3, shared)              # fresh 3 + the hit fits
    assert s1 is not None
    assert pool.allocator.refcount(shared[0]) == 2
    pool.allocator.check_invariants()


def test_reserved_tokens_counts_shared_pages_once():
    """The O(1) held-page count (not a block-table nonzero scan) is also
    the only correct answer under sharing: a shared page is one page of
    reach for each owner that maps it."""
    pool = _pool()
    toks = np.arange(17, dtype=np.int32)
    s0 = pool.admit_pages(3)
    pool.advance(s0, 16)
    pool.register_prefix(s0, toks)
    shared, _ = pool.prefix_lookup(toks)
    s1 = pool.admit_shared(2, shared)
    assert pool.reserved_tokens(s0) == 3 * 8
    assert pool.reserved_tokens(s1) == 4 * 8
    total_held = (pool.allocator.n_held(s0) + pool.allocator.n_held(s1)
                  + len(list(pool.prefix.pages())))
    assert total_held > pool.allocator.n_live      # sharing overcommits


@settings(max_examples=20, deadline=None)
@given(ops=st.lists(st.integers(0, 2), min_size=1, max_size=24),
       seed=st.integers(0, 5))
def test_pool_share_cow_release_property(ops, seed):
    """Random admit-hit/advance+CoW/release sequences against one shared
    prompt: allocator invariants hold throughout, no reclaim while any
    owner (or the index) still maps a page, and a final release of every
    slot leaves exactly the index's pages live."""
    rng = np.random.default_rng(seed)
    pool = _pool(n_pages=21, n_slots=3, max_pages=6)
    toks = rng.integers(0, 1000, (33,)).astype(np.int32)
    s0 = pool.admit_pages(5)
    pool.advance(s0, 32)
    pool.register_prefix(s0, toks)
    pool.release(s0)
    idx_pages = set(pool.prefix.pages())
    slots = []
    for op in ops:
        if op == 0 and pool.n_free:
            shared, matched = pool.prefix_lookup(toks)
            s = pool.admit_shared(1, shared)
            if s is not None:
                pool.advance(s, matched)
                slots.append(s)
        elif op == 1 and slots:
            s = slots[rng.integers(len(slots))]
            if (pool.reserved_tokens(s) - int(pool.lengths[s]) >= 1
                    and pool.cow_for_write(s, 1)):
                pool.advance(s, 1)
        elif op == 2 and slots:
            slots.remove(s := slots[rng.integers(len(slots))])
            pool.release(s)
        pool.allocator.check_invariants()
        # the incremental reclaimable counter always matches a recount
        assert pool.n_reclaimable == sum(
            1 for p in pool.prefix.pages()
            if pool.allocator.refcount(p) == 1)
        for p in idx_pages:               # the index never loses its pages
            assert pool.allocator.refcount(p) >= 1
    for s in slots:
        pool.release(s)
    pool.allocator.check_invariants()
    assert pool.allocator.n_live == len(idx_pages)


# ---------------------------------------------------------------------------
# Governor: shared-aware victim scoring + prefix-aware watermark
# ---------------------------------------------------------------------------


class _Req:
    def __init__(self, rid, t_admit):
        self.rid, self.t_admit, self.n_preempts = rid, t_admit, 0


def test_pick_victim_prefers_unshared_over_hotter_shared():
    pool = _pool()
    gov = MemoryGovernor(pool, MemoryPolicy(max_preempts=4))
    toks = np.arange(25, dtype=np.int32)
    s_old = pool.admit_pages(4)           # donor: publishes 3 pages
    pool.advance(s_old, 24)
    pool.register_prefix(s_old, toks)
    pool.release(s_old)
    shared, matched = pool.prefix_lookup(toks)
    s_shared = pool.admit_shared(1, shared)        # maps 3 shared pages
    pool.advance(s_shared, matched)
    s_plain = pool.admit_pages(4)                  # private pages only
    # LIFO alone would evict the *younger* sharer; the shared-page cost
    # channel (refcount N = N requests' recompute) spares it
    residents = {s_plain: _Req(0, 0.1), s_shared: _Req(1, 0.9)}
    assert gov.pick_victim(residents) == s_plain
    assert gov.shared_spared == 1
    # all-private pools degrade to pure LIFO (cost 0 everywhere)
    pool.release(s_shared)
    residents = {s_plain: _Req(0, 0.1)}
    assert gov.pick_victim(residents) == s_plain
    assert gov.shared_spared == 1


def test_admit_reserves_only_unshared_remainder_and_counts_reclaimable():
    pool = _pool(n_pages=11, ps=8, max_pages=6)    # 10 allocatable
    gov = MemoryGovernor(pool, MemoryPolicy(reservation="lazy",
                                            watermark=0.5))
    toks = np.arange(25, dtype=np.int32)
    s0 = pool.admit_pages(4)
    pool.advance(s0, 24)
    pool.register_prefix(s0, toks)
    pool.release(s0)                      # 3 indexed (reclaimable), 7 free
    shared, _ = pool.prefix_lookup(toks)
    # lazy demand 25 prompt -> 4+1 pages, minus 3 shared = 2 fresh; the
    # watermark sees free-equivalent 7 + 3 = 10, so 10 - 2 >= 5 admits
    # (a reclaimable-blind governor would starve admission to protect
    # droppable cache)
    s1 = gov.admit(prompt_tokens=25, total_tokens=48, shared_pages=shared)
    assert s1 is not None
    assert pool.allocator.n_held(s1) == 5
    assert pool.allocator.n_free == 5


# ---------------------------------------------------------------------------
# set_policy plumbing + bounded trace (satellite bugfixes)
# ---------------------------------------------------------------------------


def test_set_policy_plumbs_max_preempts_and_rejects_unknown():
    gov = MemoryGovernor(_pool(), MemoryPolicy())
    gov.set_policy(max_preempts=0)
    assert gov.policy.max_preempts == 0
    gov.set_policy(reservation="lazy", watermark=0.3, max_preempts=7)
    assert (gov.policy.reservation, gov.policy.watermark,
            gov.policy.max_preempts) == ("lazy", 0.3, 7)
    with pytest.raises(ValueError):
        gov.set_policy(reservation="elastic")
    with pytest.raises(ValueError):
        gov.set_policy(max_preempts=-1)
    assert gov.policy.reservation == "lazy"        # reject mutated nothing


def test_free_page_trace_bounded_with_exact_min():
    pool = _pool(n_pages=40)
    gov = MemoryGovernor(pool, MemoryPolicy())
    slot = pool.admit_pages(2)
    lows = []
    for i in range(5000):
        if i == 2500:                     # a one-step dip between samples
            for _ in range(20):
                pool.grow(slot)
            gov.note_step(0)
            lows.append(pool.allocator.n_free)
            pool.release(slot)
            slot = pool.admit_pages(2)
        gov.note_step(0)
    assert len(gov.free_page_trace) < gov._TRACE_CAP
    # a lower-occupancy regime at the very END of the serve: the summary
    # must stride across the whole buffer, not truncate it — the old
    # trace[:64] reported only the first 64 samples and silently dropped
    # the last portion of a long serve
    for _ in range(3):
        pool.admit_pages(6)
    end_free = pool.allocator.n_free
    assert end_free < min(lows)
    for _ in range(400):
        gov.note_step(0)
    s = gov.summary()
    assert s["free_pages_min"] == end_free         # exact, not sampled
    assert len(s["free_page_trace"]) <= 64
    assert min(s["free_page_trace"]) <= end_free   # tail regime reported


# ---------------------------------------------------------------------------
# Engine lifecycle: cache-hit serving is bit-identical to a cold pool
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def shared_trace():
    from repro.configs.registry import get_config
    from repro.models.model import build
    from repro.serve.scheduler import Request
    cfg = get_config("stablelm-1.6b").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    P = rng.integers(0, cfg.vocab_size, (24,)).astype(np.int32)
    div = np.concatenate(
        [P[:16], rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)])

    def mk():
        # r0 populates the index; r1/r2 are full-prefix hits; r3 diverges
        # after 16 tokens (partial hit + CoW on its own suffix pages)
        return [Request(rid=0, prompt=P.copy(), max_new_tokens=8),
                Request(rid=1, prompt=P.copy(), max_new_tokens=8),
                Request(rid=2, prompt=P.copy(), max_new_tokens=10),
                Request(rid=3, prompt=div.copy(), max_new_tokens=8)]

    return model, params, mk


def _engine(model, params, prefix, **kw):
    from repro.serve.engine import Engine, ServeConfig
    base = dict(max_len=40, max_slots=2, page_size=8, prefill_chunk=8,
                spec_depth=2, prefix_cache=prefix)
    base.update(kw)
    return Engine(model, params, serve_cfg=ServeConfig(**base))


def test_prefix_serving_bit_identical_and_saves_prefill(shared_trace):
    from repro.serve.scheduler import RequestState, summarize
    model, params, mk = shared_trace
    cold_reqs = mk()
    _engine(model, params, "off").serve(cold_reqs)
    warm = _engine(model, params, "on")
    warm_reqs = mk()
    res = warm.serve(warm_reqs)
    for rc, rw in zip(cold_reqs, warm_reqs):
        assert rw.state is RequestState.DONE
        assert rw.out_tokens == rc.out_tokens, f"req {rw.rid} diverged"
    pf = res["memory"]["prefix"]
    assert pf["hit_requests"] >= 2 and pf["tokens_saved"] > 0
    # default reservation is full: the engine trims the partially-adopted
    # boundary page at admission, so every adopted run is page-aligned
    # and a full-mode serve never CoWs — nor preempts/stalls (the
    # preemption-free contract survives sharing)
    assert pf["cow_copies"] == 0
    assert pf["tokens_saved"] % 8 == 0
    assert res["memory"]["preemptions"] == 0
    assert res["memory"]["stall_steps"] == 0
    s = summarize(warm_reqs)
    assert s["prefix_hit_tokens"] == pf["tokens_saved"]
    assert s["prefix_hit_requests"] == pf["hit_requests"]
    # all requests done: only the index still holds pages, and a fresh
    # same-prefix trace would hit it again
    warm._pool.allocator.check_invariants()
    assert warm._pool.allocator.n_live == len(list(warm._pool.prefix.pages()))
    assert warm._pool.prefix_lookup(mk()[0].token_history())[1] > 0


def test_prefix_serving_survives_overcommit_preemption(shared_trace):
    """Sharing + lazy overcommit: preempting a sharer never corrupts a
    survivor (CoW/refcounts), preempted requests re-enter through the
    prefix path (hitting pages they may have published themselves), and
    the trace stays bit-identical."""
    from repro.serve.scheduler import RequestState
    model, params, mk = shared_trace
    cold_reqs = mk()
    _engine(model, params, "off").serve(cold_reqs)
    eng = _engine(model, params, "on", max_slots=4, kv_pages=13,
                  reservation="lazy", mem_watermark=0.0)
    reqs = mk()
    res = eng.serve(reqs)
    for rc, rw in zip(cold_reqs, reqs):
        assert rw.state is RequestState.DONE
        assert rw.out_tokens == rc.out_tokens, f"req {rw.rid} diverged"
    eng._pool.allocator.check_invariants()
    assert res["memory"]["prefix"]["tokens_saved"] > 0


def test_prefix_lazy_mode_cows_partial_boundary_page(shared_trace):
    """Lazy reservation adopts the partially-covered boundary page of a
    full-prefix hit (matched is capped at hist-1, landing mid-page), so
    the hit's first decode write must privatise it — with 2 slots the
    later requests admit after the donor published its full run, which
    pins the mid-page shape.  Output stays bit-identical throughout."""
    from repro.serve.scheduler import RequestState
    model, params, mk = shared_trace
    cold_reqs = mk()
    _engine(model, params, "off").serve(cold_reqs)
    eng = _engine(model, params, "on", reservation="lazy")
    reqs = mk()
    res = eng.serve(reqs)
    for rc, rw in zip(cold_reqs, reqs):
        assert rw.state is RequestState.DONE
        assert rw.out_tokens == rc.out_tokens, f"req {rw.rid} diverged"
    assert res["memory"]["prefix"]["cow_copies"] >= 1
    eng._pool.allocator.check_invariants()


def test_moe_prefix_cache_forced_off_bit_identical():
    """MoE capacity groups route by token-group length, so prefilling
    only a cache-hit suffix (zero-padded back to the feed length) would
    route — and drop — tokens differently than whole-prompt cold
    prefill, diverging the suffix K/V.  The engine therefore forces
    prefix sharing off for n_experts models (mirroring the spec-depth
    gate), and ``--prefix-cache on`` stays bit-identical to ``off``."""
    from repro.configs.registry import get_config
    from repro.models.model import build
    from repro.serve.scheduler import Request, RequestState
    cfg = get_config("granite-moe-1b-a400m").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(3)
    P = rng.integers(0, cfg.vocab_size, (24,)).astype(np.int32)

    def mk():
        return [Request(rid=i, prompt=P.copy(), max_new_tokens=6)
                for i in range(3)]

    off_reqs, on_reqs = mk(), mk()
    _engine(model, params, "off").serve(off_reqs)
    on = _engine(model, params, "on")
    assert on.prefix_cache_for(on.plan) is False       # forced off for MoE
    res = on.serve(on_reqs)
    pf = res["memory"]["prefix"]
    assert not pf["enabled"]
    assert pf["hit_requests"] == 0 and pf["tokens_saved"] == 0
    for ro, rn in zip(off_reqs, on_reqs):
        assert rn.state is RequestState.DONE
        assert rn.out_tokens == ro.out_tokens, f"req {rn.rid} diverged"
