"""Serving: static batched generation, continuous batching over the slot
pool (scheduler invariants, slot hygiene, static/continuous greedy
equivalence), and counter-driven plan selection."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.counters import Counters
from repro.models.model import build
from repro.serve.cache import SlotKVPool
from repro.serve.engine import Engine, PlanDecider, ServeConfig
from repro.serve.scheduler import (Request, RequestState, Scheduler,
                                   summarize)


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("stablelm-1.6b").reduced()
    model = build(cfg)
    # f32 params: greedy-argmax equality between the decode and forward
    # paths is exact in f32 (bf16 leaves argmax ties to op order)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, Engine(model, params,
                       serve_cfg=ServeConfig(max_len=64, temperature=0.0,
                                             max_slots=3))


def test_generate_shapes(engine):
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    out = eng.generate(prompts, 8)
    assert out["tokens"].shape == (3, 8)
    assert out["decode_tok_per_s"] > 0


def test_greedy_is_deterministic(engine):
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    a = eng.generate(prompts, 6)["tokens"]
    b = eng.generate(prompts, 6)["tokens"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_generate_matches_teacher_forced_forward(engine):
    """Engine greedy decode == argmax of the forward logits, step by step."""
    cfg, eng = engine
    model = eng.model
    params = eng.params
    prompts = jax.random.randint(jax.random.PRNGKey(3), (1, 10), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    out = eng.generate(prompts, 4)["tokens"]
    toks = prompts
    for t in range(4):
        logits, _ = model.forward(params, {"tokens": toks})
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)
        assert int(nxt[0]) == int(out[0, t])
        toks = jnp.concatenate([toks, nxt[:, None].astype(jnp.int32)], axis=1)


# ---------------------------------------------------------------------------
# Scheduler invariants (pure host logic, no jax)
# ---------------------------------------------------------------------------


def _req(rid, arrival=0.0, gen=4, plen=4):
    return Request(rid=rid, prompt=np.arange(1, plen + 1, dtype=np.int32),
                   max_new_tokens=gen, arrival_s=arrival)


def test_scheduler_fifo_and_lifecycle():
    sched = Scheduler()
    for i, t in enumerate([0.3, 0.0, 0.1]):
        sched.submit(_req(i, arrival=t))
    sched.sort_queue()
    # not yet arrived
    assert not sched.has_ready(-1.0)
    # arrival order, not submit order
    order = []
    while sched.has_ready(1.0):
        r = sched.pop_ready(1.0)
        assert r.state is RequestState.PREFILL
        order.append(r.rid)
    assert order == [1, 2, 0]


def test_scheduler_bind_complete_invariants():
    sched = Scheduler()
    for i in range(3):
        sched.submit(_req(i))
    a = sched.pop_ready(0.0)
    b = sched.pop_ready(0.0)
    sched.bind(a, 0, 0.0)
    with pytest.raises(ValueError):        # no double-binding a slot
        sched.bind(b, 0, 0.0)
    sched.bind(b, 1, 0.0)
    assert not sched.done()
    sched.complete(a, 1.0)
    assert a.state is RequestState.DONE and a.slot is None
    with pytest.raises(ValueError):        # no double-complete
        sched.complete(a, 1.0)
    sched.complete(b, 1.0)
    assert not sched.done()                # one request still waiting
    c = sched.pop_ready(0.0)
    sched.bind(c, 0, 2.0)
    sched.complete(c, 3.0)
    assert sched.done()
    assert {r.rid for r in sched.finished} == {0, 1, 2}


def test_scheduler_preempt_requeue_ordering_and_waits():
    """Preempted requests re-enter ahead of fresh arrivals (no-starvation
    ordering), FIFO among themselves, accumulating their requeue wait;
    done() accounts for them."""
    sched = Scheduler()
    for i in range(4):
        sched.submit(_req(i))
    a = sched.pop_ready(0.0)
    b = sched.pop_ready(0.0)
    sched.bind(a, 0, 0.0)
    sched.bind(b, 1, 0.1)
    sched.preempt(b, 1.0)
    assert b.state is RequestState.PREEMPTED
    assert b.slot is None and b.n_preempts == 1
    sched.preempt(a, 2.0)
    with pytest.raises(ValueError):       # not active any more
        sched.preempt(a, 2.0)
    assert not sched.done()               # preempted requests still pending
    # b (preempted first) re-enters first, before the waiting queue
    assert sched.peek_ready(10.0) is b
    r = sched.pop_ready(3.0)
    assert r is b and b.state is RequestState.PREFILL
    assert b.requeue_wait_s == pytest.approx(2.0)
    r = sched.pop_ready(5.0)
    assert r is a and a.requeue_wait_s == pytest.approx(3.0)
    # only now does the fresh queue drain
    assert sched.pop_ready(10.0).rid == 2
    # a twice-preempted request accumulates waits and counts
    sched.bind(b, 1, 5.0)
    sched.preempt(b, 6.0)
    assert sched.next_arrival() == 0.0    # admissible immediately
    sched.pop_ready(6.5)
    assert b.n_preempts == 2
    assert b.requeue_wait_s == pytest.approx(2.5)
    sched.bind(b, 1, 6.5)
    sched.complete(b, 7.0)
    s = summarize([b])
    assert s["preempts"] == 2 and s["preempted_requests"] == 1
    assert s["preempts_by_rid"] == {b.rid: 2}
    assert s["requeue_wait_p50_s"] == pytest.approx(2.5)
    assert s["requeue_wait_max_s"] == pytest.approx(2.5)


def test_slot_pool_alloc_free_write():
    avals = {"k": jax.ShapeDtypeStruct((1, 4, 2), jnp.float32),
             "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    pool = SlotKVPool(avals, n_slots=2)
    s0, s1 = pool.alloc(), pool.alloc()
    assert {s0, s1} == {0, 1}
    assert pool.alloc() is None            # exhausted
    cache = {"k": jnp.full((1, 4, 2), 7.0), "pos": jnp.asarray(5, jnp.int32)}
    pool.write(s1, cache)
    assert int(pool.pool["pos"][s1]) == 5
    assert float(pool.pool["k"][s1].sum()) == 7.0 * 8
    assert int(pool.pool["pos"][s0]) == 0  # neighbour slot untouched
    pool.free(s0)
    with pytest.raises(ValueError):        # double free
        pool.free(s0)
    with pytest.raises(ValueError):        # write to unallocated slot
        pool.write(s0, cache)
    assert pool.alloc() == s0              # freed slot is reusable
    assert pool.n_free == 0 and pool.n_active == 2


# ---------------------------------------------------------------------------
# Continuous batching vs. the static lockstep path
# ---------------------------------------------------------------------------


def test_continuous_matches_static_burst(engine):
    """Greedy tokens per request identical to lockstep generate (f32)."""
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(4), (3, 12), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    static = np.asarray(eng.generate(prompts, 6)["tokens"])
    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]), max_new_tokens=6)
            for i in range(3)]
    res = eng.serve(reqs)
    for i, r in enumerate(reqs):
        assert r.out_tokens == static[i].tolist()
        assert r.state is RequestState.DONE
    assert res["stats"]["tokens"] == 18
    assert eng._pool.n_free == eng.cfg.max_slots   # no slot leaks


def test_continuous_matches_static_staggered(engine):
    """More requests than slots, mixed budgets, staggered arrivals: requests
    join the decode batch mid-flight and still reproduce lockstep tokens."""
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(5), (5, 10), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    gens = [7, 3, 5, 2, 6]
    static = np.asarray(eng.generate(prompts, max(gens))["tokens"])
    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]), max_new_tokens=g,
                    arrival_s=0.005 * i)
            for i, g in enumerate(gens)]
    res = eng.serve(reqs)
    for i, r in enumerate(reqs):
        assert r.out_tokens == static[i][:gens[i]].tolist(), f"req {i}"
    # in-flight batching never takes more pool steps than serial decode
    # would (equality only if requests never overlapped on a fast machine)
    assert res["steps"] <= sum(gens)
    assert eng._pool.n_free == eng.cfg.max_slots


def test_continuous_bucketed_prefill_matches_exact(engine):
    """Slot path: pad-to-bucket prefill (warm jit across prompt lengths) is
    lossless for full-KV caches: pad K/V entries are masked then
    overwritten."""
    cfg, eng = engine
    eng_b = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=2, prefill_bucket=8, paged="off"))
    prompts = jax.random.randint(jax.random.PRNGKey(6), (3, 13), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    static = np.asarray(eng.generate(prompts, 5)["tokens"])
    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]), max_new_tokens=5)
            for i in range(3)]
    eng_b.serve(reqs)
    for i, r in enumerate(reqs):
        assert r.out_tokens == static[i].tolist()
    # 13-token prompts feed 12 tokens -> one 16-wide bucket, one jit entry
    # (keyed on (feed_len, resolved scan mode); attention families have
    # no scan-mode choice, so the mode half is empty)
    assert list(eng_b._slot_prefills) == [(16, "")]


def test_paged_is_default_for_full_kv(engine):
    """Dense families serve off the paged pool by default; the slot pool
    remains selectable and produces identical greedy tokens."""
    from repro.serve.cache import PagedKVPool
    cfg, eng = engine
    eng._ensure_pool()
    assert isinstance(eng._pool, PagedKVPool)
    eng_s = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=3, paged="off"))
    prompts = jax.random.randint(jax.random.PRNGKey(11), (2, 11), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    reqs_p = [Request(rid=i, prompt=np.asarray(prompts[i]), max_new_tokens=5)
              for i in range(2)]
    reqs_s = [Request(rid=i, prompt=np.asarray(prompts[i]), max_new_tokens=5)
              for i in range(2)]
    eng.serve(reqs_p)
    eng_s.serve(reqs_s)
    for rp, rs in zip(reqs_p, reqs_s):
        assert rp.out_tokens == rs.out_tokens


def test_chunked_prefill_matches_static(engine):
    """Chunked prefill (prompt split into fixed pieces interleaved with
    decode steps) reproduces the static path's greedy tokens, across chunk
    sizes that do and don't divide the prompt or page size."""
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(12), (3, 13), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    static = np.asarray(eng.generate(prompts, 5)["tokens"])
    for chunk in (3, 4, 8):
        eng_c = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
            max_len=64, max_slots=2, page_size=8, prefill_chunk=chunk))
        reqs = [Request(rid=i, prompt=np.asarray(prompts[i]),
                        max_new_tokens=5, arrival_s=0.004 * i)
                for i in range(3)]
        eng_c.serve(reqs)
        for i, r in enumerate(reqs):
            assert r.out_tokens == static[i].tolist(), f"chunk={chunk} req {i}"
        assert eng_c._pool.n_free == 2
        eng_c._pool.allocator.check_invariants()


def test_chunked_prefill_pad_overhang_at_max_len(engine):
    """A padded final chunk whose pad positions overhang the block table's
    reach (prompt near max_len, chunk width not dividing the feed) must
    route the overhanging writes to the null page, not clamp into the
    request's own last page — greedy tokens stay equal to the static path."""
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(15), (1, 15), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    static = np.asarray(eng.generate(prompts, 2)["tokens"])
    eng_c = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=16, max_slots=1, page_size=8, prefill_chunk=12))
    req = Request(rid=0, prompt=np.asarray(prompts[0]), max_new_tokens=2)
    eng_c.serve([req])                    # pads cover positions 14..23 > 16
    assert req.out_tokens == static[0].tolist()
    eng_c._pool.allocator.check_invariants()


def test_paged_rejects_unsatisfiable_request(engine):
    """A demand no admission could ever satisfy (more pages than the pool
    holds) is rejected up front instead of spinning the serve loop."""
    cfg, eng = engine
    eng_t = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=2, page_size=16, kv_pages=3))
    prompts = jax.random.randint(jax.random.PRNGKey(16), (1, 8), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    ok = Request(rid=0, prompt=np.asarray(prompts[0]), max_new_tokens=20)
    eng_t.serve([ok])                     # 27 tokens -> 2 pages: fits
    assert len(ok.out_tokens) == 20
    bad = Request(rid=1, prompt=np.asarray(prompts[0]), max_new_tokens=40)
    with pytest.raises(ValueError, match="KV pages"):
        eng_t.serve([bad])                # 47 tokens -> 3 pages > 2 usable


def test_paged_pool_memory_freed_on_completion(engine):
    """Pages go back to the allocator as requests complete; the high-water
    mark records the trace's real working set."""
    cfg, eng = engine
    eng_p = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=2, page_size=8))
    prompts = jax.random.randint(jax.random.PRNGKey(13), (4, 9), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]), max_new_tokens=4)
            for i in range(4)]
    eng_p.serve(reqs)
    alloc = eng_p._pool.allocator
    alloc.check_invariants()
    assert alloc.n_live == 0                      # everything released
    assert alloc.high_water >= 2                  # something was resident
    assert eng_p._pool.high_water_bytes() <= eng_p._pool.hbm_bytes()


def test_paged_sampling_masks_inactive_slots(engine):
    """Temperature sampling over a paged pool with empty slots completes
    and never emits tokens from garbage logits (inactive slots decode the
    null page; their samples are pinned to 0 and discarded)."""
    cfg, eng = engine
    eng_t = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=4, page_size=8, temperature=0.7, seed=3))
    prompts = jax.random.randint(jax.random.PRNGKey(14), (2, 7), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]),
                    max_new_tokens=3 + 2 * i) for i in range(2)]
    eng_t.serve(reqs)
    for r in reqs:
        assert r.state is RequestState.DONE
        assert len(r.out_tokens) == r.max_new_tokens
        assert all(0 <= t < cfg.vocab_size for t in r.out_tokens)


def test_continuous_eos_stops_early(engine):
    """A request whose eos_id matches a generated token stops at it."""
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(7), (1, 8), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    static = np.asarray(eng.generate(prompts, 6)["tokens"])[0]
    eos = int(static[2])
    req = Request(rid=0, prompt=np.asarray(prompts[0]), max_new_tokens=6,
                  eos_id=eos)
    eng.serve([req])
    stop = static.tolist().index(eos)
    assert req.out_tokens == static[: stop + 1].tolist()
    assert req.out_tokens[-1] == eos


def test_serve_summary_stats(engine):
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(8), (2, 6), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]), max_new_tokens=3)
            for i in range(2)]
    eng.serve(reqs)
    s = summarize(reqs)
    assert s["n_done"] == 2 and s["tokens"] == 6
    assert s["tok_per_s"] > 0
    assert s["latency_p99_s"] >= s["latency_p50_s"] >= 0


# ---------------------------------------------------------------------------
# Speculative multi-token decode (draft -> verify -> commit/rollback)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_speculative_matches_nonspeculative(engine, depth):
    """Greedy tokens with spec_depth > 0 are bit-identical per request to
    the non-speculative paged path (f32): acceptance only reorders work,
    never changes tokens — even when every draft is rejected."""
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(20), (3, 11), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    gens = [9, 4, 6]

    def mk():
        return [Request(rid=i, prompt=np.asarray(prompts[i]),
                        max_new_tokens=g, arrival_s=0.003 * i)
                for i, g in enumerate(gens)]

    eng_p = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=2, page_size=8, spec_depth=0))
    base_reqs = mk()
    eng_p.serve(base_reqs)

    eng_s = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=2, page_size=8, spec_depth=depth))
    reqs = mk()
    res = eng_s.serve(reqs)
    for r, b in zip(reqs, base_reqs):
        assert r.out_tokens == b.out_tokens, f"depth={depth} req {r.rid}"
        assert r.state is RequestState.DONE
    assert res["spec"]["committed_tokens"] == sum(gens)
    # never fewer committed tokens per step than the plain path's one
    assert res["spec"]["tokens_per_step"] >= 1.0
    assert eng_s._pool.n_free == 2
    eng_s._pool.allocator.check_invariants()


def test_speculative_near_budget_and_block_table_edge(engine):
    """Speculation overshooting a request's budget (and its block table's
    reach, near max_len) commits only up to the budget and rolls the rest
    back — token-identical to plain decode, no allocator damage."""
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(21), (1, 12), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    eng_p = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=16, max_slots=1, page_size=8, spec_depth=0))
    base = Request(rid=0, prompt=np.asarray(prompts[0]), max_new_tokens=5)
    eng_p.serve([base])
    eng_s = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=16, max_slots=1, page_size=8, spec_depth=4))
    req = Request(rid=0, prompt=np.asarray(prompts[0]), max_new_tokens=5)
    eng_s.serve([req])                    # 11 + 5 = 16 tokens = max_len
    assert req.out_tokens == base.out_tokens
    assert len(req.out_tokens) == 5
    eng_s._pool.allocator.check_invariants()
    assert eng_s._pool.allocator.n_live == 0


def test_speculative_eos_stops_inside_accepted_block(engine):
    """An EOS produced mid-way through an accepted speculative block stops
    the request at the EOS, exactly like sequential decode."""
    cfg, eng = engine
    prompts = jax.random.randint(jax.random.PRNGKey(22), (1, 8), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    static = np.asarray(eng.generate(prompts, 6)["tokens"])[0]
    eos = int(static[2])
    eng_s = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=1, page_size=8, spec_depth=3))
    req = Request(rid=0, prompt=np.asarray(prompts[0]), max_new_tokens=6,
                  eos_id=eos)
    eng_s.serve([req])
    stop = static.tolist().index(eos)
    assert req.out_tokens == static[: stop + 1].tolist()
    assert req.out_tokens[-1] == eos


def test_speculative_with_chunked_prefill_and_echo_params(engine):
    """High-acceptance regime (echo params: scaled-down init repeats
    itself) with chunked prefill: speculative decode commits multiple
    tokens per step and still reproduces the plain path bit for bit."""
    cfg, eng = engine
    params = jax.tree.map(lambda a: a * 0.3, eng.params)
    prompts = jax.random.randint(jax.random.PRNGKey(23), (3, 13), 0,
                                 cfg.vocab_size, dtype=jnp.int32)

    def mk():
        return [Request(rid=i, prompt=np.asarray(prompts[i]),
                        max_new_tokens=12, arrival_s=0.002 * i)
                for i in range(3)]

    eng_p = Engine(eng.model, params, serve_cfg=ServeConfig(
        max_len=64, max_slots=2, page_size=8, prefill_chunk=5, spec_depth=0))
    base_reqs = mk()
    res_p = eng_p.serve(base_reqs)
    eng_s = Engine(eng.model, params, serve_cfg=ServeConfig(
        max_len=64, max_slots=2, page_size=8, prefill_chunk=5, spec_depth=3))
    reqs = mk()
    res_s = eng_s.serve(reqs)
    for r, b in zip(reqs, base_reqs):
        assert r.out_tokens == b.out_tokens
    # echo outputs are draftable: the verify step must actually accept
    assert res_s["steps"] < res_p["steps"]
    assert res_s["spec"]["tokens_per_step"] > 1.5


def test_draft_ngram_lookup_and_fallback():
    from repro.serve.engine import draft_ngram
    # n-gram hit: ...5 6 7 ... 5 6 -> proposes 7 then the continuation
    h = np.array([1, 5, 6, 7, 8, 9, 2, 5, 6], np.int32)
    np.testing.assert_array_equal(draft_ngram(h, 3), [7, 8, 9])
    # short continuation pads by repeating its last token
    h2 = np.array([4, 4, 9, 3, 4, 4], np.int32)
    d2 = draft_ngram(h2, 4)
    assert d2[0] == 9 and d2.shape == (4,)
    # no match anywhere: repeat the last token (degenerate-loop regime)
    h3 = np.array([1, 2, 3], np.int32)
    np.testing.assert_array_equal(draft_ngram(h3, 2), [3, 3])


# ---------------------------------------------------------------------------
# Counter-driven plan selection (the paper loop at serve time)
# ---------------------------------------------------------------------------


class _RC:
    """RegionCounters stand-in."""
    def __init__(self, regions):
        self.regions = regions

    def top_regions(self, key, n):
        items = [(r, getattr(c, key)) for r, c in self.regions.items()]
        return sorted(items, key=lambda kv: -kv[1])[:n]


def _tree(rule):
    """Train a real DecisionTree on a separable synthetic corpus."""
    from repro.core.dtree import DecisionTree, features
    rng = np.random.default_rng(0)
    X, y = [], []
    for _ in range(40):
        ai = rng.uniform(0.5, 200)
        c = Counters(flops=ai * 1e9, bytes=1e9)
        X.append(features(c))
        y.append(rule(ai))
    return DecisionTree(max_depth=3).fit(np.stack(X), y)


def test_plan_decider_applies_predicted_candidate():
    # low arithmetic intensity -> chunk the q blocks; high -> keep default
    tree = _tree(lambda ai: "attn_blockq_1k" if ai < 20 else "keep_default")
    rc = _RC({
        "layer0/attn": Counters(flops=5e9, bytes=1e9),    # AI 5: wants 1k
        "layer0/mlp": Counters(flops=4e9, bytes=1e7),
    })
    from repro.core.policy import null_plan
    plan, decisions = PlanDecider(tree).decide(rc, null_plan(), top_n=2)
    assert plan.config_for("layer3/attn").block_q == 1024
    assert dict(decisions)["layer/attn"] == "attn_blockq_1k"
    # prediction for mlp exists but no mlp-applicable candidate matched
    assert plan.config_for("layer3/mlp").block_q == 0


def test_plan_decider_load_scaling_changes_decision():
    """Occupancy scaling moves the feature past the tree's split."""
    tree = _tree(lambda ai: "keep_default" if ai < 20 else "attn_blockq_1k")
    # tree splits on a log-flops-ish boundary: scale flops via load_frac
    rc = _RC({"layer0/attn": Counters(flops=40e9, bytes=1e9)})   # AI 40
    from repro.core.policy import null_plan
    full, _ = PlanDecider(tree).decide(rc, null_plan(), load_frac=1.0)
    assert full.config_for("layer0/attn").block_q == 1024
    # at 1/8 occupancy the scaled counters look memory-ish -> keep default
    low, _ = PlanDecider(tree).decide(rc, null_plan(), load_frac=0.125)
    assert low.config_for("layer0/attn").block_q == 0


def test_serve_with_dtree_selects_and_stays_correct(engine):
    """End to end: a tree that always votes attn_blockq_1k changes the plan
    for the decode step, and greedy outputs still match the static path."""
    cfg, eng = engine
    from repro.core.dtree import DecisionTree, features
    X = np.stack([features(Counters(flops=1e9, bytes=1e9)),
                  features(Counters(flops=1e12, bytes=1e10))])
    tree = DecisionTree().fit(X, ["attn_blockq_1k", "attn_blockq_1k"])
    eng_d = Engine(eng.model, eng.params, dtree=tree,
                   serve_cfg=ServeConfig(max_len=64, max_slots=2))
    prompts = jax.random.randint(jax.random.PRNGKey(9), (2, 9), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    static = np.asarray(eng.generate(prompts, 4)["tokens"])
    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]), max_new_tokens=4)
            for i in range(2)]
    res = eng_d.serve(reqs)
    assert res["decisions"], "dtree was never consulted"
    picked = dict(res["decisions"][0][1])
    assert picked.get("layer/attn") == "attn_blockq_1k"
    for i, r in enumerate(reqs):
        assert r.out_tokens == static[i].tolist()


# ---------------------------------------------------------------------------
# One decode step in flight (spec depth 0): step k+1 is dispatched before
# the host reads step k's tokens
# ---------------------------------------------------------------------------


def _in_flight_case(event):
    """(ServeConfig kwargs, prompt rows, prompt length, budgets, whether
    request 0 stops at an EOS) for one event landing while a step is in
    flight."""
    base = dict(max_len=64, max_slots=3, page_size=8, prefill_chunk=4,
                spec_depth=0)
    gens = [8, 6, 5]
    if event == "eos":                  # request 0 stops at an EOS
        return base, [0, 1, 2], 12, gens, True
    if event == "budget":               # budgets end at different steps
        return base, [0, 1, 2], 12, [7, 3, 5], False
    if event == "prefill_mid_batch":    # prompts join a running batch
        return base, [0, 1, 2], 12, [9, 6, 5], False
    if event == "prefix_hit_running":   # a running prompt, served again
        return (dict(base, page_size=4, prefix_cache="on"), [0, 0, 1], 12,
                gens, False)
    if event == "lazy_preempt":         # growth runs dry, a victim goes
        return (dict(base, max_len=21, prefill_chunk=8, kv_pages=7,
                     reservation="lazy", mem_watermark=0.0),
                [0, 1, 2], 4, [16, 16, 16], False)
    assert event == "logits_nan"        # non-finite rows retry
    return (dict(base, chaos_rate=0.25, chaos_seed=3,
                 chaos_sites=("logits.nan",), max_retries=50),
            [0, 1, 2], 12, gens, False)


@pytest.mark.parametrize("event", ["eos", "budget", "prefill_mid_batch",
                                   "prefix_hit_running", "lazy_preempt",
                                   "logits_nan"])
def test_step_in_flight_keeps_greedy_tokens(engine, event):
    """Each event lands while a step is in flight; every request still
    commits exactly the static path's greedy tokens, and the pool is
    whole afterwards."""
    cfg, eng = engine
    scfg, rows, plen, gens, eos = _in_flight_case(event)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(30), (3, plen), 0, cfg.vocab_size,
        dtype=jnp.int32))[rows]
    static = np.asarray(eng.generate(jnp.asarray(prompts), max(gens))
                        ["tokens"])
    eos_id = int(static[0][2]) if eos else None
    reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=g,
                    eos_id=eos_id if i == 0 else None)
            for i, g in enumerate(gens)]
    eng_f = Engine(eng.model, eng.params, serve_cfg=ServeConfig(**scfg))
    res = eng_f.serve(reqs)
    for i, r in enumerate(reqs):
        want = static[i][:gens[i]].tolist()
        if i == 0 and eos_id is not None:
            want = want[:want.index(eos_id) + 1]
        assert r.state is RequestState.DONE, (event, r.rid, r.error)
        assert r.out_tokens == want, (event, r.rid)
    pool = eng_f._pool
    pool.allocator.check_invariants()
    assert pool.leaked_pages() == 0
    ov = res["overlap"]
    assert ov["steps_ahead"] >= 1
    if event == "budget":
        assert ov["dropped_rows"] == 0      # budget ends are known ahead
    if event in ("eos", "lazy_preempt", "logits_nan"):
        assert ov["dropped_rows"] >= 1      # the ended row was in flight
    if event == "prefix_hit_running":
        assert pool.prefix_hit_requests >= 1
    if event == "lazy_preempt":
        assert pool.n_preempts >= 1
    if event == "logits_nan":
        assert res["faults"]["injected_total"] >= 1


class _WindowShut(Exception):
    pass


class _ShutAfter(list):
    """A request's token list that raises on the ``n``-th append over all
    requests sharing ``left``, as the benchmark's window does at its end."""

    def __init__(self, left: list):
        super().__init__()
        self.left = left

    def append(self, tok):
        self.left[0] -= 1
        if self.left[0] < 0:
            raise _WindowShut()
        super().append(tok)


@pytest.mark.parametrize("n_tokens", [1, 7, 16])
def test_step_count_survives_a_raise_mid_commit(engine, n_tokens):
    """A token list that raises mid-commit leaves the pool step's call
    count at most one ahead of the health ladder's step count (the step
    in flight), and the pool consistent."""
    cfg, eng = engine
    eng_c = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=3, page_size=8, prefill_chunk=4,
        spec_depth=0))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(31), (3, 10), 0, cfg.vocab_size, dtype=jnp.int32))
    eng_c.serve([Request(rid=9, prompt=prompts[0], max_new_tokens=3)])
    calls = []
    step = eng_c._pool_step

    def counted(*args):
        calls.append(1)
        return step(*args)

    eng_c._pool_step = counted
    left = [n_tokens]
    reqs = []
    for i in range(3):
        r = Request(rid=i, prompt=prompts[i], max_new_tokens=8)
        r.out_tokens = _ShutAfter(left)
        reqs.append(r)
    with pytest.raises(_WindowShut):
        eng_c.serve(reqs)
    assert len(calls) - eng_c.health.taps["steps"] in (0, 1)
    eng_c._pool.allocator.check_invariants()
    assert eng_c._pool.leaked_pages() == 0


@pytest.mark.parametrize("depth", [0, 2])
def test_overlap_counts_steps_ahead(engine, depth):
    """At depth 0 every step but a few after drains goes ahead; with
    speculation the acceptance walk precedes the next draft, so none
    does."""
    cfg, eng = engine
    eng_o = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=3, page_size=8, prefill_chunk=4,
        spec_depth=depth))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(32), (4, 10), 0, cfg.vocab_size, dtype=jnp.int32))
    reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=g)
            for i, g in enumerate([9, 4, 6, 7])]
    res = eng_o.serve(reqs)
    ov, steps = res["overlap"], res["steps"]
    if depth == 0:
        assert ov["steps_ahead"] >= steps - ov["drains"]
        assert 1 <= ov["drains"] <= 3 and steps - ov["steps_ahead"] <= 3
    else:
        assert ov["steps_ahead"] == 0
    assert res["spec"]["committed_tokens"] == sum(len(r.out_tokens)
                                                  for r in reqs)


class _Spans:
    """A stand-in for ``telemetry.span`` that keeps each span's name,
    counters and place in the order, and the open spans' stack."""

    def __init__(self):
        self.stack: list = []
        self.opened: list = []

    def __call__(self, name):
        spans = self

        class _S:
            meta: dict

            def __enter__(self):
                self.name, self.meta = name, {}
                spans.stack.append(name)
                spans.opened.append(self)
                return self

            def __exit__(self, *exc):
                spans.stack.pop()

            def set_metadata(self, **kw):
                self.meta.update(kw)
        return _S()


class _NumpyReads:
    """The engine module's ``np``, logging each ``asarray``/``array`` of a
    device array (on the CPU backend those read through the buffer
    protocol, not ``jax.Array._value``)."""

    def __init__(self, log):
        self._log = log

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self._log()
        return np.asarray(a, *args, **kw)

    def array(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self._log()
        return np.array(a, *args, **kw)


def test_no_read_back_between_a_dispatch_and_the_wait(engine, monkeypatch):
    """Inside a pass, the only device-to-host reads are the wait on the
    step in flight; a pass marked ``ahead`` dispatches its step before
    that wait.  (The CPU backend ignores ``transfer_guard``, so every
    read of a device array is logged with the spans open at the time.)"""
    from jax._src import array as jarray
    from repro.serve import engine as engine_mod
    cfg, eng = engine
    eng_t = Engine(eng.model, eng.params, serve_cfg=ServeConfig(
        max_len=64, max_slots=3, page_size=8, prefill_chunk=4,
        spec_depth=0))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(33), (3, 10), 0, cfg.vocab_size, dtype=jnp.int32))

    def mk():
        return [Request(rid=i, prompt=prompts[i], max_new_tokens=g)
                for i, g in enumerate([7, 4, 5])]
    eng_t.serve(mk())                               # compile outside
    spans = _Spans()
    reads = []
    value = jarray.ArrayImpl._value

    def log():
        reads.append(tuple(spans.stack))

    def logged(arr):
        log()
        return value.fget(arr)
    monkeypatch.setattr(engine_mod, "span", spans)
    monkeypatch.setattr(engine_mod, "np", _NumpyReads(log))
    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(logged))
    res = eng_t.serve(mk())
    monkeypatch.undo()

    in_pass = [r for r in reads if "serve.pass" in r]
    assert in_pass and all(r[-1] == "serve.step_wait" for r in in_pass)
    names = [s.name for s in spans.opened]
    passes = [i for i, n in enumerate(names) if n == "serve.pass"]
    ahead = 0
    for a, b in zip(passes, passes[1:] + [len(names)]):
        sp = spans.opened[a]
        if sp.meta.get("ahead"):
            ahead += 1
            inner = names[a + 1:b]
            assert inner.index("serve.step_dispatch") < inner.index(
                "serve.step_wait")
    assert ahead == res["overlap"]["steps_ahead"] >= 1
