"""Fault injection and failure domains: injector determinism, corpus
quarantine + atomic save, the health state machine, scheduler terminal
transitions, and the chaos properties the serving engine must hold —
fault sequences conserve allocator pages, every request reaches exactly
one terminal state, and surviving greedy output is bit-identical to a
fault-free run."""
import glob
import math
import os

import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import given, settings

import jax
import jax.numpy as jnp

from repro.autotune.corpus import Corpus
from repro.configs.registry import get_config
from repro.models.model import build
from repro.serve.cache import PagedKVPool
from repro.serve.engine import Engine, ServeConfig
from repro.serve.faults import FAULT_SITES, FaultInjector
from repro.serve.health import HealthMonitor, HealthPolicy, HealthState
from repro.serve.scheduler import (TERMINAL_STATES, Request, RequestState,
                                   Scheduler, summarize)


# ---------------------------------------------------------------------------
# FaultInjector (pure host logic)
# ---------------------------------------------------------------------------


def test_injector_deterministic_and_site_isolated():
    """The n-th draw at a site is a pure function of (seed, site, n):
    replaying the same seed reproduces the fire sequence exactly, and
    interleaving draws at OTHER sites never perturbs it."""
    a = FaultInjector(seed=3, rate=0.4)
    b = FaultInjector(seed=3, rate=0.4)
    seq_a = [a.fire("logits.nan") for _ in range(64)]
    seq_b = []
    for _ in range(64):
        b.fire("alloc.exhaust")         # foreign-site draws interleaved
        seq_b.append(b.fire("logits.nan"))
        b.fire("mem.grow")
    assert seq_a == seq_b
    assert any(seq_a) and not all(seq_a)
    c = FaultInjector(seed=4, rate=0.4)
    assert [c.fire("logits.nan") for _ in range(64)] != seq_a


def test_injector_disabled_paths():
    off = FaultInjector(seed=0, rate=0.0)
    assert not off.enabled
    assert not any(off.fire("logits.nan") for _ in range(32))
    assert off.injected_total == 0
    only = FaultInjector(seed=0, rate=1.0, sites=("mem.grow",))
    assert not only.fire("logits.nan")  # excluded site never fires
    assert only.fire("mem.grow")
    with pytest.raises(ValueError):
        only.fire("no.such.site")
    with pytest.raises(ValueError):
        FaultInjector(sites=("bogus",))
    with pytest.raises(ValueError):
        FaultInjector(rate=1.5)


def test_injector_summary_counts():
    inj = FaultInjector(seed=1, rate=0.5)
    for _ in range(40):
        inj.fire("alloc.exhaust")
        inj.fire("step.latency")
    s = inj.summary()
    assert s["enabled"] and s["draws"] == 80
    assert s["injected_total"] == sum(s["injected"].values())
    assert set(s["injected"]) <= set(FAULT_SITES)


# ---------------------------------------------------------------------------
# Corpus: quarantine on load, atomicity on save
# ---------------------------------------------------------------------------


def _toy_corpus(n: int = 20) -> Corpus:
    c = Corpus()
    for i in range(n):
        c.append(f"r{i}", [float(i), 0.5], f"cls{i % 3}", reward=float(i))
    return c


def test_corpus_quarantines_corrupt_lines(tmp_path):
    path = str(tmp_path / "corpus.jsonl")
    c = _toy_corpus()
    inj = FaultInjector(seed=5, rate=0.5, sites=("corpus.corrupt",))
    c.save_jsonl(path, faults=inj)
    n_corrupt = inj.fired["corpus.corrupt"]
    assert n_corrupt >= 1, "pick a seed that actually corrupts something"
    loaded = Corpus.load_jsonl(path)
    assert loaded.quarantined == n_corrupt
    assert len(loaded) == len(c) - n_corrupt
    for e in loaded.entries():          # survivors parsed intact
        assert e.region.startswith("r") and not math.isnan(e.reward)


def test_corpus_corrupt_line_modes_all_quarantine(tmp_path):
    """Every corruption mode must actually defeat the parser."""
    import json
    inj = FaultInjector(seed=0, rate=1.0, sites=("corpus.corrupt",))
    good = json.dumps(_toy_corpus(1).entries()[0].to_json())
    path = str(tmp_path / "one.jsonl")
    for _ in range(6):                  # cycles through all three modes
        with open(path, "w") as f:
            f.write(inj.corrupt_line(good) + "\n")
        assert len(Corpus.load_jsonl(path)) == 0
        assert Corpus.load_jsonl(path).quarantined == 1


def test_corpus_save_is_atomic(tmp_path):
    """A crash mid-save must leave the previous corpus intact and no
    temp litter behind."""
    path = str(tmp_path / "corpus.jsonl")
    _toy_corpus(5).save_jsonl(path)
    before = open(path).read()

    class Boom:
        def fire(self, site):
            raise RuntimeError("disk died mid-save")

    with pytest.raises(RuntimeError):
        _toy_corpus(20).save_jsonl(path, faults=Boom())
    assert open(path).read() == before
    assert glob.glob(str(tmp_path / ".corpus-*")) == []


# ---------------------------------------------------------------------------
# Health state machine
# ---------------------------------------------------------------------------


def test_health_ladder_up_and_down():
    p = HealthPolicy(window=8, degrade_after=2, shed_after=4,
                     recover_after=3)
    m = HealthMonitor(p)
    m.note_step(0.0, n_slot_faults=1)
    assert m.state is HealthState.HEALTHY
    m.note_step(0.0, n_slot_faults=2)   # 2 faulted steps in window
    assert m.state is HealthState.DEGRADED and m.degraded
    for _ in range(2):
        m.note_step(0.0, n_slot_faults=1)
    assert m.state is HealthState.SHEDDING and m.shedding
    for _ in range(3):                  # recover_after clean -> one rung
        m.note_step(0.0)
    assert m.state is HealthState.DEGRADED
    for _ in range(3):
        m.note_step(0.0)
    assert m.state is HealthState.HEALTHY and not m.degraded
    s = m.summary()
    assert s["degraded_entries"] == 1 and s["shed_entries"] == 1
    assert s["recoveries"] == 1


def test_health_watchdog_counts_latency():
    m = HealthMonitor(HealthPolicy(watchdog_s=0.01, degrade_after=2))
    m.note_step(0.5)                    # overruns the per-step budget
    m.note_step(0.5)
    assert m.taps["latency_faults"] == 2
    assert m.state is HealthState.DEGRADED


def test_backoff_is_capped_exponential():
    p = HealthPolicy(backoff_base=1, backoff_cap=8)
    assert [p.backoff(k) for k in range(1, 7)] == [1, 2, 4, 8, 8, 8]


def test_health_reset_clears_everything():
    m = HealthMonitor(HealthPolicy(degrade_after=1))
    m.note_step(0.0, n_slot_faults=1)
    assert m.degraded and m.fault_rate() > 0
    m.reset()
    assert m.state is HealthState.HEALTHY
    assert m.fault_rate() == 0.0
    assert all(v == 0 for v in m.taps.values())


# ---------------------------------------------------------------------------
# Scheduler terminal transitions
# ---------------------------------------------------------------------------


def _req(rid, arrival=0.0, gen=4, plen=4, deadline=0.0):
    return Request(rid=rid, prompt=np.arange(1, plen + 1, dtype=np.int32),
                   max_new_tokens=gen, arrival_s=arrival,
                   deadline_s=deadline)


def test_scheduler_fail_moves_resident_to_terminal():
    sched = Scheduler()
    r0, r1 = _req(0), _req(1)
    sched.submit(r0)
    sched.submit(r1)
    a = sched.pop_ready(0.0)
    sched.bind(a, slot=0, now_s=0.0)
    b = sched.pop_ready(0.0)
    sched.bind_prefill(b, slot=1, now_s=0.0)
    sched.fail(a, now_s=1.0, reason="nan logits")
    sched.fail(b, now_s=1.0, reason="prefill fault")
    assert a.state is RequestState.FAILED and a.error == "nan logits"
    assert a.t_done == 1.0 and a.slot is None
    assert not sched.active and not sched.prefilling
    assert sched.done()
    with pytest.raises(ValueError):     # not resident anymore
        sched.fail(a, now_s=2.0)
    s = summarize([r0, r1])
    assert s["failed"] == 2 and s["n_done"] == 0


def test_scheduler_shed_deadline_and_queue_bound():
    sched = Scheduler()
    reqs = [_req(0, deadline=0.5),      # expires: still waiting at t=1
            _req(1),                    # kept (arrived, inside the bound)
            _req(2),                    # kept
            _req(3),                    # rejected: bound is 2
            _req(4, arrival=99.0)]      # future arrival: exempt from bound
    for r in reqs:
        sched.submit(r)
    expired, rejected = sched.shed_waiting(1.0, max_queue=2)
    assert [r.rid for r in expired] == [0]
    assert [r.rid for r in rejected] == [3]
    assert reqs[0].state is RequestState.EXPIRED and reqs[0].error
    assert reqs[3].state is RequestState.REJECTED
    assert reqs[4].state is RequestState.WAITING
    assert {r.rid for r in sched.shed} == {0, 3}
    # default deadline applies where the request carries none
    expired, _ = sched.shed_waiting(200.0, default_deadline_s=50.0)
    assert {r.rid for r in expired} == {1, 2, 4}
    assert sched.done()
    s = summarize(reqs)
    assert s["expired"] == 4 and s["rejected"] == 1


def test_terminal_states_registry():
    assert RequestState.DONE in TERMINAL_STATES
    assert RequestState.FAILED in TERMINAL_STATES
    assert RequestState.EXPIRED in TERMINAL_STATES
    assert RequestState.REJECTED in TERMINAL_STATES
    assert RequestState.DECODE not in TERMINAL_STATES


# ---------------------------------------------------------------------------
# Property: fault sequences conserve allocator pages (pool level)
# ---------------------------------------------------------------------------


@settings(max_examples=25)
@given(ops=st.lists(st.integers(min_value=0, max_value=9),
                    min_size=1, max_size=40),
       seed=st.integers(min_value=0, max_value=999))
def test_pool_fault_sequences_conserve_pages(ops, seed):
    """Random admit/grow/release interleavings with ``alloc.exhaust``
    injected at 50%: whatever the injector denies, page conservation
    holds at every step (refcounts match owners, nothing is reachable
    from neither a slot nor the index) and a full drain returns the
    pool to empty."""
    ps, n_slots, n_pages = 4, 3, 13
    avals = {"k": jax.ShapeDtypeStruct((n_pages, ps, 1, 2), jnp.float32)}
    pool = PagedKVPool(avals, n_slots, ps, n_pages, max_pages_per_slot=4)
    pool.faults = FaultInjector(seed=seed, rate=0.5,
                                sites=("alloc.exhaust",))
    held: list[int] = []
    for op in ops:
        if op <= 4:                     # admit 1..3 pages (may be denied)
            slot = pool.admit_pages(1 + op % 3)
            if slot is not None:
                held.append(slot)
        elif op <= 7 and held:          # grow (may be denied)
            pool.grow(held[op % len(held)])
        elif held:                      # release
            pool.release(held.pop(op % len(held)))
        pool.allocator.check_invariants()
        assert pool.leaked_pages() == 0
    for slot in held:
        pool.release(slot)
    assert pool.allocator.n_live == 0
    assert pool.leaked_pages() == 0


# ---------------------------------------------------------------------------
# Engine-level chaos (compiled paths; module-scoped model)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served_model():
    cfg = get_config("stablelm-1.6b").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, model, params


def _trace(vocab, n=6, plen=12, gens=(8, 6, 7, 5, 6, 4), deadlines=None):
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(n):
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, vocab, plen).astype(np.int32),
            max_new_tokens=gens[i % len(gens)],
            deadline_s=(deadlines or {}).get(i, 0.0)))
    return reqs


_CHAOS_COMMON = dict(max_len=21, max_slots=3, page_size=8, prefill_chunk=8,
                     spec_depth=2, kv_pages=12, reservation="lazy",
                     mem_watermark=0.0, prefix_cache="on")


def test_chaos_survivors_bit_identical_no_leaks(served_model):
    """The tentpole contract end to end: under injected NaNs, allocator
    exhaustion, growth denials and latency spikes — with speculation AND
    prefix caching on — serve() returns (never raises), every request
    reaches exactly one terminal state, survivors' greedy tokens match a
    fault-free run bit for bit, and the pool leaks nothing."""
    cfg, model, params = served_model
    base_eng = Engine(model, params, serve_cfg=ServeConfig(**_CHAOS_COMMON))
    base = _trace(cfg.vocab_size)
    res_b = base_eng.serve(base)
    assert res_b["stats"]["n_done"] == len(base)
    assert res_b["faults"] == {"enabled": False, "injected_total": 0}

    chaos_eng = Engine(model, params, serve_cfg=ServeConfig(
        **_CHAOS_COMMON, chaos_rate=0.15, chaos_seed=7))
    reqs = _trace(cfg.vocab_size)
    res = chaos_eng.serve(reqs)
    assert res["faults"]["injected_total"] >= 1, "chaos run injected nothing"
    assert res["page_leaks"] == 0
    chaos_eng._pool.allocator.check_invariants()
    for r in reqs:
        assert r.state in TERMINAL_STATES, f"rid {r.rid} stuck in {r.state}"
        if r.state is RequestState.DONE:
            assert r.out_tokens == base[r.rid].out_tokens, (
                f"chaos changed survivor {r.rid}'s tokens")
    assert res["failures"]["retries"] >= 1  # at least one transient retried


def test_chaos_relentless_nan_fails_requests(served_model):
    """When the same slot faults past max_retries the request goes
    terminal FAILED with its pages released; the trace still returns."""
    cfg, model, params = served_model
    eng = Engine(model, params, serve_cfg=ServeConfig(
        **_CHAOS_COMMON, chaos_rate=0.95, chaos_seed=1,
        chaos_sites=("logits.nan",), max_retries=2))
    reqs = _trace(cfg.vocab_size, n=2)
    res = eng.serve(reqs)
    assert all(r.state is RequestState.FAILED for r in reqs)
    assert all(r.error for r in reqs)
    assert res["failures"]["failed"] == 2
    assert set(res["failures"]["errors"]) == {0, 1}
    assert res["page_leaks"] == 0
    assert res["health"]["state"] != "healthy"


def test_chaos_safe_plan_fallback_and_recovery(served_model):
    """Sustained faults must pin the safe plan (spec0) without poisoning
    the step cache: a follow-up fault-free serve on the SAME engine runs
    healthy again and stays bit-identical to an untouched engine."""
    cfg, model, params = served_model
    eng = Engine(model, params, serve_cfg=ServeConfig(
        **_CHAOS_COMMON, chaos_rate=0.3, chaos_seed=11))
    reqs = _trace(cfg.vocab_size)
    res = eng.serve(reqs)
    assert res["health"]["fallbacks"] >= 1, "fallback never engaged"
    assert res["page_leaks"] == 0
    # disable chaos on the same engine: healthy plan must be restored
    eng.faults = None
    eng._pool.faults = None
    eng.governor.faults = None
    clean = _trace(cfg.vocab_size)
    res2 = eng.serve(clean)
    assert res2["stats"]["n_done"] == len(clean)
    assert res2["health"]["state"] == "healthy"
    assert res2["health"]["fallbacks"] == 0
    fresh_eng = Engine(model, params, serve_cfg=ServeConfig(**_CHAOS_COMMON))
    fresh = _trace(cfg.vocab_size)
    fresh_eng.serve(fresh)
    for a, b in zip(clean, fresh):
        assert a.out_tokens == b.out_tokens, (
            f"post-chaos engine diverged on rid {a.rid}")


def test_engine_abort_releases_pages(served_model):
    """A crash mid-serve (not a per-request fault) must release every
    resident's pages, mark residents FAILED, and re-raise with the
    allocator invariants intact — no stranded pages for the process to
    carry into its next trace."""
    cfg, model, params = served_model
    eng = Engine(model, params, serve_cfg=ServeConfig(**_CHAOS_COMMON))
    eng.serve(_trace(cfg.vocab_size, n=2))      # warm + build the pool
    calls = {"n": 0}
    real_step = eng._pool_step

    def dying_step(*a, **k):
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("device lost")
        return real_step(*a, **k)

    eng._pool_step = dying_step
    reqs = _trace(cfg.vocab_size)
    with pytest.raises(RuntimeError, match="device lost"):
        eng.serve(reqs)
    eng._pool_step = real_step
    eng._pool.allocator.check_invariants()
    assert eng._pool.leaked_pages() == 0
    assert all(r.state in (RequestState.FAILED, RequestState.WAITING,
                           RequestState.EXPIRED, RequestState.REJECTED)
               for r in reqs)
    assert any(r.state is RequestState.FAILED and "engine aborted" in r.error
               for r in reqs)


def test_engine_deadline_and_queue_shed(served_model):
    """Bounded admission on a live engine: the first waiting request
    carries a sub-ms deadline (expired), the backlog is capped (newest
    arrivals rejected), and everything admitted completes."""
    cfg, model, params = served_model
    eng = Engine(model, params, serve_cfg=ServeConfig(
        **_CHAOS_COMMON, max_queue=3))
    gens = (6, 5, 6, 5, 6, 5, 6, 5, 6)
    # request 3 must expire at the first shed, before the backlog cap
    # counts it; a 1 ns deadline has passed by then on any host, where
    # 0.2 ms raced the admission pass that precedes the shed
    reqs = _trace(cfg.vocab_size, n=9, gens=gens, deadlines={3: 1e-9})
    res = eng.serve(reqs)
    by_state = {r.rid: r.state for r in reqs}
    assert by_state[3] is RequestState.EXPIRED
    assert [r for r, s in by_state.items()
            if s is RequestState.REJECTED] == [7, 8]
    assert res["failures"]["expired"] == 1
    assert res["failures"]["rejected"] == 2
    assert res["stats"]["n_done"] == 6
    assert res["page_leaks"] == 0


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_chaos_every_request_terminal_property(served_model, seed):
    """Property over fault schedules: for ANY injector seed, serve()
    returns, every request lands in exactly one terminal state, page
    conservation holds, and nothing leaks.  One module-scoped engine is
    rewired per example so each seed reuses the compiled steps."""
    cfg, model, params = served_model
    eng = _property_engine(served_model)
    inj = FaultInjector(seed=seed, rate=0.3)
    eng.faults = inj
    eng._ensure_pool()
    eng._pool.faults = inj
    eng.governor.faults = inj
    reqs = _trace(cfg.vocab_size, n=4, gens=(6, 5, 4, 6))
    res = eng.serve(reqs)
    for r in reqs:
        assert r.state in TERMINAL_STATES, (
            f"seed {seed}: rid {r.rid} stuck in {r.state}")
    eng._pool.allocator.check_invariants()
    assert res["page_leaks"] == 0
    assert eng._pool.allocator.n_live >= 0
    done = [r for r in reqs if r.state is RequestState.DONE]
    assert res["stats"]["n_done"] == len(done)


_PROP_ENGINE = {}


def _property_engine(served_model):
    """One compiled engine shared by every property example (compilation
    dominates; the property varies only the injector)."""
    if "eng" not in _PROP_ENGINE:
        cfg, model, params = served_model
        _PROP_ENGINE["eng"] = Engine(model, params, serve_cfg=ServeConfig(
            **_CHAOS_COMMON, max_retries=2))
    return _PROP_ENGINE["eng"]
