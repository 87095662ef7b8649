"""Serve telemetry: quantile-sketch properties, span lifecycle invariants
on real serves (including preemption), exporter validity (Chrome trace /
Prometheus text / JSONL), telemetry-on bit-identity, the zero-allocation
disabled path, the residual measurement-tap flush fix, and the serve
loop's profiler spans and counters."""
import json
import math
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.configs.registry import get_config
from repro.models.model import build
from repro.serve.engine import Engine, ServeConfig
from repro.serve.scheduler import Request, RequestState
from repro.serve.telemetry import (LatencySketch, SpanTracer, Telemetry,
                                   prometheus_text)

# ---------------------------------------------------------------------------
# LatencySketch: provable rank/relative-error bound
# ---------------------------------------------------------------------------


@settings(max_examples=60)
@given(micros=st.lists(st.integers(min_value=1, max_value=100_000_000),
                       min_size=1, max_size=200))
def test_sketch_quantile_relative_error_bound(micros):
    """For every p, the sketch's quantile brackets the exact order
    statistic q at rank ceil(p*n): q <= quantile(p) <= q * growth
    (the documented HDR-style guarantee, up to float rounding)."""
    sk = LatencySketch()
    vals = [m * 1e-6 for m in micros]
    for v in vals:
        sk.add(v)
    ordered = sorted(vals)
    for p in (0.0, 0.5, 0.9, 0.99, 1.0):
        q = ordered[max(1, math.ceil(p * len(vals))) - 1]
        v = sk.quantile(p)
        assert q <= v * (1 + 1e-9), f"p={p}: {v} below exact {q}"
        assert v <= q * sk.growth * (1 + 1e-9), (
            f"p={p}: {v} above bound {q * sk.growth}")


@settings(max_examples=40)
@given(micros=st.lists(st.integers(min_value=1, max_value=100_000_000),
                       min_size=1, max_size=100))
def test_sketch_count_min_max_mean_exact(micros):
    sk = LatencySketch()
    vals = [m * 1e-6 for m in micros]
    for v in vals:
        sk.add(v)
    assert sk.count == len(vals)
    assert sk.min == min(vals) and sk.max == max(vals)
    assert math.isclose(sk.mean, sum(vals) / len(vals), rel_tol=1e-9)
    s = sk.summary()
    assert s["count"] == len(vals) and s["p50"] <= s["p90"] <= s["p99"]


def test_sketch_empty_and_bad_growth():
    assert LatencySketch().quantile(0.99) == 0.0
    with pytest.raises(ValueError):
        LatencySketch(growth=1.0)


# ---------------------------------------------------------------------------
# SpanTracer unit behavior
# ---------------------------------------------------------------------------


def test_tracer_nesting_and_auto_close():
    tr = SpanTracer()
    tr.begin(7, "PREFILL", 1.0)
    tr.begin(7, "PREFILL_CHUNK", 1.1)
    # ending the parent auto-closes the still-open child at the same
    # instant, so spans always nest
    assert tr.end(7, "PREFILL", 2.0)
    kinds = {k: (t0, t1) for _, k, t0, t1, _ in tr.spans}
    assert kinds["PREFILL"] == (1.0, 2.0)
    assert kinds["PREFILL_CHUNK"] == (1.1, 2.0)
    assert not tr.end(7, "PREFILL", 3.0)        # nothing left open
    assert not tr.end(8, "DECODE", 3.0)         # never opened


def test_tracer_end_all_and_cap():
    tr = SpanTracer(cap=2)
    tr.begin(1, "PREFILL", 0.0)
    tr.begin(1, "DECODE", 1.0)
    tr.end_all(1, 2.0)
    assert len(tr.spans) == 2 and tr.dropped == 0
    tr.add(2, "QUEUED", 0.0, 1.0)               # over cap: counted, dropped
    assert len(tr.spans) == 2 and tr.dropped == 1
    assert not tr.has_open(1, "DECODE")


def test_tracer_chrome_trace_schema():
    tr = SpanTracer()
    tr.add(0, "QUEUED", 0.0, 0.5, note="x")
    tr.instant(0, "DONE", 0.5)
    doc = tr.chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert metas and metas[0]["name"] == "thread_name"
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and xs[0]["dur"] == pytest.approx(0.5e6)
    assert xs[0]["args"] == {"note": "x"}
    ins = [e for e in evs if e["ph"] == "i"]
    assert ins and ins[0]["s"] == "t" and "dur" not in ins[0]
    json.loads(json.dumps(doc))                 # round-trips as JSON


# ---------------------------------------------------------------------------
# Real serves: bit-identity, lifecycle invariants, exporters, off-path
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """One model, a telemetry-off and a telemetry-on engine serving the
    identical mixed-length trace, plus the on-engine's serve result."""
    cfg = get_config("stablelm-1.6b").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, cfg.vocab_size, (4, 8)).astype(np.int32)
    gens = [6, 10, 6, 8]

    def mk():
        return [Request(rid=i, prompt=prompts[i].copy(), max_new_tokens=g,
                        arrival_s=0.002 * i) for i, g in enumerate(gens)]

    common = dict(max_len=8 + max(gens) + 1, max_slots=2, page_size=8,
                  prefill_chunk=8, spec_depth=0)
    eng_off = Engine(model, params, serve_cfg=ServeConfig(**common))
    eng_on = Engine(model, params, serve_cfg=ServeConfig(
        **common, telemetry=True, log_level="debug"))
    reqs_off, reqs_on = mk(), mk()
    res_off = eng_off.serve(reqs_off)
    res_on = eng_on.serve(reqs_on)
    return (model, params, common, mk, eng_off, eng_on,
            reqs_off, reqs_on, res_off, res_on)


def test_telemetry_on_is_bit_identical(served):
    _, _, _, _, _, _, reqs_off, reqs_on, _, _ = served
    for a, b in zip(reqs_on, reqs_off):
        assert a.state is RequestState.DONE
        assert a.out_tokens == b.out_tokens, (
            f"telemetry changed request {a.rid}'s greedy tokens")


def _check_lifecycle(tracer, reqs):
    """Spans per request nest, start at arrival, and cover the whole
    admission -> terminal timeline without gaps."""
    lifecycle = ("QUEUED", "PREFILL", "DECODE", "PREEMPTED")
    for r in reqs:
        spans = tracer.spans_for(r.rid)
        assert spans, f"request {r.rid} traced no spans"
        # pairwise: any two spans are disjoint or properly nested
        for i, (_, _, a0, a1, _) in enumerate(spans):
            for _, _, b0, b1, _ in spans[i + 1:]:
                assert (a1 <= b0 or b1 <= a0
                        or (a0 <= b0 and b1 <= a1)
                        or (b0 <= a0 and a1 <= b1)), (
                    f"request {r.rid}: spans overlap without nesting")
        chain = sorted([s for s in spans if s[1] in lifecycle],
                       key=lambda s: (s[2], s[3]))
        assert chain[0][1] == "QUEUED", f"request {r.rid} skipped QUEUED"
        assert chain[0][2] == pytest.approx(r.arrival_s), (
            f"request {r.rid}'s QUEUED span misses its arrival")
        for prev, nxt in zip(chain, chain[1:]):
            assert nxt[2] == prev[3], (
                f"request {r.rid}: gap between {prev[1]} and {nxt[1]}")
        assert chain[-1][1] == "DECODE", f"request {r.rid} never decoded"
        terminals = [s for s in spans if s[1] == "DONE"]
        assert len(terminals) == 1
        assert terminals[0][2] == chain[-1][3], (
            f"request {r.rid}: DONE marker off the DECODE close")
        # intra-phase chunks stay inside their PREFILL parents
        pf = [(t0, t1) for _, k, t0, t1, _ in spans if k == "PREFILL"]
        for _, k, t0, t1, _ in spans:
            if k == "PREFILL_CHUNK":
                assert any(p0 <= t0 and t1 <= p1 for p0, p1 in pf)


def test_span_lifecycle_covers_admission_to_terminal(served):
    _, _, _, _, _, eng_on, _, reqs_on, _, res_on = served
    _check_lifecycle(eng_on.telemetry.tracer, reqs_on)
    tm = res_on["telemetry"]
    assert tm["spans"] == len(eng_on.telemetry.tracer.spans)
    assert tm["spans_dropped"] == 0
    assert tm["step_latency_s"]["count"] == res_on["steps"]
    assert tm["queue_delay_s"]["count"] == len(reqs_on)
    assert tm["ttft_s"]["count"] == len(reqs_on)
    assert tm["counts"]["admissions"] == len(reqs_on)


def test_preemption_spans_under_overcommit(served):
    """An overcommitted lazy pool preempts; the victim's timeline gains a
    PREEMPTED span that still chains gap-free into its re-admission."""
    model, params, _, _, _, _, _, _, _, _ = served
    cfg = get_config("stablelm-1.6b").reduced()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (6, 8)).astype(np.int32)
    reqs = [Request(rid=i, prompt=prompts[i].copy(), max_new_tokens=g)
            for i, g in enumerate([20, 20, 24, 20, 20, 24])]
    eng = Engine(model, params, serve_cfg=ServeConfig(
        max_len=8 + 24 + 1, max_slots=4, page_size=8, prefill_chunk=8,
        kv_pages=11, reservation="lazy", mem_watermark=0.0,
        telemetry=True))
    res = eng.serve(reqs)
    assert res["memory"]["preemptions"] >= 1
    preempted = [s for s in eng.telemetry.tracer.spans
                 if s[1] == "PREEMPTED"]
    assert len(preempted) >= 1
    assert all(t1 > t0 for _, _, t0, t1, _ in preempted)
    _check_lifecycle(eng.telemetry.tracer, reqs)
    assert eng.telemetry.counts.get("readmissions", 0) >= 1


def test_chrome_trace_export_valid(served):
    _, _, _, _, _, eng_on, _, _, _, _ = served
    doc = eng_on.telemetry.chrome_trace()
    evs = doc["traceEvents"]
    assert evs
    for ev in evs:
        assert {"ph", "pid", "tid", "name"} <= set(ev)
        assert ev["ph"] in ("X", "i", "M")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and "ts" in ev
        if ev["ph"] == "i":
            assert ev["s"] == "t" and "ts" in ev
    kinds = {e["name"] for e in evs if e["ph"] != "M"}
    assert {"QUEUED", "PREFILL", "PREFILL_CHUNK", "DECODE", "DONE"} <= kinds
    json.loads(json.dumps(doc))


def test_prometheus_export_parses(served):
    _, _, _, _, eng_off, eng_on, _, _, _, _ = served
    text = eng_on.metrics_text()
    samples = {}
    for line in text.splitlines():
        if line.startswith("#"):
            assert line.startswith(("# TYPE ", "# HELP ")), line
            continue
        name, val = line.rsplit(" ", 1)
        samples[name] = float(val)          # every sample value parses
    assert any(k.startswith("repro_serve_step_latency_seconds{")
               for k in samples)
    assert samples["repro_serve_step_latency_seconds_count"] > 0
    assert "repro_serve_health_steps" in samples
    assert "repro_serve_memory_preemptions" in samples
    # the exporter also works with telemetry off: observability-only
    off_text = eng_off.metrics_text()
    assert "repro_serve_health_steps" in off_text
    assert "step_latency_seconds" not in off_text


def test_event_log_levels_and_jsonl(served, tmp_path):
    _, _, _, _, _, eng_on, _, _, _, res_on = served
    tm = res_on["telemetry"]
    assert tm["events"] > 0
    kinds = {e["kind"] for e in eng_on.telemetry.events}
    assert "step" in kinds and "serve_done" in kinds
    # warning-level telemetry filters the debug/info stream
    t = Telemetry(level="warning", log_out=str(tmp_path / "ev.jsonl"))
    t.event("noise", level="debug", x=1)
    t.event("info_noise", level="info", x=2)
    t.event("trouble", level="warning", x=3)
    t.close()
    lines = [json.loads(l) for l in open(tmp_path / "ev.jsonl")]
    assert [e["kind"] for e in lines] == ["trouble"]
    with pytest.raises(ValueError):
        Telemetry(level="loud")


def test_observability_aggregate_matches_legacy_keys(served):
    """The per-subsystem summary() dicts now hang off one
    Engine.observability() aggregate; serve() still returns the same
    top-level keys the launcher and benches always read."""
    _, _, _, _, _, eng_on, _, _, res_off, res_on = served
    for res in (res_off, res_on):
        for key in ("stats", "failures", "memory", "mesh", "health",
                    "faults", "autotune", "requests", "decisions", "steps"):
            assert key in res, f"serve() lost the {key!r} key"
    obs = eng_on.observability()
    assert {"memory", "health", "faults", "autotune", "telemetry"} <= set(obs)
    assert "stats" not in obs               # request rollups need requests
    assert "reservation" in obs["memory"]   # paged-pool governor summary
    assert obs["telemetry"]["enabled"] is True
    # requests passed -> the rollups appear, matching the serve() result
    obs_r = eng_on.observability(res_on["requests"])
    assert obs_r["stats"] == res_on["stats"]
    assert obs_r["failures"] == res_on["failures"]


def test_disabled_path_allocates_nothing_from_telemetry(served):
    """With telemetry off the subsystem is never constructed and the hot
    path never touches telemetry.py: a traced serve shows zero
    allocations from the module (the one-`is not None`-check contract)."""
    model, params, common, mk, eng_off, _, _, _, _, _ = served
    assert eng_off.telemetry is None
    reqs = mk()
    tracemalloc.start()
    try:
        eng_off.serve(reqs)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    # match the source module only ("*telemetry.py" would also catch this
    # test file, whose name ends the same way)
    tele = snap.filter_traces(
        [tracemalloc.Filter(True, "*/serve/telemetry.py")]).statistics("filename")
    assert not tele, f"telemetry-off serve allocated via telemetry.py: {tele}"


def test_residual_tap_flush_not_lost(served):
    """Bugfix: a serve ending mid-retrain-interval used to drop the final
    partial measurement-tap accumulator — the corpus stayed empty for any
    trace shorter than retrain_interval.  The residual flush at loop exit
    must land those observations."""
    model, params, common, mk, _, _, _, _, _, _ = served
    eng = Engine(model, params, serve_cfg=ServeConfig(
        **common, online_retrain=True, retrain_interval=10_000,
        explore_eps=0.0))
    res = eng.serve(mk())
    assert res["steps"] < 10_000
    at = eng.autotune_summary()
    assert at["corpus_entries"] >= 1, (
        "short serve's measurement tap was lost at loop exit")
    # the landed observations carry the latency-aware feature channels
    # (FEATURE_NAMES[-2:] == step_latency_p99, queue_delay)
    feats = [e.features for e in eng.corpus.entries()]
    assert all(len(f) == 11 for f in feats)
    assert any(f[-2] > 0 for f in feats), (
        "no observation recorded a quantized step-latency p99")
    assert all(f[-1] >= 0 for f in feats)


# ---------------------------------------------------------------------------
# Serve-loop spans on the profiler's clock
# ---------------------------------------------------------------------------
PHASES = ("serve.admit", "serve.chunk_dispatch", "serve.headroom",
          "serve.step_dispatch", "serve.step_wait", "serve.commit",
          "serve.tap", "serve.idle")


def _program_spans(log_dir):
    """(name, start_ns, end_ns, counters) of every serve.* / python.gc
    host event in the profile written under ``log_dir``."""
    import glob
    import os
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve.") or ev.name == "python.gc":
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def profiled(served, tmp_path_factory):
    """A warmed paged engine serving multi-chunk prompts under a profiler
    session — prefills overlap decode steps, and the last arrival leaves
    the loop idle first; its pool step wrapped to keep each call's
    ``lengths`` and ``active`` arguments."""
    model, params, _, _, _, _, _, _, _, _ = served
    cfg = get_config("stablelm-1.6b").reduced()
    rng = np.random.default_rng(7)
    arrivals = (0.0, 0.0, 0.004, 0.008, 0.3)

    def mk():
        return [Request(rid=i, prompt=rng.integers(
                    0, cfg.vocab_size, 6 + 5 * i).astype(np.int32),
                    max_new_tokens=6 + i, arrival_s=t)
                for i, t in enumerate(arrivals)]

    eng = Engine(model, params, serve_cfg=ServeConfig(
        max_len=40, max_slots=3, page_size=8, prefill_chunk=8,
        spec_depth=0))
    eng.serve(mk())                                 # compile outside
    calls = []
    step = eng._pool_step

    def kept(params, pages, toks, bt, lengths, act, key):
        calls.append((np.asarray(lengths), np.asarray(act)))
        return step(params, pages, toks, bt, lengths, act, key)

    eng._pool_step = kept
    log_dir = tmp_path_factory.mktemp("profile")
    reqs = mk()
    with jax.profiler.trace(str(log_dir)):
        res = eng.serve(reqs)
    return _program_spans(log_dir), res, reqs, calls


def test_serve_spans_nest_run_loop_pass_phase(profiled):
    spans, _, _, _ = profiled

    def within(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    run, = [s for s in spans if s[0] == "serve.run"]
    loop, = [s for s in spans if s[0] == "serve.loop"]
    passes = [s for s in spans if s[0] == "serve.pass"]
    assert within(loop, run) and passes
    assert all(within(p, loop) for p in passes)
    phases = [s for s in spans if s[0] in PHASES]
    assert {s[0] for s in phases} >= {
        "serve.admit", "serve.chunk_dispatch", "serve.headroom",
        "serve.step_dispatch", "serve.step_wait", "serve.commit",
        "serve.tap", "serve.idle"}
    for ph in phases:
        assert sum(within(ph, p) for p in passes) == 1, ph
    # passes follow one another; the phases of a pass do too
    for a, b in zip(passes, passes[1:]):
        assert a[2] <= b[1]
    for a, b in zip(phases, phases[1:]):
        assert a[2] <= b[1]


def test_serve_pass_counters_match_the_serve(profiled):
    spans, res, reqs, _ = profiled
    passes = [s[3] for s in spans if s[0] == "serve.pass"]
    stepped = [c for c in passes if "step" in c]
    assert len(stepped) == res["steps"]
    assert [c["step"] for c in stepped] == list(range(1, res["steps"] + 1))
    assert sum(c.get("tokens", 0) for c in passes) == sum(
        len(r.out_tokens) for r in reqs)
    chunks = [s[3] for s in spans if s[0] == "serve.chunk_dispatch"]
    assert sum(c.get("chunks", 0) for c in passes) == len(chunks)
    # each request's chunks cover its prompt but the pending last token
    for r in reqs:
        mine = [c for c in chunks if c["rid"] == r.rid]
        assert [c["base"] for c in mine] == list(range(
            0, r.prompt.size - 1, 8))
        assert sum(c["tokens"] for c in mine) == r.prompt.size - 1
    admitted = [s[3] for s in spans if s[0] == "serve.admit"]
    assert sum(c["admitted"] for c in admitted) == len(reqs)


def test_step_dispatch_live_tokens_are_the_steps_lengths(profiled):
    spans, res, _, calls = profiled
    got = [s[3] for s in spans if s[0] == "serve.step_dispatch"]
    assert len(got) == len(calls) == res["steps"]
    for c, (lengths, act) in zip(got, calls):
        assert c["live_tokens"] == int(lengths[act].sum())
        assert c["active"] == int(act.sum())


def _page_bytes(model, dtype) -> int:
    """K and V bytes of one page of 8 tokens over every layer, in the
    pool's layout."""
    spec = model.paged_cache_spec(1, 8, dtype=dtype)
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(spec))


def test_kv_pages_read_counts_every_page_on_the_gather_path(profiled, served):
    """On the CPU the step resolves to the gather, which reads every
    block-table page of every active slot; ``kv_bytes_read`` is those
    pages' bytes."""
    spans, _, _, calls = profiled
    model, params = served[:2]
    dtype = jax.tree.leaves(params)[0].dtype
    got = [s[3] for s in spans if s[0] == "serve.step_dispatch"]
    max_pages = 5                       # max_len 40 over pages of 8
    for c, (_, act) in zip(got, calls):
        assert c["kv_pages_read"] == int(act.sum()) * max_pages
        assert c["kv_bytes_read"] == c["kv_pages_read"] * _page_bytes(
            model, dtype)


def test_kv_pages_read_counts_live_pages_on_the_kernel_path(served,
                                                            tmp_path,
                                                            monkeypatch):
    """With the Pallas kernel pinned (interpreted on the CPU), each step's
    ``kv_pages_read`` is the sum over active slots of the pages their
    attention sees: the written tokens plus this step's row.  It is also
    what every layer's kernel call reads: inactive slots reach the kernel
    at length 0."""
    from repro.core.policy import RegionConfig, RegionPlan
    from repro.kernels import ops
    from repro.kernels.paged_attention import live_pages
    model, params, _, _, _, _, _, _, _, _ = served
    rng = np.random.default_rng(11)
    read = []
    kernel = ops.paged_attention_mq

    def counted(q, k_pages, v_pages, bt, lengths, *, block_k=0):
        jax.debug.callback(lambda n: read.append(int(n)), live_pages(
            lengths, q.shape[1], k_pages.shape[1]).sum())
        return kernel(q, k_pages, v_pages, bt, lengths, block_k=block_k)

    monkeypatch.setattr(ops, "paged_attention_mq", counted)

    def mk():
        return [Request(rid=i, prompt=rng.integers(0, 256, 5 + 9 * i)
                        .astype(np.int32), max_new_tokens=4 + 3 * i)
                for i in range(2)]

    plan = RegionPlan(region_configs={
        "layer/attn": RegionConfig(attn_impl="paged")})
    eng = Engine(model, params, plan=plan, serve_cfg=ServeConfig(
        max_len=40, max_slots=2, page_size=8, prefill_chunk=8,
        spec_depth=0))
    eng.serve(mk())                                 # compile outside
    assert eng.observability()["step"]["attn_impl"] == "paged"
    calls = []
    step = eng._pool_step

    def kept(params, pages, toks, bt, lengths, act, key):
        calls.append((np.asarray(lengths), np.asarray(act)))
        return step(params, pages, toks, bt, lengths, act, key)

    eng._pool_step = kept
    read.clear()
    with jax.profiler.trace(str(tmp_path)):
        res = eng.serve(mk())
    got = [s[3] for s in _program_spans(tmp_path)
           if s[0] == "serve.step_dispatch"]
    assert len(got) == len(calls) == res["steps"]
    for c, (lengths, act) in zip(got, calls):
        assert c["kv_pages_read"] == int(
            live_pages(lengths[act] + 1, 1, 8).sum())
        assert c["kv_bytes_read"] == (c["kv_pages_read"]
                                      * eng._pool.page_bytes())
    assert any(not act.all() for _, act in calls)   # inactive slots ran
    assert sorted(read) == sorted(c["kv_pages_read"] for c in got
                                  for _ in range(model.cfg.n_layers))


def test_program_times_sit_on_the_trace_clock(profiled):
    """The loop's clock starts at the serve.loop span's start: a request's
    admission time placed on the trace falls inside a serve.admit span,
    and its prefill time inside its own last chunk dispatch."""
    spans, _, reqs, _ = profiled
    t0 = next(s[1] for s in spans if s[0] == "serve.loop")

    def inside(t_s, name, **counters):
        t = t0 + t_s * 1e9
        return any(s[0] == name and s[1] <= t <= s[2]
                   and all(s[3].get(k) == v for k, v in counters.items())
                   for s in spans)

    for r in reqs:
        assert inside(r.t_admit, "serve.admit")
        assert inside(r.t_prefilled, "serve.chunk_dispatch", rid=r.rid)
        assert r.t_admit < r.t_prefilled < r.t_first


def test_no_profiler_session_no_span():
    from repro.serve import telemetry
    with telemetry.span("serve.pass") as sp:
        assert sp is None
    assert telemetry.span("serve.pass") is telemetry.span("serve.commit")


def test_gc_callback_records_python_gc_span(tmp_path):
    import gc
    from repro.serve.telemetry import GcSpans
    cb = GcSpans()
    gc.callbacks.append(cb)
    try:
        with jax.profiler.trace(str(tmp_path)):
            gc.collect(1)
    finally:
        gc.callbacks.remove(cb)
    got = [s for s in _program_spans(tmp_path) if s[0] == "python.gc"]
    assert got and any(s[3].get("generation") == 1 for s in got)
    assert all(s[2] >= s[1] for s in got)


def test_start_decode_stamps_t_prefilled():
    from repro.serve.scheduler import Scheduler
    sched = Scheduler()
    req = Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                  max_new_tokens=2)
    sched.submit(req)
    sched.pop_ready(0.0)
    sched.bind_prefill(req, 0, 0.5)
    assert req.t_prefilled is None
    sched.start_decode(req, 1.25)
    assert (req.t_admit, req.t_prefilled) == (0.5, 1.25)
