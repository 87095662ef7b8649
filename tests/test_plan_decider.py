"""PlanDecider occupancy scaling and load-bucket replan triggering, unit
tested straight on ``Counters.scaled`` -> decision — no engine, no model.

The serve-time loop is: measured region counters, scaled by the pool's
occupancy fraction, featurised, classified by the tuner-trained tree, the
predicted candidate overlaid on the plan.  These tests pin each stage."""
import numpy as np
import pytest

from repro.core.counters import Counters
from repro.core.dtree import DecisionTree, features
from repro.core.policy import null_plan
from repro.serve.engine import PlanDecider, load_bucket


class _RC:
    """RegionCounters stand-in (regions dict + top_regions only)."""
    def __init__(self, regions):
        self.regions = regions

    def top_regions(self, key, n):
        items = [(r, getattr(c, key)) for r, c in self.regions.items()]
        return sorted(items, key=lambda kv: -kv[1])[:n]


# ---------------------------------------------------------------------------
# Counters.scaled — the occupancy attribution primitive
# ---------------------------------------------------------------------------


def test_counters_scaled_is_proportional_and_preserves_ops():
    c = Counters(flops=8e9, bytes=2e9, collective_bytes=1e8, link_bytes=5e7,
                 collective_ops=3, ops=17)
    half = c.scaled(0.5)
    assert half.flops == 4e9 and half.bytes == 1e9
    assert half.collective_bytes == 5e7 and half.link_bytes == 2.5e7
    assert half.collective_ops == 3 and half.ops == 17   # structure, not work
    # arithmetic intensity is occupancy-invariant; log-magnitudes shift
    f_full, f_half = features(c), features(half)
    assert np.isclose(f_full[4], f_half[4], rtol=1e-6)   # AI unchanged
    assert f_half[0] < f_full[0]                         # log_flops drops


def _spec_tree():
    """A tree shaped like the serving benchmark's: low occupancy (scaled
    counters look small / memory-ish) -> deep speculation, high -> shallow."""
    base = Counters(flops=8e9, bytes=2e9)
    X, y = [], []
    for frac, label in ((0.125, "spec4"), (0.25, "spec4"),
                        (0.5, "spec2"), (1.0, "spec2")):
        X.append(features(base.scaled(frac)))
        y.append(label)
    return DecisionTree(max_depth=3).fit(np.stack(X), y), base


def test_occupancy_scaling_switches_spec_depth_decision():
    """The same measured step flips the spec_depth candidate purely through
    the load_frac the decider scales the counters by."""
    tree, base = _spec_tree()
    rc = _RC({"layer0/attn": base})
    dec = PlanDecider(tree, kind="decode")
    low, dlow = dec.decide(rc, null_plan(), load_frac=0.25)
    high, dhigh = dec.decide(rc, null_plan(), load_frac=1.0)
    assert dict(dlow)["layer/attn"] == "spec4"
    assert dict(dhigh)["layer/attn"] == "spec2"
    assert low.config_for("layer3/attn").spec_depth == 4
    assert high.config_for("layer3/attn").spec_depth == 2


def test_spec_candidate_not_applied_to_non_attn_regions():
    tree, base = _spec_tree()
    rc = _RC({"layer0/mlp": base})
    dec = PlanDecider(tree, kind="decode")
    plan, decisions = dec.decide(rc, null_plan(), load_frac=0.25)
    # the tree votes, but spec candidates only apply to attention regions
    assert dict(decisions)["layer/mlp"].startswith("spec")
    assert plan.config_for("layer0/mlp").spec_depth == -1   # knob unset


# ---------------------------------------------------------------------------
# Load-bucket replan triggering
# ---------------------------------------------------------------------------


def test_load_bucket_is_next_power_of_two():
    assert [load_bucket(n) for n in range(9)] == [1, 1, 2, 4, 4, 8, 8, 8, 8]


def test_load_bucket_triggers_replan_only_on_bucket_change():
    """Replay an occupancy trace the way Engine._maybe_replan gates on it:
    a decision is re-taken exactly when the bucket changes, so plan churn
    tracks load swings logarithmically, not per-request."""
    trace = [1, 1, 2, 2, 3, 4, 4, 3, 2, 1, 1]
    last, replans = None, []
    for n_active in trace:
        b = load_bucket(n_active)
        if b != last:
            replans.append((n_active, b))
            last = b
    assert replans == [(1, 1), (2, 2), (3, 4), (2, 2), (1, 1)]
    # ramping within a bucket (3 -> 4 slots) triggered nothing
    assert all(n != 4 for n, _ in replans)


def test_bucketed_decisions_follow_occupancy_over_a_trace():
    """End-to-end over a synthetic occupancy swing: decisions taken at each
    bucket change pick deeper speculation at the trough than at the peak."""
    tree, base = _spec_tree()
    rc = _RC({"layer0/attn": base})
    dec = PlanDecider(tree, kind="decode")
    n_slots = 8
    picked = {}
    last = None
    for n_active in [1, 2, 5, 8, 5, 2, 1]:
        b = load_bucket(n_active)
        if b == last:
            continue
        last = b
        frac = min(b, n_slots) / n_slots
        _, decisions = dec.decide(rc, null_plan(), load_frac=frac)
        picked[b] = dict(decisions)["layer/attn"]
    assert picked[1] == "spec4" and picked[2] == "spec4"
    assert picked[8] == "spec2"


# ---------------------------------------------------------------------------
# Latency-aware channels: step_latency_p99 / queue_delay as dtree features
# ---------------------------------------------------------------------------


def test_latency_channels_reach_the_feature_vector():
    import dataclasses

    from repro.core.dtree import FEATURE_NAMES
    assert FEATURE_NAMES[-2:] == ("step_latency_p99", "queue_delay")
    base = Counters(flops=8e9, bytes=2e9)
    c = dataclasses.replace(base, step_latency_p99=0.25, queue_delay=0.5)
    f = features(c)
    assert len(f) == len(FEATURE_NAMES) == 11
    assert f[-2] == 0.25 and f[-1] == 0.5
    # occupancy scaling attributes compute, not observed latency: the
    # telemetry channels pass through Counters.scaled unchanged
    fs = features(c.scaled(0.25))
    assert fs[-2] == 0.25 and fs[-1] == 0.5
    assert fs[0] < f[0]                           # log_flops still drops


def _latency_tree():
    """Identical compute shape, different observed latency regime: a calm
    pool keeps elastic lazy admission, a latency-stressed one (preemption
    churn showing up as step-p99 spikes and queue delay) votes the
    preemption-free mem_full candidate.  The split can ONLY come from the
    telemetry feature channels — every other feature is constant."""
    import dataclasses
    base = Counters(flops=8e9, bytes=2e9)
    X, y = [], []
    for lat, qd, label in ((0.0, 0.0, "mem_lazy"), (0.25, 0.0, "mem_lazy"),
                           (1.5, 1.0, "mem_full"), (2.0, 1.5, "mem_full")):
        X.append(features(dataclasses.replace(
            base, step_latency_p99=lat, queue_delay=qd)))
        y.append(label)
    return DecisionTree(max_depth=3).fit(np.stack(X), y), base


def test_latency_features_switch_memory_policy_decision():
    """The same occupancy, the same measured compute — only the quantized
    step-latency p99 / queue-delay channels differ, and the decider lands
    a different reservation policy on the plan."""
    import dataclasses
    tree, base = _latency_tree()
    calm = dataclasses.replace(base, step_latency_p99=0.25)
    stressed = dataclasses.replace(base, step_latency_p99=1.75,
                                   queue_delay=1.25)
    dec = PlanDecider(tree, kind="decode")
    plan_c, d_c = dec.decide(_RC({"layer0/attn": calm}), null_plan(),
                             load_frac=1.0)
    plan_s, d_s = dec.decide(_RC({"layer0/attn": stressed}), null_plan(),
                             load_frac=1.0)
    assert dict(d_c)["layer/attn"] == "mem_lazy"
    assert dict(d_s)["layer/attn"] == "mem_full"
    assert plan_c.config_for("layer0/attn").reservation == "lazy"
    assert plan_s.config_for("layer0/attn").reservation == "full"


def test_bucket_log_ms_quantization_dedups_latency_windows():
    """The corpus-side quantizer: windows in the same latency regime land
    the same feature value (so observations merge), decades apart land
    apart, and the zero-latency floor is exact."""
    from repro.autotune.corpus import bucket_log_ms
    assert bucket_log_ms(0.0) == 0.0
    assert bucket_log_ms(0.010) == bucket_log_ms(0.011)   # same regime
    assert bucket_log_ms(0.001) < bucket_log_ms(0.1) < bucket_log_ms(10.0)
    # monotone, non-decreasing over a latency sweep
    vals = [bucket_log_ms(s) for s in (0.0, 1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0)]
    assert vals == sorted(vals)


# ---------------------------------------------------------------------------
# tp_degree: decider channel + engine-side resolution/clamping
# ---------------------------------------------------------------------------


def _tp_tree():
    """Low occupancy -> tp1 (latency/collective-bound decode), high
    occupancy -> tp4 (flops-bound prefill wants the model axis wide)."""
    base = Counters(flops=8e9, bytes=2e9)
    X, y = [], []
    for frac, label in ((0.125, "tp1"), (0.25, "tp1"),
                        (0.5, "tp4"), (1.0, "tp4")):
        X.append(features(base.scaled(frac)))
        y.append(label)
    return DecisionTree(max_depth=3).fit(np.stack(X), y), base


def test_occupancy_scaling_switches_tp_degree_decision():
    """The tp1/tp2/tp4 serve candidates are a decider channel like spec_*:
    the same measured step lands a different tp_degree on the plan purely
    through the load_frac scaling."""
    tree, base = _tp_tree()
    rc = _RC({"layer0/attn": base})
    dec = PlanDecider(tree, kind="decode")
    low, dlow = dec.decide(rc, null_plan(), load_frac=0.25)
    high, dhigh = dec.decide(rc, null_plan(), load_frac=1.0)
    assert dict(dlow)["layer/attn"] == "tp1"
    assert dict(dhigh)["layer/attn"] == "tp4"
    assert low.config_for("layer3/attn").tp_degree == 1
    assert high.config_for("layer3/attn").tp_degree == 4


def _stub_engine(tp_pin=0, n_kv_heads=4, paged=True, head_dim=128):
    """An Engine shell exercising tp_for/_step_cache_key resolution logic
    without a model: only the attributes those methods read are present."""
    from types import SimpleNamespace

    from repro.serve.engine import Engine, ServeConfig
    eng = object.__new__(Engine)
    eng.cfg = ServeConfig(tp=tp_pin)
    eng.model = SimpleNamespace(cfg=SimpleNamespace(
        n_kv_heads=n_kv_heads, n_experts=0, resolved_head_dim=head_dim))
    eng._paged = paged
    return eng


def _plan_with_tp(tp_degree):
    from repro.core.policy import RegionConfig
    plan = null_plan()
    plan.region_configs["layer/attn"] = RegionConfig(tp_degree=tp_degree)
    return plan


def test_tp_for_resolution_precedence_and_clamping(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [None] * 4)
    # plan knob decides in auto mode; unset means 1
    assert _stub_engine().tp_for(_plan_with_tp(2)) == 2
    assert _stub_engine().tp_for(null_plan()) == 1
    # an explicit ServeConfig.tp pins over the plan knob
    assert _stub_engine(tp_pin=4).tp_for(_plan_with_tp(1)) == 4
    # device-count clamp: tp4 on a 2-device host degrades to 2
    monkeypatch.setattr(jax, "devices", lambda: [None] * 2)
    assert _stub_engine().tp_for(_plan_with_tp(4)) == 2
    # kv-head divisibility clamp: 6 heads cannot split 4 ways, can 3
    monkeypatch.setattr(jax, "devices", lambda: [None] * 4)
    assert _stub_engine(n_kv_heads=6).tp_for(_plan_with_tp(4)) == 3
    # single device: everything is tp1
    monkeypatch.setattr(jax, "devices", lambda: [None] * 1)
    assert _stub_engine(tp_pin=4).tp_for(_plan_with_tp(4)) == 1


def test_step_cache_keys_on_resolved_tp_and_nothing_else(monkeypatch):
    """A tp change forces the expected recompile; allocator-policy knobs
    and clamped-identical degrees never do."""
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [None] * 2)
    from repro.core.policy import RegionConfig
    eng = _stub_engine()

    def plan_of(**kw):
        p = null_plan()
        p.region_configs["layer/attn"] = RegionConfig(**kw)
        return p

    k1 = eng._step_cache_key(plan_of(tp_degree=1))
    k2 = eng._step_cache_key(plan_of(tp_degree=2))
    assert k1 != k2                               # tp change -> new step
    # tp4 clamps to 2 on this 2-device host: shares the tp2 executable
    assert eng._step_cache_key(plan_of(tp_degree=4)) == k2
    # memory-policy knobs never reshape the step
    assert eng._step_cache_key(
        plan_of(tp_degree=2, reservation="lazy", mem_watermark=0.3,
                prefix_cache="on")) == k2


def _plan_with_attn(**kw):
    from repro.core.policy import RegionConfig
    plan = null_plan()
    plan.region_configs["layer/attn"] = RegionConfig(**kw)
    return plan


# (kv heads, head width) of stablelm-1.6b, stablelm-3b-4e1t and qwen3-8b:
# page rows of 2,048, 2,560 and 1,024 lanes, whole 128-lane tiles
PUBLISHED_HEADS = {64: 32, 80: 32, 128: 8}


def test_attn_impl_for_resolves_from_backend_and_tp(monkeypatch):
    """'' (auto) reads the Pallas kernel on a TPU at tp 1 — simulated here
    without a chip — for 64-, 80- and 128-wide heads at their published
    counts (page rows of whole lane tiles), and the gather on the CPU, at
    tp 2, or for rows off the lane tiles (4 heads of 80), over a pin too;
    on the CPU a pin reaches the interpreted kernel at any width."""
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [None] * 2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for hd, kvh in PUBLISHED_HEADS.items():
        eng = _stub_engine(n_kv_heads=kvh, head_dim=hd)
        assert eng.attn_impl_for(null_plan()) == "paged"
        assert eng.attn_impl_for(_plan_with_attn(attn_impl="paged")) == \
            "paged"
    assert _stub_engine(head_dim=80).attn_impl_for(
        _plan_with_attn(attn_impl="paged")) == "gather"
    assert _stub_engine().attn_impl_for(_plan_with_attn(tp_degree=2)) == \
        "gather"
    assert _stub_engine(tp_pin=2).attn_impl_for(null_plan()) == "gather"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert _stub_engine().attn_impl_for(null_plan()) == "gather"
    assert _stub_engine(head_dim=80).attn_impl_for(
        _plan_with_attn(attn_impl="paged")) == "paged"


def test_attn_impl_explicit_pins_and_safe_plan_win(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [None] * 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = _stub_engine()
    assert eng.attn_impl_for(_plan_with_attn(attn_impl="gather")) == "gather"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert eng.attn_impl_for(_plan_with_attn(attn_impl="paged")) == "paged"
    # the safe plan pins the gather explicitly, even over a 'paged' pin
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng.plan = _plan_with_attn(attn_impl="paged")
    safe = eng._safe_plan()
    assert safe.config_for("layer0/attn").attn_impl == "gather"
    assert eng.attn_impl_for(safe) == "gather"
    eng._force_safe = True
    assert eng.attn_impl_for(_plan_with_attn(attn_impl="paged")) == "gather"


def test_step_cache_keys_on_resolved_attn_impl(monkeypatch):
    """Plans that resolve to one impl share one step executable; auto
    resolving differently (another backend) keys another."""
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [None] * 1)
    eng = _stub_engine()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    auto_cpu = eng._step_cache_key(_plan_with_attn())
    assert auto_cpu == eng._step_cache_key(_plan_with_attn(attn_impl="gather"))
    assert auto_cpu != eng._step_cache_key(_plan_with_attn(attn_impl="paged"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    auto_tpu = eng._step_cache_key(_plan_with_attn())
    assert auto_tpu == eng._step_cache_key(_plan_with_attn(attn_impl="paged"))
    assert auto_tpu != auto_cpu


def test_plan_with_attn_impl_pins_only_unset_knobs():
    """The lowered plan carries the resolved impl wherever the knob was
    unset, keeps explicit per-region pins and every other knob."""
    from repro.core.policy import RegionConfig
    from repro.serve.engine import Engine
    plan = null_plan()
    plan.region_configs["layer"] = RegionConfig(remat=True)
    plan.region_configs["layer3/attn"] = RegionConfig(attn_impl="gather")
    pinned = Engine._plan_with_attn_impl(plan, "paged")
    assert pinned.config_for("layer0/attn").attn_impl == "paged"
    assert pinned.config_for("layer0/attn").remat is True
    assert pinned.config_for("layer3/attn").attn_impl == "gather"
    assert pinned.config_for("layer0/mlp").attn_impl == "paged"
    assert plan.config_for("layer0/attn").attn_impl == ""   # not mutated


@pytest.mark.parametrize("head_dim", [64, 80, 128])
def test_every_head_width_keeps_its_pins(monkeypatch, head_dim):
    """On a TPU every head width at its published count keys a 'paged'
    pin and the auto plan to one executable, and lowering pins the
    resolved impl only where the knob was unset: an explicit 'gather' pin
    stays."""
    import jax
    from repro.core.policy import RegionConfig
    from repro.serve.engine import Engine
    monkeypatch.setattr(jax, "devices", lambda: [None] * 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = _stub_engine(n_kv_heads=PUBLISHED_HEADS[head_dim],
                       head_dim=head_dim)
    assert eng._step_cache_key(_plan_with_attn(attn_impl="paged")) == \
        eng._step_cache_key(_plan_with_attn())
    plan = _plan_with_attn()
    plan.region_configs["layer3/attn"] = RegionConfig(attn_impl="gather")
    lowered = Engine._plan_with_attn_impl(plan, eng.attn_impl_for(plan))
    assert [lowered.config_for(f"layer{i}/attn").attn_impl
            for i in range(4)] == ["paged", "paged", "paged", "gather"]


def test_refused_rows_lower_the_gather_over_pins(monkeypatch):
    """On a TPU, page rows the compiled kernel refuses (4 kv heads of 80:
    320 lanes) lower the gather in every region, over explicit 'paged'
    pins, and key one executable with the auto plan."""
    import jax
    from repro.core.policy import RegionConfig
    from repro.serve.engine import Engine
    monkeypatch.setattr(jax, "devices", lambda: [None] * 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = _stub_engine(n_kv_heads=4, head_dim=80)
    pinned = _plan_with_attn(attn_impl="paged")
    pinned.region_configs["layer3/attn"] = RegionConfig(attn_impl="paged")
    assert eng._kernel_refuses_rows()
    auto = _plan_with_attn()
    auto.region_configs["layer3/attn"] = RegionConfig()
    assert eng._step_cache_key(pinned) == eng._step_cache_key(auto)
    lowered = Engine._plan_with_attn_impl(pinned, "gather", override=True)
    assert {lowered.config_for(f"layer{i}/attn").attn_impl
            for i in range(4)} == {"gather"}
    assert not _stub_engine(n_kv_heads=8, head_dim=128)._kernel_refuses_rows()
