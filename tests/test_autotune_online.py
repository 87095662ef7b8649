"""Online autotuning subsystem (repro.autotune): corpus append/dedup/merge
properties, DecisionTree JSON round-trip, the trainer's holdout regret
gate, epsilon-greedy exploration budgets, and the engine-level hot-swap
regression (a swapped tree must bust the load-bucket replan latch)."""
import math

import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.autotune.corpus import Corpus, CorpusEntry
from repro.autotune.explorer import EpsilonGreedyExplorer
from repro.autotune.trainer import OnlineTrainer, holdout_value
from repro.core.counters import Counters
from repro.core.dtree import DecisionTree, features
from repro.core.policy import null_plan

# ---------------------------------------------------------------------------
# Corpus: append / dedup / merge / persistence
# ---------------------------------------------------------------------------

_F = np.arange(7.0)


def test_corpus_append_dedups_and_means_rewards():
    c = Corpus()
    e1 = c.append("layer/attn", _F, "spec2", 10.0)
    e2 = c.append("layer/attn", _F, "spec2", 30.0)
    assert e1 is e2 and len(c) == 1 and c.observations == 2
    assert e1.n == 2 and e1.reward == 20.0
    # a different class (or region, or features) is a distinct entry
    c.append("layer/attn", _F, "spec4", 5.0)
    c.append("layer/mlp", _F, "spec2", 5.0)
    c.append("layer/attn", _F + 1, "spec2", 5.0)
    assert len(c) == 4
    assert c.classes() == {"spec2", "spec4"}


def test_corpus_reward_upgrades_offline_label():
    c = Corpus()
    c.append("offline", _F, "ff_tp")                  # unrewarded search label
    assert not c.entries()[0].rewarded
    c.append("offline", _F, "ff_tp", 7.0)             # live reward arrives
    e = c.entries()[0]
    assert e.rewarded and e.reward == 7.0 and e.n == 2 and len(c) == 1


def test_corpus_merge_offline_pairs():
    c = Corpus()
    n = c.merge_offline([(_F, "attn_tp_heads"), (_F + 1, "ff_tp")])
    assert n == 2 and len(c) == 2
    assert all(not e.rewarded for e in c.entries())
    X, y = c.training_data()
    assert sorted(y) == ["attn_tp_heads", "ff_tp"] and X.shape == (2, 7)


def test_corpus_training_data_labels_argmax_reward():
    c = Corpus()
    c.append("layer/attn", _F, "spec0", 100.0)
    c.append("layer/attn", _F, "spec4", 300.0)
    c.append("layer/attn", _F + 1, "spec0", 50.0)
    X, y = c.training_data()
    by_feat = {tuple(x): cls for x, cls in zip(X, y)}
    assert by_feat[tuple(_F)] == "spec4"              # best observed wins
    assert by_feat[tuple(_F + 1)] == "spec0"


@settings(max_examples=25)
@given(obs=st.lists(
    st.integers(min_value=0, max_value=59), min_size=0, max_size=40))
def test_corpus_merge_equals_sequential_append(obs):
    """Property: appending a stream into one corpus == splitting the stream
    arbitrarily into two corpora and merging — same entries, same rewards,
    same observation count (merge is dedup-respecting and n-weighted)."""
    def decode(o):
        region = f"r{o % 2}"
        feat = _F + (o // 2) % 3
        cls = ["spec0", "spec2", "spec4"][(o // 6) % 3]
        reward = float(o) if o % 5 else math.nan
        return region, feat, cls, reward

    whole, left, right = Corpus(), Corpus(), Corpus()
    for i, o in enumerate(obs):
        region, feat, cls, reward = decode(o)
        whole.append(region, feat, cls, reward)
        (left if i % 2 else right).append(region, feat, cls, reward)
    merged = left.merge(right)
    assert len(merged) == len(whole)
    assert merged.observations == whole.observations == len(obs)
    a = {e.key(): (e.n, e.rewarded) for e in merged.entries()}
    b = {e.key(): (e.n, e.rewarded) for e in whole.entries()}
    assert a == b
    for e in whole.entries():                         # rewards match (means
        m = merged._entries[e.key()]                  # are order-independent)
        if e.rewarded:
            assert np.isclose(m.reward, e.reward)


@settings(max_examples=15)
@given(obs=st.lists(
    st.integers(min_value=0, max_value=59), min_size=1, max_size=30))
def test_corpus_jsonl_roundtrip(obs):
    import os
    import tempfile
    c = Corpus()
    for o in obs:
        c.append(f"r{o % 3}", _F * (o % 4), f"cls{o % 5}",
                 float(o) if o % 2 else math.nan)
    fd, p = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        assert c.save_jsonl(p) == len(c)
        c2 = Corpus.load_jsonl(p)
    finally:
        os.unlink(p)
    assert len(c2) == len(c) and c2.observations == c.observations
    for e in c.entries():
        e2 = c2._entries[e.key()]
        assert e2.n == e.n
        assert (not e.rewarded and not e2.rewarded) or np.isclose(
            e2.reward, e.reward)


# ---------------------------------------------------------------------------
# DecisionTree JSON round-trip: identical predictions on the corpus
# ---------------------------------------------------------------------------


@settings(max_examples=20)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n=st.integers(min_value=2, max_value=40),
       k=st.integers(min_value=1, max_value=4))
def test_dtree_json_roundtrip_identical_predictions(seed, n, k):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 7)) * rng.uniform(0.5, 5.0)
    y = [f"class{int(i)}" for i in rng.integers(0, k, n)]
    tree = DecisionTree(max_depth=5).fit(X, y)
    tree2 = DecisionTree.from_json(tree.to_json())
    assert tree2.classes_ == tree.classes_
    assert tree2.predict(X) == tree.predict(X)
    # and on points the fit never saw
    X2 = rng.normal(size=(16, 7)) * 3.0
    assert tree2.predict(X2) == tree.predict(X2)


def test_dtree_roundtrip_on_autotune_corpus():
    """The exact artifact the serve launcher writes: a tree trained from a
    rewarded corpus survives to_json/from_json with identical votes."""
    c = Corpus()
    base = Counters(flops=8e9, bytes=2e9)
    for frac, cls, r in ((0.25, "spec4", 900.0), (0.25, "spec2", 500.0),
                         (1.0, "spec2", 800.0), (1.0, "spec4", 300.0)):
        c.append("layer/attn", features(base.scaled(frac)), cls, r)
    X, y = c.training_data()
    tree = DecisionTree(max_depth=4).fit(X, y)
    tree2 = DecisionTree.from_json(tree.to_json())
    assert tree2.predict(X) == tree.predict(X) == ["spec4", "spec2"]


# ---------------------------------------------------------------------------
# OnlineTrainer: triggers + the holdout regret gate
# ---------------------------------------------------------------------------


class _FixedTree:
    """Stand-in tree with a hand-set decision rule."""
    def __init__(self, fn):
        self.fn = fn

    def predict_one(self, x):
        return self.fn(np.asarray(x))


def _two_regime_corpus(n_points=12):
    """Points split by feature[0] with a wide margin (so any holdout split
    generalises): low regime -> spec4 best, high regime -> spec0 best."""
    c = Corpus()
    for i in range(n_points):
        low = i < n_points // 2
        f = np.full(7, float(i if low else 100 + i))
        best, worst = ("spec4", "spec0") if low else ("spec0", "spec4")
        c.append("layer/attn", f, best, 1000.0)
        c.append("layer/attn", f, worst, 100.0)
    return c


def test_trainer_interval_and_novelty_triggers():
    t = OnlineTrainer(interval=10)
    c = Corpus()
    assert not t.should_retrain(c)            # empty corpus: nothing to fit
    c.append("r", _F, "spec2", 1.0)
    assert t.should_retrain(c)                # cold start: any class is novel
    assert t.maybe_retrain(c) is not None
    for i in range(9):
        c.append("r", _F + i, "spec2", 1.0)
    assert not t.should_retrain(c)            # under interval, no new class
    c.append("r", _F + 9, "spec2", 1.0)
    assert t.should_retrain(c)                # interval reached
    assert t.maybe_retrain(c) is not None
    c.append("r", _F, "spec4", 2.0)           # one obs, but a NOVEL class
    assert t.should_retrain(c)
    assert t.maybe_retrain(c) is not None
    assert not t.should_retrain(c)            # nothing new since


def test_trainer_cold_start_swaps_first_tree_in():
    t = OnlineTrainer(interval=1)
    c = Corpus()
    c.append("layer/attn", _F, "spec2", 10.0)
    tree = t.maybe_retrain(c, current_tree=None)
    assert tree is not None and tree.predict_one(_F) == "spec2"
    assert t.retrain_count == 1 and t.reject_count == 0


def test_trainer_never_swaps_in_a_worse_tree():
    """Holdout regret gate: against an oracle incumbent, a candidate
    crippled to a single leaf (majority vote) must be rejected; a full
    candidate (at least as good) must be accepted."""
    c = _two_regime_corpus()
    oracle = _FixedTree(lambda x: "spec4" if x[0] < 50 else "spec0")

    crippled = OnlineTrainer(interval=1, tree_kw={"max_depth": 0})
    assert crippled.maybe_retrain(c, current_tree=oracle) is None
    assert crippled.reject_count == 1

    full = OnlineTrainer(interval=1, tree_kw={"max_depth": 4})
    tree = full.maybe_retrain(c, current_tree=oracle)
    assert tree is not None and full.reject_count == 0
    assert tree.predict_one(np.full(7, 0.0)) == "spec4"
    assert tree.predict_one(np.full(7, 110.0)) == "spec0"


def test_holdout_value_scores_predictions_by_observed_reward():
    groups = Corpus()
    groups.append("r", _F, "good", 100.0)
    groups.append("r", _F, "bad", 10.0)
    g = groups.groups()
    assert holdout_value(_FixedTree(lambda x: "good"), g) == 100.0
    assert holdout_value(_FixedTree(lambda x: "bad"), g) == 10.0
    # predicting a class never measured there is scored pessimistically
    assert holdout_value(_FixedTree(lambda x: "unseen"), g) == 10.0


# ---------------------------------------------------------------------------
# EpsilonGreedyExplorer
# ---------------------------------------------------------------------------


def test_explorer_eps_zero_is_a_guaranteed_noop():
    ex = EpsilonGreedyExplorer(eps=0.0, budget=100)
    assert not ex.active
    assert all(ex.maybe_explore(null_plan()) is None for _ in range(50))
    assert ex.explored == 0


def test_explorer_budget_caps_exploration():
    ex = EpsilonGreedyExplorer(eps=1.0, budget=3, seed=0)
    picks = [ex.maybe_explore(null_plan(), region="layer/attn")
             for _ in range(10)]
    taken = [p for p in picks if p is not None]
    assert len(taken) == 3 and ex.explored == 3 and not ex.active
    for cls, plan in taken:
        # the explored candidate's knob is actually set on the plan copy
        rc = plan.config_for("layer3/attn")
        if cls.startswith("spec"):
            assert rc.spec_depth == int(cls[-1])
        elif cls == "mem_full":
            assert rc.reservation == "full"
        elif cls.startswith("mem_prefix"):
            assert rc.prefix_cache == cls.rsplit("_", 1)[-1]
        elif cls.startswith("tp"):
            assert rc.tp_degree == int(cls[2:])
        else:
            assert cls.startswith("mem_lazy") and rc.reservation == "lazy"


def test_explorer_menu_is_the_serve_only_classes():
    from repro.autotune.candidates import explore_menu
    assert {c.name for c in explore_menu()} == {
        "spec0", "spec2", "spec4",
        "mem_full", "mem_lazy", "mem_lazy_wm10", "mem_lazy_wm30",
        "mem_prefix_on", "mem_prefix_off",
        "tp1", "tp2", "tp4",
        "scan_chunk", "scan_fused", "scan_chunk_ssd", "scan_fused_ssd"}
    assert all(c.serve_only for c in explore_menu())
    # the watermark variants carry their fraction on the config
    wm = {c.name: c.config.mem_watermark for c in explore_menu()
          if c.name.startswith("mem_lazy_wm")}
    assert wm == {"mem_lazy_wm10": 0.10, "mem_lazy_wm30": 0.30}


# ---------------------------------------------------------------------------
# Engine integration: hot-swap latch regression + online-loop bit-identity
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.models.model import build
    cfg = get_config("stablelm-1.6b").reduced()
    model = build(cfg)
    # f32 params: greedy-argmax equality across step shapes is exact in f32
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, model, params


def _trace(cfg, n=4, gen=8, plen=6):
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(3)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, plen).astype(
                        np.int32),
                    max_new_tokens=gen, arrival_s=0.0) for i in range(n)]


def test_hot_swapped_dtree_takes_effect_within_the_current_bucket(tiny_model):
    """Regression: swapping the decider's tree must invalidate the engine's
    load-bucket replan latch — without the version bump, a new tree would
    silently never take effect until the next occupancy-bucket change."""
    from repro.serve.engine import Engine, ServeConfig
    cfg, model, params = tiny_model
    eng = Engine(model, params,
                 serve_cfg=ServeConfig(max_len=32, max_slots=2, page_size=8),
                 dtree=_FixedTree(lambda x: "spec2"))
    eng._ensure_pool()
    eng._maybe_replan(2)
    assert eng._spec_depth == 2
    n_log = len(eng.decisions_log)

    # same bucket, same tree: the latch holds (no re-decision)
    eng._maybe_replan(2)
    assert len(eng.decisions_log) == n_log

    # hot-swap mid-bucket: the very next replan check must re-decide
    eng.dtree = _FixedTree(lambda x: "spec4")
    eng._maybe_replan(2)
    assert eng._spec_depth == 4, "swapped tree never took effect"
    assert len(eng.decisions_log) == n_log + 1
    # and the step executable actually changed with it
    assert dict(eng.decisions_log[-1][1])["layer/attn"] == "spec4"


def test_online_retrain_keeps_greedy_output_bit_identical(tiny_model):
    """With exploration OFF, the online loop (tap -> corpus -> retrain ->
    swap) must not change a single greedy token vs the plain engine."""
    from repro.serve.engine import Engine, ServeConfig
    cfg, model, params = tiny_model
    plain = Engine(model, params, serve_cfg=ServeConfig(
        max_len=32, max_slots=2, page_size=8, spec_depth=0))
    online = Engine(model, params, serve_cfg=ServeConfig(
        max_len=32, max_slots=2, page_size=8, spec_depth=-1,
        online_retrain=True, retrain_interval=3, explore_eps=0.0))
    reqs_a = _trace(cfg)
    reqs_b = _trace(cfg)
    plain.serve(reqs_a)
    res = online.serve(reqs_b)
    for a, b in zip(reqs_a, reqs_b):
        assert a.out_tokens == b.out_tokens, \
            f"online retrain changed request {a.rid}'s greedy tokens"
    # the loop genuinely ran: observations flowed, a tree was trained in
    at = res["autotune"]
    assert at["corpus_entries"] >= 1
    assert at["retrains"] >= 1 and at["swaps"] >= 1
    assert online.dtree is not None
    assert at["explore_fraction"] == 0.0
    # autotune_reset restarts the learning loop cold (fresh corpus/stats,
    # supplied incumbent) while compiled steps stay cached
    n_steps = len(online._pool_steps)
    online.autotune_reset(tree=None)
    assert len(online.corpus) == 0 and online.dtree is None
    assert online.autotune_stats["retrains"] == 0
    assert len(online._pool_steps) == n_steps


def test_mid_window_class_change_flushes_old_attribution(tiny_model):
    """Regression: when a bucket's class changes mid-flush-window (tree
    swap / exploration), the steps accumulated under the OLD class must be
    flushed to the corpus under that class — not silently re-credited to
    the new one at the next flush."""
    from repro.serve.engine import Engine, ServeConfig
    cfg, model, params = tiny_model
    # spec_depth pinned to 0 so replans never change the executable (no
    # recompiles in this test) — the class decision is still recorded
    eng = Engine(model, params, serve_cfg=ServeConfig(
        max_len=32, max_slots=2, page_size=8, spec_depth=0,
        online_retrain=True, retrain_interval=100, explore_eps=0.0),
        dtree=_FixedTree(lambda x: "spec2"))
    eng._ensure_pool()
    eng._maybe_replan(2)                       # bucket 2 decided: spec2
    assert eng._bucket_class[2] == "spec2"
    eng._tap_step(2, 8, 0.01)                  # a window under spec2
    eng._tap_step(2, 8, 0.01)
    eng.dtree = _FixedTree(lambda x: "spec4")  # swap changes the class...
    eng._maybe_replan(2)                       # ...mid-bucket, mid-window
    assert eng._bucket_class[2] == "spec4"
    spec2 = [e for e in eng.corpus.entries() if e.chosen_class == "spec2"]
    assert spec2 and spec2[0].rewarded, \
        "old-class window lost (or re-credited to the new class)"
    assert np.isclose(spec2[0].reward, 16 / 0.02)
    assert not any(e.chosen_class == "spec4" for e in eng.corpus.entries())
    assert 2 not in eng._tap_acc               # window consumed, not doubled


def test_serve_reports_autotune_summary_even_when_off(tiny_model):
    from repro.serve.engine import Engine, ServeConfig
    cfg, model, params = tiny_model
    eng = Engine(model, params, serve_cfg=ServeConfig(
        max_len=32, max_slots=2, page_size=8, spec_depth=0))
    res = eng.serve(_trace(cfg, n=2, gen=4))
    assert res["autotune"]["retrains"] == 0
    assert res["autotune"]["swaps"] == 0
