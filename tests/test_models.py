"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
output shapes + finiteness; prefill+decode consistency against the full
forward (the serving-correctness invariant)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_batch
from repro.configs.registry import ARCH_IDS, get_config
from repro.models.layers import STACK_AXES
from repro.models.model import build, count_params
from repro.optim import adamw
from repro.train import trainer

ALL = list(ARCH_IDS)


@pytest.mark.parametrize("arch", ALL)
def test_forward_smoke(arch, key):
    cfg = get_config(arch).reduced()
    model = build(cfg)
    params = model.init(key)
    batch = tiny_batch(cfg, key)
    logits, aux = model.forward(params, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ["qwen3-8b", "granite-moe-1b-a400m",
                                  "rwkv6-3b", "zamba2-2.7b",
                                  "whisper-large-v3"])
def test_train_step_smoke(arch, key):
    cfg = get_config(arch).reduced()
    model = build(cfg)
    params = model.init(key)
    opt = adamw.init_state(params)
    step = jax.jit(trainer.make_train_step(model, unroll=False))
    batch = tiny_batch(cfg, key)
    params2, opt2, metrics = step(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(opt2["step"]) == 1
    # params actually moved
    delta = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        params, params2))
    assert max(delta) > 0


@pytest.mark.parametrize("arch", ALL)
def test_prefill_decode_consistency(arch, key):
    """Greedy decode after prefill must match teacher-forced forward."""
    import dataclasses
    cfg = get_config(arch).reduced()
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no drops
    model = build(cfg)
    params = model.init(key, dtype=jnp.float32)
    B, S, extra = 2, 16, 4
    batch = tiny_batch(cfg, key, batch=B, seq=S + extra)
    full_batch = dict(batch)
    prompt = {k: (v[:, :S] if k in ("tokens", "labels") else v)
              for k, v in batch.items()}
    prompt.pop("labels", None)

    logits_full, _ = model.forward(params, {k: v for k, v in full_batch.items()
                                            if k != "labels"})
    logits_pre, cache = model.prefill(params, prompt, max_len=S + extra + 1)
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, -1], np.float32),
        np.asarray(logits_full[:, S - 1], np.float32), rtol=2e-2, atol=2e-2)

    # teacher-forced decode along the true continuation
    for t in range(extra):
        tok = full_batch["tokens"][:, S + t][:, None]
        logits_dec, cache = model.decode(params, cache, tok)
        np.testing.assert_allclose(
            np.asarray(logits_dec[:, 0], np.float32),
            np.asarray(logits_full[:, S + t], np.float32),
            rtol=2e-2, atol=2e-2)


def test_param_counts_match_published():
    """Full configs land near their published parameter counts."""
    expected = {
        "qwen3-8b": (8.0e9, 8.4e9),
        "qwen3-32b": (32e9, 33.5e9),
        "stablelm-1.6b": (1.5e9, 1.8e9),
        "stablelm-3b-4e1t": (2.7e9, 2.9e9),    # published 2.795e9
        "h2o-danube-1.8b": (1.7e9, 1.9e9),
        "rwkv6-3b": (2.9e9, 3.2e9),
        "whisper-large-v3": (1.5e9, 1.7e9),
        "internvl2-26b": (19e9, 21e9),   # LM backbone only (InternLM2-20B)
        "zamba2-2.7b": (2.2e9, 2.9e9),
        "granite-moe-1b-a400m": (1.2e9, 1.4e9),
    }
    for arch, (lo, hi) in expected.items():
        n = count_params(get_config(arch))
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9},{hi/1e9}]"


def test_swa_ring_cache_matches_full(key):
    """SWA ring cache decode == full-cache decode (h2o-danube invariant)."""
    import dataclasses
    cfg = dataclasses.replace(get_config("h2o-danube-1.8b").reduced(),
                              swa_window=8)
    model = build(cfg)
    params = model.init(key, dtype=jnp.float32)
    B, S, extra = 1, 24, 6  # S > window: ring wraps
    batch = tiny_batch(cfg, key, batch=B, seq=S + extra)
    logits_full, _ = model.forward(params, {"tokens": batch["tokens"]})
    _, cache = model.prefill(params, {"tokens": batch["tokens"][:, :S]},
                             max_len=S + extra + 1)
    assert cache["layers"]["l0"]["k"].shape[1] == cfg.swa_window
    for t in range(extra):
        tok = batch["tokens"][:, S + t][:, None]
        logits_dec, cache = model.decode(params, cache, tok)
        np.testing.assert_allclose(
            np.asarray(logits_dec[:, 0], np.float32),
            np.asarray(logits_full[:, S + t], np.float32),
            rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch,path,fan_in", [
    ("stablelm-1.6b", ("blocks", "mlp", "up"), 2048),
    ("stablelm-1.6b", ("blocks", "mlp", "down"), 5632),
    ("stablelm-1.6b", ("blocks", "attn", "wq"), 2048),
    ("granite-moe-1b-a400m", ("blocks", "mlp", "up"), 1024),
])
def test_stacked_init_std_is_inverse_sqrt_fan_in(arch, path, fan_in, key):
    """A full-width layer-stacked (and expert-stacked) leaf holds
    independent matrices: its init std is 1/sqrt(d_in), not 1/sqrt of the
    layer or expert count.  The stacking axes are cut to 2 to keep the
    sample small; the std does not depend on them."""
    spec = build(get_config(arch)).spec()
    for k in path:
        spec = spec[k]
    assert spec.axes[0] == "layers"
    cut = tuple(2 if a in STACK_AXES else n
                for n, a in zip(spec.shape, spec.axes))
    w = np.asarray(dataclasses.replace(spec, shape=cut).materialise(
        key, jnp.float32))
    assert abs(w.std() * math.sqrt(fan_in) - 1.0) < 0.02
