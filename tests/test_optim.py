"""AdamW from scratch: convergence, clipping, schedule, ZeRO shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.optim import adamw
from repro.optim.schedule import warmup_cosine


def test_adamw_converges_quadratic(key):
    target = jax.random.normal(key, (16,))
    params = {"w": jnp.zeros((16,))}
    state = adamw.init_state(params)
    cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0)

    @jax.jit
    def step(params, state):
        grads = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
        return adamw.apply_updates(cfg, params, grads, state)

    for _ in range(300):
        params, state, m = step(params, state)
    assert float(jnp.max(jnp.abs(params["w"] - target))) < 0.05


def test_grad_clipping_bounds_update(key):
    params = {"w": jnp.zeros((4,))}
    state = adamw.init_state(params)
    cfg = adamw.AdamWConfig(lr=1e-3, clip_norm=1.0, weight_decay=0)
    grads = {"w": jnp.full((4,), 1e9)}
    _, _, metrics = adamw.apply_updates(cfg, params, grads, state)
    assert float(metrics["grad_norm"]) > 1e8  # reported pre-clip


def test_weight_decay_only_on_matrices(key):
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    state = adamw.init_state(params)
    cfg = adamw.AdamWConfig(lr=0.1, weight_decay=1.0)
    grads = jax.tree.map(jnp.zeros_like, params)
    p2, _, _ = adamw.apply_updates(cfg, params, grads, state)
    assert float(jnp.abs(p2["b"] - 1.0).max()) < 1e-6   # bias untouched
    assert float(p2["w"].max()) < 1.0                    # matrix decayed


@given(step=st.integers(1, 20000))
@settings(max_examples=50, deadline=None)
def test_schedule_bounded(step):
    v = float(warmup_cosine(jnp.asarray(step), warmup=100, total=10000))
    assert 0.0 <= v <= 1.0


def test_schedule_warmup_ramps():
    vals = [float(warmup_cosine(jnp.asarray(s), warmup=100, total=10000))
            for s in (1, 50, 100)]
    assert vals[0] < vals[1] < vals[2] <= 1.0
