"""Optimized implementations must match their reference forms exactly —
the hillclimb's correctness gate (EXPERIMENTS.md §Perf)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.configs.registry import get_config
from repro.core.policy import RegionConfig, RegionPlan, null_plan
from repro.kernels import ref
from repro.models.mamba2 import ssd_chunked
from repro.models.model import build


def test_moe_einsum_matches_scatter(key):
    cfg = dataclasses.replace(get_config("granite-moe-1b-a400m").reduced(),
                              capacity_factor=8.0)
    model = build(cfg)
    params = model.init(key, dtype=jnp.float32)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                          cfg.vocab_size, dtype=jnp.int32)}
    plan_s = RegionPlan(mesh=None, region_configs={
        "moe": RegionConfig(moe_impl="scatter")})
    le, _ = model.forward(params, batch, null_plan())
    ls, _ = model.forward(params, batch, plan_s)
    np.testing.assert_allclose(np.asarray(le), np.asarray(ls),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("chunk", [8, 16, 64, 128])
def test_ssd_chunked_matches_scan(chunk, key):
    B, T, H, P, N = 2, 128, 3, 8, 16
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (B, T, H, P)) * 0.3
    b = jax.random.normal(ks[1], (B, T, N)) * 0.3
    c = jax.random.normal(ks[2], (B, T, N)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    a = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
    s0 = jax.random.normal(ks[5], (B, H, P, N)) * 0.1
    y, s = ssd_chunked(x, b, c, dt, a, s0, chunk=chunk)
    want, s_want = ref.ssd_linear_scan(x, b, c, dt, a, s0)
    # bf16 intra-chunk streams -> loose-ish tolerance
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_want),
                               rtol=1e-3, atol=1e-3)


@given(chunk=st.sampled_from([4, 8, 32]), t=st.sampled_from([32, 64]))
@settings(max_examples=8, deadline=None)
def test_ssd_chunked_property(chunk, t):
    """State passing across chunk boundaries is exact for random sizes."""
    key = jax.random.PRNGKey(chunk * 1000 + t)
    B, H, P, N = 1, 2, 4, 8
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, t, H, P)) * 0.3
    b = jax.random.normal(ks[1], (B, t, N)) * 0.3
    c = jax.random.normal(ks[2], (B, t, N)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, t, H)))
    a = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.2)
    s0 = jnp.zeros((B, H, P, N))
    _, s_chunked = ssd_chunked(x, b, c, dt, a, s0, chunk=chunk)
    _, s_ref = ref.ssd_linear_scan(x, b, c, dt, a, s0)
    np.testing.assert_allclose(np.asarray(s_chunked), np.asarray(s_ref),
                               rtol=1e-3, atol=1e-3)


def test_zamba2_forward_chunked_matches_scan(key):
    cfg = get_config("zamba2-2.7b").reduced()
    model = build(cfg)
    params = model.init(key, dtype=jnp.float32)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                          cfg.vocab_size, dtype=jnp.int32)}
    plan_c = RegionPlan(mesh=None, region_configs={
        "layer/ssm": RegionConfig(ssm_impl="chunked", chunk=16)})
    l1, _ = model.forward(params, batch, null_plan())
    l2, _ = model.forward(params, batch, plan_c)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=5e-2, atol=5e-2)
