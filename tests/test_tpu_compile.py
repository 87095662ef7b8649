"""Compiles for a described TPU v5e (``v5e:2x2``), no chip attached: the
Pallas paged-attention kernel at stablelm-1.6b widths and the full-width
paged decode step, checked for the kernel call, the one-chip memory fit and
the HLO counters the serve-time decider reads.

The topology is described inside a module fixture, never at import: only one
process may load the TPU compiler library, and every test worker imports
this file.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core import counters
from repro.core.policy import null_plan
from repro.kernels.paged_attention import paged_attention_mq
from repro.models.model import build
from repro.serve.engine import paged_step_fn

V5E_HBM_BYTES = 16 * 10**9
B, PAGE, MAX_PAGES = 4, 16, 32          # 4 slots of 512 tokens each


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def stablelm():
    return build(get_config("stablelm-1.6b"))


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def full_step(stablelm, one_chip):
    """The engine's paged decode+sample step at full width, compiled for
    one v5e chip under the default (gather) plan."""
    n_pages = B * MAX_PAGES + 1
    params = _abstract(stablelm.abstract_params(), one_chip)
    pages = _abstract(stablelm.paged_cache_spec(n_pages, PAGE,
                                                dtype=jnp.bfloat16), one_chip)
    small = _abstract([jax.ShapeDtypeStruct((B, 1), jnp.int32),
                       jax.ShapeDtypeStruct((B, MAX_PAGES), jnp.int32),
                       jax.ShapeDtypeStruct((B,), jnp.int32),
                       jax.ShapeDtypeStruct((B,), jnp.bool_),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)], one_chip)
    step = paged_step_fn(stablelm, null_plan(), 0.0)
    return jax.jit(step, donate_argnums=(1,)).lower(
        params, pages, *small).compile()


@pytest.mark.parametrize("s_len", [1, 3])
def test_paged_kernel_compiles_for_v5e(s_len, stablelm, one_chip):
    """Plain decode (S=1) and the spec2 verify width (S=3)."""
    cfg = stablelm.cfg
    kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    hd = cfg.head_dim
    n_pages = B * MAX_PAGES + 1
    args = _abstract([
        jax.ShapeDtypeStruct((B, s_len, kvh, g, hd), jnp.bfloat16),
        jax.ShapeDtypeStruct((n_pages, PAGE, kvh, hd), jnp.bfloat16),
        jax.ShapeDtypeStruct((n_pages, PAGE, kvh, hd), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, MAX_PAGES), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32)], one_chip)
    compiled = paged_attention_mq.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_paged_step_fits_one_v5e(full_step):
    m = full_step.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 0 < need < V5E_HBM_BYTES, m


def test_counters_parse_tpu_step(full_step):
    """The decider's features come from this parse: on TPU HLO it must see
    the step's matmuls, within 2x of XLA's own flop count."""
    rc = counters.collect(full_step)
    assert rc.xla_flops > 0
    assert 0.5 <= rc.total.flops / rc.xla_flops <= 2.0, (
        rc.total.flops, rc.xla_flops)
    hot = dict(rc.top_regions("flops", 4))
    assert "logits" in hot and any("mlp" in r for r in hot), hot
