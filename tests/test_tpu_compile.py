"""Compiles for a described TPU v5e (``v5e:2x2``), no chip attached: the
Pallas paged-attention kernel at stablelm head counts and 64-, 80- and
128-wide heads, the full-width paged decode step, the qwen3-8b-h18 and
stablelm-3b-4e1t pool decode steps on the kernel path, and a reduced
model's step on the gather its narrow page rows resolve to, checked for
the kernel call, its ``R.attn`` scope, the pool's bytes, the one-chip
memory fit and the HLO counters the serve-time decider reads.

The topology is described inside a module fixture, never at import: only one
process may load the TPU compiler library, and every test worker imports
this file.  Keep these tests in this one file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core import counters
from repro.core.policy import RegionConfig, RegionPlan, null_plan
from repro.kernels.paged_attention import (KERNEL_NAME, page_row,
                                           paged_attention_mq)
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
    one v5e chip under the null plan (the model's gather path)."""
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


@pytest.mark.parametrize("hd", [64, 80, 128])
@pytest.mark.parametrize("s_len", [1, 3])
def test_paged_kernel_compiles_for_v5e(s_len, hd, stablelm, one_chip):
    """Plain decode (S=1) and the spec2 verify width (S=3) at stablelm's
    head counts (32 heads, MHA): 64- and 80-wide heads (stablelm-1.6b,
    stablelm-3b-4e1t) on the pool's lane-dense rows and 128-wide heads on
    rows per kv head all compile to the named kernel call."""
    cfg = stablelm.cfg
    kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    n_pages = B * MAX_PAGES + 1
    pages = (n_pages, PAGE) + page_row(kvh, hd)
    args = _abstract([
        jax.ShapeDtypeStruct((B, s_len, kvh, g, hd), jnp.bfloat16),
        jax.ShapeDtypeStruct(pages, jnp.bfloat16),
        jax.ShapeDtypeStruct(pages, jnp.bfloat16),
        jax.ShapeDtypeStruct((B, MAX_PAGES), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32)], one_chip)
    text = paged_attention_mq.lower(*args, interpret=False).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and KERNEL_NAME in calls[0]


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


def _pool_step(model, plan, sharding, slots, n_pages, page, max_pages):
    """The engine's pool decode step for ``slots`` slots over ``n_pages``
    pages of ``page`` tokens, compiled for the described chip."""
    params = _abstract(model.abstract_params(), sharding)
    pages = _abstract(model.paged_cache_spec(n_pages, page,
                                             dtype=jnp.bfloat16), sharding)
    small = _abstract([jax.ShapeDtypeStruct((slots, 1), jnp.int32),
                       jax.ShapeDtypeStruct((slots, max_pages), jnp.int32),
                       jax.ShapeDtypeStruct((slots,), jnp.int32),
                       jax.ShapeDtypeStruct((slots,), jnp.bool_),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)], sharding)
    step = paged_step_fn(model, plan, 0.0)
    return jax.jit(step, donate_argnums=(1,)).lower(
        params, pages, *small).compile()


def test_qwen3_h18_pool_step_on_the_kernel_path(one_chip, monkeypatch):
    """The benchmark's pool decode step (qwen3-8b widths, 18 layers, 32
    slots, 3,000 pages of 16, max_len 2,560) with ``attn_impl='paged'``:
    it fits one v5e, holds the Pallas kernel under the ``R.attn`` scope
    the benchmark's trace reduction attributes it by, and needs fewer
    temporaries than the gather path, whose dense copy of every slot's
    160 pages it no longer makes."""
    from repro.kernels import ops
    # the backend is the CPU here: steer the kernel to its compiled form
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = build(dataclasses.replace(get_config("qwen3-8b"), n_layers=18))
    assert model.paged_cache_spec(1, 16)["layers"]["l0"]["k_pages"].shape \
        == (1, 16, 8, 128), "128-wide heads keep a row per kv head"
    args = (one_chip, 32, 3000, 16, 2560 // 16)
    paged = _pool_step(model, RegionPlan(region_configs={
        "layer/attn": RegionConfig(attn_impl="paged")}), *args)
    gather = _pool_step(model, RegionPlan(region_configs={
        "layer/attn": RegionConfig(attn_impl="gather")}), *args)
    m, mg = paged.memory_analysis(), gather.memory_analysis()
    assert 0 < _need(m) < V5E_HBM_BYTES, m
    calls = _kernel_calls(paged, 18)
    # the kernel form of whole-tile heads: q stacked (query, kv head,
    # group) rows of 128 lanes, 32 of them a slot
    assert all("bf16[32,32,128]" in line.split("custom-call(")[0]
               for line in calls), calls[0][:200]
    assert m.temp_size_in_bytes < mg.temp_size_in_bytes, (
        m.temp_size_in_bytes, mg.temp_size_in_bytes)
    print(f"[qwen3-8b-h18 pool step] temporaries: kernel "
          f"{m.temp_size_in_bytes} B, gather {mg.temp_size_in_bytes} B")


def _need(m) -> int:
    """Device bytes a compiled step needs: arguments and outputs that do
    not alias them, and temporaries."""
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _kernel_calls(compiled, layers: int) -> list:
    """The step's kernel calls: one a layer, each named and under the
    ``R.attn`` scope the benchmark's trace reduction attributes it by."""
    from bench.devtrace import hlo_scopes, instruction_name, scope_of
    text = compiled.as_text()
    scopes = hlo_scopes(text)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [instruction_name(line) for line in calls]
    assert len(calls) == layers, f"{len(calls)} kernel calls, one per layer"
    assert all(n.startswith(KERNEL_NAME) for n in names), names
    assert all(scope_of(scopes[n]) == "attn" for n in names), {
        n: scopes[n] for n in names}
    return calls


def test_stablelm_3b_pool_step_on_the_kernel_path(one_chip, monkeypatch):
    """The stablelm-3b-4e1t cell's pool decode step (published widths, 32
    layers, MHA with 80-wide heads, 32 slots, 1,400 pages of 16, max_len
    2,560), resolved as the engine resolves it on a TPU: it fits one v5e,
    holds the named kernel under ``R.attn`` in every layer, and its
    arguments are the weights and the logical K/V bytes of the pool — the
    lane-dense rows carry no padding (a row per kv head would pad 80 lanes
    to 128, 1.6x)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("stablelm-3b-4e1t")
    model = build(cfg)
    n_pages = 1400
    step = _pool_step(model, RegionPlan(region_configs={
        "layer/attn": RegionConfig(attn_impl="paged")}), one_chip, 32,
        n_pages, 16, 2560 // 16)
    m = step.memory_analysis()
    assert 0 < _need(m) < V5E_HBM_BYTES, m
    _kernel_calls(step, cfg.n_layers)
    weights = 2 * cfg.param_count()
    kv = n_pages * 16 * 2 * cfg.n_kv_heads * cfg.head_dim * 2 * cfg.n_layers
    small = m.argument_size_in_bytes - weights - kv   # tokens, tables, key
    assert 0 <= small < 2**20, (m.argument_size_in_bytes, weights, kv)
    print(f"[stablelm-3b-4e1t pool step] arguments "
          f"{m.argument_size_in_bytes} B (pool {kv} B), temporaries "
          f"{m.temp_size_in_bytes} B")


def test_narrow_rows_resolve_the_gather_for_a_v5e(one_chip, monkeypatch):
    """The reduced stablelm's page rows (2 kv heads of 16: 32 lanes) are not
    whole 128-lane tiles, which the compiled kernel cannot copy.  On a TPU
    the engine resolves them to the gather, over a 'paged' pin too, and
    the step it lowers for that plan compiles for a v5e with no kernel
    call; the kernel itself is refused before Mosaic sees it."""
    import jax.random as jr
    from repro.serve.engine import Engine, ServeConfig
    model = build(get_config("stablelm-1.6b").reduced())
    cfg = model.cfg
    assert cfg.n_kv_heads * cfg.resolved_head_dim % 128
    pinned = RegionPlan(region_configs={
        "layer/attn": RegionConfig(attn_impl="paged")})
    eng = Engine(model, model.init(jr.PRNGKey(0)), plan=pinned,
                 serve_cfg=ServeConfig(paged="on", max_len=64, max_slots=2,
                                       page_size=16))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [None])
    assert eng._kernel_refuses_rows()
    assert eng.attn_impl_for(pinned) == eng.attn_impl_for(null_plan()) \
        == "gather"
    lowered = Engine._plan_with_attn_impl(pinned, "gather", override=True)
    step = _pool_step(model, lowered, one_chip, 2, 9, 16, 4)
    assert "tpu_custom_call" not in step.as_text()
    assert 0 < _need(step.memory_analysis()) < V5E_HBM_BYTES
    q = jax.ShapeDtypeStruct((2, 1, cfg.n_kv_heads, 1, 16), jnp.bfloat16)
    pages = jax.ShapeDtypeStruct((9, 16) + page_row(cfg.n_kv_heads, 16),
                                 jnp.bfloat16)
    with pytest.raises(ValueError, match="128-lane"):
        paged_attention_mq.lower(
            q, pages, pages, jax.ShapeDtypeStruct((2, 4), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32), interpret=False)
