"""HLO census engine: exact dot flops, while-loop trip multiplication,
region attribution (fwd+bwd), collective parsing, fusion byte semantics."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import counters
from repro.core.regions import collect_regions, discover_regions, region


def test_dot_flops_exact(key):
    M, K, N = 64, 128, 32

    def f(x, w):
        with region("mm"):
            return jnp.sum(x @ w)

    x = jnp.ones((M, K))
    w = jnp.ones((K, N))
    rc = counters.collect(jax.jit(f).lower(x, w).compile())
    want = 2 * M * K * N
    assert abs(rc.regions["mm"].flops - want) / want < 0.05


def test_scan_trip_count_multiplied(key):
    L = 9

    def f(x, w):
        def body(c, _):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, None, length=L)
        return jnp.sum(y)

    x = jnp.ones((32, 64))
    w = jnp.ones((64, 64))
    rc = counters.collect(jax.jit(f).lower(x, w).compile())
    want = 2 * 32 * 64 * 64 * L
    assert abs(rc.total.flops - want) / want < 0.1
    # XLA's own analysis counts the body once — our census must exceed it
    assert rc.total.flops > rc.xla_flops * 2


def test_backward_ops_attributed_to_region(key):
    def f(w, x):
        with region("lyr"):
            return jnp.sum(jnp.tanh(x @ w))

    w = jnp.ones((64, 64))
    x = jnp.ones((32, 64))
    rc = counters.collect(jax.jit(jax.grad(f)).lower(w, x).compile())
    # fwd matmul + the w-grad matmul both attributed to the same region
    assert rc.regions["lyr"].flops >= 2 * 2 * 32 * 64 * 64 * 0.9


def test_collective_census_from_text():
    hlo = """
HloModule test

ENTRY %main (p: f32[128,256]) -> f32[128,256] {
  %p = f32[128,256]{1,0} parameter(0)
  %ag = f32[128,4096]{1,0} all-gather(%p), replica_groups=[16,16]<=[256], dimensions={1}, metadata={op_name="jit(f)/R.attn/ag"}
  %c = f32[128,256]{1,0} slice(%ag), slice={[0:128],[0:256]}
  %ar = f32[128,256]{1,0} all-reduce(%c), replica_groups=[16,16]<=[256], to_apply=%add, metadata={op_name="jit(f)/R.mlp/ar"}
  ROOT %out = f32[128,256]{1,0} add(%ar, %p)
}
"""
    rc = counters.collect_from_text(hlo)
    assert rc.collective_census == {"all-gather": 1, "all-reduce": 1}
    ag_bytes = 128 * 256 * 4
    # all-gather ring: (n-1) x shard through a link, n=16
    assert abs(rc.regions["attn"].link_bytes - ag_bytes * 15) < 1e-6
    ar_bytes = 128 * 256 * 4
    assert abs(rc.regions["mlp"].link_bytes - 2 * ar_bytes * 15 / 16) < 1.0


def test_fusion_bytes_are_boundary_only():
    hlo = """
HloModule test

%fused (a: f32[1024]) -> f32[1024] {
  %a = f32[1024]{0} parameter(0)
  %t = f32[1024]{0} tanh(%a)
  %u = f32[1024]{0} exponential(%t)
  ROOT %v = f32[1024]{0} negate(%u)
}

ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  ROOT %f = f32[1024]{0} fusion(%p), kind=kLoop, calls=%fused
}
"""
    rc = counters.collect_from_text(hlo)
    # bytes: operand + output of the fusion only (2 x 4KB); flops from body
    assert rc.total.bytes == 1024 * 4 * 2
    assert rc.total.flops == 3 * 1024


def test_region_discovery(key):
    def f(x):
        with region("a"):
            with region("b"):
                return x * 2

    regs = discover_regions(f, jnp.ones((4,)))
    assert regs == {"a", "a/b"}


def test_tpu_layouts_parse():
    """TPU HLO: tiled layouts with memory spaces inside the braces, tuple
    types that nest them, matmuls lowered to convolutions (one of them a
    windowed convolution over a padded size-1 spatial dim) and a loop bound
    whose constant carries a layout."""
    hlo = """
HloModule tpu_layouts

%body (p.1: (s32[], bf16[4,2048], bf16[2048,2048])) -> (s32[], bf16[4,2048], bf16[2048,2048]) {
  %p.1 = (s32[]{:T(128)}, bf16[4,2048]{1,0:T(4,128)(2,1)}, bf16[2048,2048]{1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%p.1), index=0
  %x = bf16[4,2048]{1,0:T(4,128)(2,1)} get-tuple-element(%p.1), index=1
  %w = bf16[2048,2048]{1,0:T(8,128)(2,1)} get-tuple-element(%p.1), index=2
  %one = s32[]{:T(128)} constant(1)
  %i2 = s32[]{:T(128)} add(%i, %one)
  %y = bf16[4,2048]{1,0:T(4,128)(2,1)S(1)} convolution(%x, %w), dim_labels=bf_io->bf, metadata={op_name="jit(f)/R.mm/dot_general"}
  ROOT %t = (s32[]{:T(128)}, bf16[4,2048]{1,0:T(4,128)(2,1)}, bf16[2048,2048]{1,0:T(8,128)(2,1)}) tuple(%i2, %y, %w)
}

%cond (p.2: (s32[], bf16[4,2048], bf16[2048,2048])) -> pred[] {
  %p.2 = (s32[]{:T(128)}, bf16[4,2048]{1,0:T(4,128)(2,1)}, bf16[2048,2048]{1,0:T(8,128)(2,1)}) parameter(0)
  %j = s32[]{:T(128)} get-tuple-element(%p.2), index=0
  %n = s32[]{:T(128)} constant(24)
  ROOT %lt = pred[]{:T(512)} compare(%j, %n), direction=LT
}

ENTRY %main (x.1: bf16[4,2048], w.1: bf16[2048,2048], wq: bf16[2048,32,64]) -> bf16[4,32,64] {
  %x.1 = bf16[4,2048]{1,0:T(4,128)(2,1)} parameter(0)
  %w.1 = bf16[2048,2048]{1,0:T(8,128)(2,1)} parameter(1)
  %wq = bf16[2048,32,64]{2,1,0:T(8,128)(2,1)} parameter(2)
  %zero = s32[]{:T(128)} constant(0)
  %init = (s32[]{:T(128)}, bf16[4,2048]{1,0:T(4,128)(2,1)}, bf16[2048,2048]{1,0:T(8,128)(2,1)}) tuple(%zero, %x.1, %w.1)
  %loop = (s32[]{:T(128)}, bf16[4,2048]{1,0:T(4,128)(2,1)}, bf16[2048,2048]{1,0:T(8,128)(2,1)}) while(%init), condition=%cond, body=%body
  %h = bf16[4,2048]{1,0:T(4,128)(2,1)} get-tuple-element(%loop), index=1
  %xr = bf16[4,2048,1]{1,0,2:T(4,128)(2,1)} reshape(%h)
  ROOT %q = bf16[4,32,64]{2,0,1:T(4,128)(2,1)S(1)} convolution(%xr, %wq), window={size=32 pad=31_31 rhs_reversal=1}, dim_labels=bf0_i0o->b0f, metadata={op_name="jit(f)/R.proj/dot_general"}
}
"""
    rc = counters.collect_from_text(hlo)
    assert rc.regions["mm"].flops == 24 * 2 * 4 * 2048 * 2048
    # 31 of the 32 window taps of every output read padding
    assert rc.regions["proj"].flops == 2 * 4 * 32 * 64 * 2048
