"""Shared building blocks for the model zoo.

Parameters are declared once as :class:`Spec` trees (shape + logical axes +
initializer); ``init_params`` materialises them, ``logical_axes`` extracts the
sharding metadata, so parameter shape and sharding have a single source of
truth.  Every module's ``apply`` is wrapped in an instrumented region
(:mod:`repro.core.regions`) and applies the plan's activation sharding
constraints at region boundaries.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.policy import RegionPlan
from repro.core.regions import region

# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


# logical axes that stack independent matrices (layer-stacked blocks, MoE
# experts); they never count toward a weight's fan-in
STACK_AXES = ("layers", "experts")


@dataclasses.dataclass(frozen=True)
class Spec:
    shape: tuple
    axes: tuple          # logical axis names (same length as shape)
    init: str = "normal"  # 'normal' | 'zeros' | 'ones' | 'small'
    scale: float = 1.0

    def materialise(self, key, dtype) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        # fan-in is the first axis that is not a stacking axis: a stacked
        # (n_layers, d_in, d_out) leaf holds n_layers independent matrices
        fan_in = next((n for n, a in zip(self.shape, self.axes)
                       if a not in STACK_AXES), 1)
        std = self.scale / math.sqrt(max(fan_in, 1))
        if self.init == "small":
            std = 0.02 * self.scale
        return (jax.random.normal(key, self.shape) * std).astype(dtype)


def init_params(spec_tree: Any, key, dtype=jnp.bfloat16) -> Any:
    leaves, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, Spec))
    keys = jax.random.split(key, max(len(leaves), 1))
    vals = [s.materialise(k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(spec_tree: Any, dtype=jnp.bfloat16) -> Any:
    """ShapeDtypeStruct tree (dry-run: no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        spec_tree, is_leaf=lambda x: isinstance(x, Spec))


def logical_axes(spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: s.axes, spec_tree,
                        is_leaf=lambda x: isinstance(x, Spec))


def spec_param_count(spec_tree: Any) -> int:
    total = 0
    for s in jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, Spec)):
        total += math.prod(s.shape)
    return total


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------


def norm_spec(cfg, dim: Optional[int] = None) -> Any:
    d = dim or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": Spec((d,), (None,), "ones"),
                "bias": Spec((d,), (None,), "zeros")}
    return {"scale": Spec((d,), (None,), "ones")}


def apply_norm(cfg, p, x, eps: float = 1e-5) -> jax.Array:
    """Reductions in f32, streams in the input dtype (bf16 residual tensors
    never round-trip through f32 HBM traffic)."""
    if "bias" in p:  # layernorm
        mu = jnp.mean(x.astype(jnp.float32), -1, keepdims=True)
        var = jnp.var(x.astype(jnp.float32), -1, keepdims=True)
        inv = jax.lax.rsqrt(var + eps)
        out = ((x - mu.astype(x.dtype))
               * inv.astype(x.dtype) * p["scale"] + p["bias"])
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        out = x * jax.lax.rsqrt(ms + eps).astype(x.dtype) * p["scale"]
    return out.astype(x.dtype)


def activation(cfg, x: jax.Array) -> jax.Array:
    if cfg.act == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(cfg, head_dim: int) -> jax.Array:
    rot = int(head_dim * cfg.partial_rotary)
    rot -= rot % 2
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, jnp.float32) / rot))


def apply_rope(cfg, x: jax.Array, positions: jax.Array) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    if not cfg.use_rope:
        return x
    head_dim = x.shape[-1]
    rot = int(head_dim * cfg.partial_rotary)
    rot -= rot % 2
    if rot == 0:
        return x
    freqs = rope_frequencies(cfg, head_dim)                     # (rot/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, rot/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.concatenate(
        [o1.astype(x.dtype), o2.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# MLP (gated or plain)
# ---------------------------------------------------------------------------


def mlp_spec(cfg, d_ff: Optional[int] = None) -> Any:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    p = {"up": Spec((d, f), ("embed", "ff")),
         "down": Spec((f, d), ("ff", "embed"))}
    if cfg.glu:
        p["gate"] = Spec((d, f), ("embed", "ff"))
    return p


def apply_mlp(cfg, p, x, plan: RegionPlan, name: str = "mlp") -> jax.Array:
    with region(name) as rpath:
        h = jnp.einsum("...d,df->...f", x, p["up"])
        if cfg.glu:
            g = jnp.einsum("...d,df->...f", x, p["gate"])
            h = activation(cfg, g) * h
        else:
            h = activation(cfg, h)
        h = plan.constrain(h, rpath, ("batch", "seq", "ff"))
        out = jnp.einsum("...f,fd->...d", h, p["down"])
        return plan.constrain(out, rpath, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_spec(cfg) -> Any:
    p = {"tokens": Spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), "small")}
    if not cfg.tie_embeddings:
        p["unembed"] = Spec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    return p


def apply_embed(cfg, p, tokens, plan: RegionPlan) -> jax.Array:
    with region("embed") as rpath:
        x = jnp.take(p["tokens"], tokens, axis=0)
        return plan.constrain(x, rpath, ("batch", "seq", "embed"))


def apply_unembed(cfg, p, x, plan: RegionPlan) -> jax.Array:
    with region("logits") as rpath:
        w = p["tokens"].T if cfg.tie_embeddings else p["unembed"]
        logits = jnp.einsum("...d,dv->...v", x, w)
        return plan.constrain(logits, rpath, ("batch", "seq", "vocab"))
