"""Sharding trees for step functions: params, optimizer state, batches and
serving caches, derived from logical axes + a RegionPlan (legality enforced
by ``policy.legal_spec``)."""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.policy import RegionPlan, legal_spec
from repro.models.model import Model

BATCH_AXES = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "frames": ("batch", "enc_seq", "embed"),
    "vision_embeds": ("batch", None, "embed"),
}

# serving-cache leaf axes, inferred by leaf key (caches are per-layer dict
# entries, NOT layer-stacked: functional replacement of each layer's leaf
# aliases in place under buffer donation, where a stacked cache forces
# dynamic-update-slice copy chains)
CACHE_AXES = {
    "k": ("batch", "kv_seq", "kv_heads", "head_dim"),
    "v": ("batch", "kv_seq", "kv_heads", "head_dim"),
    # paged pools: (n_pages, page_size, kv_heads, head_dim), or lane-dense
    # (n_pages, page_size, kv_heads * head_dim), whose last axis takes the
    # "kv_heads" entry: split into tp contiguous runs of whole heads (the
    # engine's tp divides kv_heads).  Page id and in-page position stay
    # replicated — the host-side block table indexes them on every shard —
    # so tensor parallelism splits only the heads: each shard holds
    # kv_heads/tp heads of EVERY page.
    "k_pages": (None, None, "kv_heads", "head_dim"),
    "v_pages": (None, None, "kv_heads", "head_dim"),
    "s": ("batch", "ssm_heads", None, None),
    "x_prev": ("batch", "embed"),
    "conv_x": ("batch", None, "ssm_dim"),
    "conv_bc": ("batch", None, None),
    "enc_out": ("batch", "enc_seq", "embed"),
    "pos": (),
}


def _sh(plan: RegionPlan, shape, axes) -> NamedSharding:
    axes = tuple(axes)[: len(shape)] + (None,) * (len(shape) - len(axes))
    return NamedSharding(plan.mesh, legal_spec(shape, axes, plan.rules,
                                               plan.mesh))


def param_shardings(model: Model, plan: RegionPlan) -> Any:
    specs = model.abstract_params()
    axes = model.logical_axes()
    return jax.tree.map(lambda s, a: _sh(plan, s.shape, a), specs, axes)


def _zero1(plan: RegionPlan, shape, spec: P) -> NamedSharding:
    """ZeRO-1: additionally split moments over the data axis on the first
    dim that is still replicated and divisible — optimizer state memory
    drops ~data-fold; XLA turns the gradient all-reduce into
    reduce-scatter + sharded update + all-gather of params."""
    mesh = plan.mesh
    if "data" not in mesh.shape:
        return NamedSharding(mesh, spec)
    used = {a for e in spec if e for a in ((e,) if isinstance(e, str) else e)}
    if "data" in used:
        return NamedSharding(mesh, spec)
    n = mesh.shape["data"]
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (dim, e) in enumerate(zip(shape, entries)):
        if e is None and dim % n == 0 and dim >= n:
            entries[i] = "data"
            return NamedSharding(mesh, P(*entries))
    return NamedSharding(mesh, spec)


def opt_state_shardings(model: Model, plan: RegionPlan, zero1: bool = True) -> Any:
    """AdamW moments inherit parameter shardings (+ ZeRO-1 data split)."""
    ps = param_shardings(model, plan)
    if not zero1:
        ms = ps
    else:
        specs = model.abstract_params()
        ms = jax.tree.map(
            lambda s, sh: _zero1(plan, s.shape, sh.spec), specs, ps)
    return {"step": NamedSharding(plan.mesh, P()), "mu": ms, "nu": ms}


def batch_shardings(plan: RegionPlan, batch_specs: dict) -> dict:
    return {k: _sh(plan, v.shape, BATCH_AXES.get(k, ("batch",)))
            for k, v in batch_specs.items()}


def _cache_leaf_axes(path) -> tuple:
    key = None
    for p in reversed(path):
        k = getattr(p, "key", None)
        if isinstance(k, str):
            key = k
            break
    return CACHE_AXES.get(key, ())


def cache_shardings(plan: RegionPlan, cache_spec: Any) -> Any:
    flat = jax.tree_util.tree_flatten_with_path(cache_spec)[0]
    treedef = jax.tree.structure(cache_spec)
    out = []
    for path, leaf in flat:
        axes = _cache_leaf_axes(path)
        axes = tuple(axes)[: len(leaf.shape)] + (None,) * (len(leaf.shape) - len(axes))
        out.append(_sh(plan, leaf.shape, axes))
    return jax.tree.unflatten(treedef, out)


def logits_sharding(plan: RegionPlan, shape) -> NamedSharding:
    return _sh(plan, shape, ("batch", "seq", "vocab"))
