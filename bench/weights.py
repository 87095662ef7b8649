"""Random bf16 weights from ``--seed``, in the benchmark's own layout.

The system under test and the plain reference both take their weights
from here: the program as one jitted call that builds every layer on the
device, the reference one layer at a time after the program has been
freed.  Both call :func:`layer` with the same key, so they see the same
numbers.  Values are uniform, built from random bits by exact operations
(a bit shift, an exponent OR, a subtraction, then one multiply and one
rounding to bf16), so a fused and an unfused build round alike.

Scales: matrices have std 1/sqrt(fan_in), the embedding std 1, norm
scales lie in [0.875, 1.125) and norm biases in [-0.125, 0.125).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import rng_for


def key_data(seed: int) -> np.ndarray:
    """Two uint32 words of threefry key data for ``seed``."""
    return rng_for(seed, 0).integers(0, 2**32, 2, dtype=np.uint32)


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) of one decoder layer's tensors."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    HD = cfg.get("head_dim") or D // H
    out = {"q": ((D, H * HD), "matrix"), "k": ((D, KV * HD), "matrix"),
           "v": ((D, KV * HD), "matrix"), "o": ((H * HD, D), "matrix"),
           "gate": ((D, F), "matrix"), "up": ((D, F), "matrix"),
           "down": ((F, D), "matrix"),
           "ln1_w": ((D,), "scale"), "ln2_w": ((D,), "scale")}
    if cfg["model_type"] == "stablelm":
        out.update(ln1_b=((D,), "bias"), ln2_b=((D,), "bias"))
    elif cfg["model_type"] == "qwen3":
        out.update(q_norm=((HD,), "scale"), k_norm=((HD,), "scale"))
    else:
        raise ValueError(f"no weight layout for model_type "
                         f"{cfg['model_type']!r}")
    return out


def global_shapes(cfg: dict) -> dict:
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": ((V, D), "embed"), "lm_head": ((D, V), "matrix"),
           "norm_w": ((D,), "scale")}
    if cfg["model_type"] == "stablelm":
        out["norm_b"] = ((D,), "bias")
    return out


def _tensor(key, shape, kind) -> jax.Array:
    bits = jax.random.bits(key, shape, jnp.uint32)
    one_two = jax.lax.bitcast_convert_type(
        (bits >> 9) | np.uint32(0x3F800000), jnp.float32)
    unit = (one_two - 1.0) - 0.5                    # exact, in [-0.5, 0.5)
    if kind == "matrix":
        w = unit * (2.0 * math.sqrt(3.0 / shape[0]))
    elif kind == "embed":
        w = unit * (2.0 * math.sqrt(3.0))
    elif kind == "scale":
        w = unit * 0.25 + 1.0                       # the multiply is exact
    elif kind == "bias":
        w = unit * 0.25
    else:
        raise ValueError(kind)
    return w.astype(jnp.bfloat16)


def _root(kd):
    return jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32),
                                    impl="threefry2x32")


def layer(cfg: dict, kd, li) -> dict:
    """Layer ``li``'s tensors (traceable in ``li``)."""
    lk = jax.random.fold_in(jax.random.fold_in(_root(kd), 1), li)
    return {name: _tensor(jax.random.fold_in(lk, i), shape, kind)
            for i, (name, (shape, kind))
            in enumerate(sorted(layer_shapes(cfg).items()))}


def globals_(cfg: dict, kd) -> dict:
    gk = jax.random.fold_in(_root(kd), 0)
    return {name: _tensor(jax.random.fold_in(gk, i), shape, kind)
            for i, (name, (shape, kind))
            in enumerate(sorted(global_shapes(cfg).items()))}


def stacked(cfg: dict, kd) -> tuple[dict, dict]:
    """(layers stacked on a leading axis, globals); one layer's temporaries
    live at a time."""
    layers = jax.lax.map(lambda li: layer(cfg, kd, li),
                         jnp.arange(cfg["num_hidden_layers"]))
    return layers, globals_(cfg, kd)
