"""Plain float32 reference of a dense decoder LM, written from the layer
equations of the published architectures (Hugging Face ``modeling_*.py``):

* ``stablelm`` (StableLM with ``use_qkv_bias`` false): LayerNorm with
  bias, partial rotary embedding (``partial_rotary_factor`` of each head's
  dims, rotate-half form), MHA, SwiGLU MLP, untied head.
* ``qwen3`` (Qwen3): RMSNorm, per-head RMSNorm of q and k before the
  rotary embedding (qk-norm, at ``qk_norm_eps`` where the file states
  one), full rotary embedding at ``rope_theta``,
  GQA with each kv head serving ``heads / kv_heads`` consecutive q heads,
  SwiGLU MLP, untied head.

It imports nothing of the program under test.  Weights come from
``bench.weights`` layer by layer, so the whole model never sits on the
device in float32.  Every matrix product runs at ``Precision.HIGHEST``
(on a TPU a float32 product is otherwise one bf16 pass).

``quant="fp8"`` is the control: every matrix-product operand is rounded to
float8_e4m3 (per-row absmax scaling for activations, per-output-column for
weights) before the float32 product, the precision step below bf16.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _q8(x, axis):
    """Round to float8_e4m3 with an absmax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def _norm(cfg, x, w, b=None):
    if cfg["model_type"] == "stablelm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + cfg["layer_norm_eps"]) * w + b
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + cfg["rms_norm_eps"]) * w


def _rope(cfg, x, pos):
    """x: (T, heads, hd); rotate-half over the first ``rot`` dims."""
    hd = x.shape[-1]
    rot = int(hd * cfg.get("partial_rotary_factor", 1.0))
    rot -= rot % 2
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, rot, 2,
                                                  dtype=jnp.float32) / rot))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, xp], -1)


def _block(cfg, w, x, quant):
    """One decoder layer over one sequence x: (T, D)."""
    T = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    HD = cfg.get("head_dim") or cfg["hidden_size"] // H
    pos = jnp.arange(T)
    h = _norm(cfg, x, w["ln1_w"], w.get("ln1_b"))
    q = _mm(h, w["q"], quant).reshape(T, H, HD)
    k = _mm(h, w["k"], quant).reshape(T, KV, HD)
    v = _mm(h, w["v"], quant).reshape(T, KV, HD)
    if cfg["model_type"] == "qwen3":
        eps = cfg.get("qk_norm_eps", cfg["rms_norm_eps"])
        q = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True) + eps) \
            * w["q_norm"]
        k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + eps) \
            * w["k_norm"]
    q, k = _rope(cfg, q, pos), _rope(cfg, k, pos)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    if quant == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    s = jnp.einsum("qhe,khe->hqk", q, k, precision=HI) / math.sqrt(HD)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant == "fp8":
        p = _q8(p, -1)
    a = jnp.einsum("hqk,khe->qhe", p, v, precision=HI).reshape(T, H * HD)
    x = x + _mm(a, w["o"], quant)
    h = _norm(cfg, x, w["ln2_w"], w.get("ln2_b"))
    f = jax.nn.silu(_mm(h, w["gate"], quant)) * _mm(h, w["up"], quant)
    return x + _mm(f, w["down"], quant)


def _final(cfg, g, x, quant):
    h = _norm(cfg, x, g["norm_w"], g.get("norm_b"))
    return _mm(h, g["lm_head"], quant)


class Reference:
    """Jitted pieces for one configuration, compiled once per sequence
    length bucket."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
        self.layer = jax.jit(lambda kd, li: f32(W.layer(cfg, kd, li)))
        self.globals = jax.jit(lambda kd: f32(W.globals_(cfg, kd)))
        self.embed = jax.jit(lambda g, t: g["embed"][t])
        self.block = jax.jit(lambda w, x, quant: _block(cfg, w, x, quant),
                             static_argnums=2)
        # gap of ``tokens`` below the float32 reference's best, per position
        self.gap = jax.jit(lambda g, x, t: self._gap(g, x, t))
        self.argmax = jax.jit(
            lambda g, x, quant: jnp.argmax(_final(cfg, g, x, quant), -1),
            static_argnums=2)

    def _gap(self, g, x, tokens):
        logits = _final(self.cfg, g, x, "f32")
        return jnp.max(logits, -1) - jnp.take_along_axis(
            logits, tokens[:, None], -1)[:, 0]


@functools.lru_cache(maxsize=4)
def _reference(cfg_json: str) -> Reference:
    return Reference(json.loads(cfg_json))


def served_gaps(cfg: dict, kd, seqs: list, n_prompt: list, *,
                control: bool = False, bucket: int = 512) -> list:
    """Per request, the gap by which each served token's logit lies below
    the float32 reference's best at its position.  ``seqs[i]`` is prompt
    then served tokens, ``n_prompt[i]`` the prompt's length.  With
    ``control`` the served tokens are replaced by the fp8 control's own
    first choices at the same positions.  Sequences are padded at the end
    to a multiple of ``bucket`` (causal, so padding changes nothing)."""
    ref = _reference(json.dumps(cfg, sort_keys=True))
    kd = jnp.asarray(kd, jnp.uint32)
    g = ref.globals(kd)
    toks = []
    for s in seqs:
        n = len(s)
        pad = -(-n // bucket) * bucket
        toks.append(jnp.asarray(np.pad(np.asarray(s, np.int32),
                                       (0, pad - n))))
    xs = [ref.embed(g, t) for t in toks]
    xc = list(xs) if control else None
    for li in range(cfg["num_hidden_layers"]):
        w = ref.layer(kd, li)
        xs = [ref.block(w, x, "f32") for x in xs]
        if control:
            xc = [ref.block(w, x, "fp8") for x in xc]
        del w
    out = []
    for i, (s, p0) in enumerate(zip(seqs, n_prompt)):
        n = len(s)
        # position j's logits predict token j + 1
        if control:
            want = ref.argmax(g, xc[i], "fp8")
        else:
            want = jnp.concatenate([toks[i][1:], jnp.zeros((1,), jnp.int32)])
        gaps = np.asarray(ref.gap(g, xs[i], want))
        out.append(gaps[p0 - 1:n - 1])
    return out
