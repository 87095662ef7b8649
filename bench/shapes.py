"""Operations and bytes that the served algorithm needs, from a
configuration's published sizes (the keys of ``bench/configs/<name>.json``).

Counted is the work the algorithm requires at live lengths, never what an
implementation happens to do: a decode pass attends over each active slot's
written positions, not over the block table's full width, and slots that
sit idle cost nothing.  Weights and the KV cache are bf16 (2 bytes).
Norms, rotary embedding, softmax and the sampler are elementwise and left
out of the operation count; their bytes are those of the activations.
"""
from __future__ import annotations

from typing import Sequence

BYTES = 2       # bf16 weights, activations and KV cache


def dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
        "H": h, "KV": cfg["num_key_value_heads"],
        "HD": cfg.get("head_dim") or cfg["hidden_size"] // h,
        "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
    }


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one token over every layer."""
    d = dims(cfg)
    return d["L"] * 2 * d["KV"] * d["HD"] * BYTES


def layer_params(cfg: dict) -> dict:
    """Parameters of one decoder layer, by part."""
    d = dims(cfg)
    attn = d["D"] * (d["H"] + 2 * d["KV"]) * d["HD"] + d["H"] * d["HD"] * d["D"]
    mlp = 3 * d["D"] * d["F"]
    n_norm = 2 if cfg.get("model_type") == "stablelm" else 1   # scale, bias
    norms = 2 * n_norm * d["D"]
    if cfg.get("model_type") == "qwen3":
        norms += 2 * d["HD"]                                   # q/k norms
    return {"attn": attn, "mlp": mlp, "norms": norms}


def param_count(cfg: dict) -> int:
    d = dims(cfg)
    per_layer = sum(layer_params(cfg).values())
    embed = d["V"] * d["D"] * (1 if cfg.get("tie_word_embeddings") else 2)
    n_norm = 2 if cfg.get("model_type") == "stablelm" else 1
    return d["L"] * per_layer + embed + n_norm * d["D"]


def attn_decode_work(cfg: dict, lengths: Sequence[int]) -> tuple[float, float]:
    """(flops, bytes) of the attention scope of one decode pass over every
    layer: q/k/v/o projections, the new K/V rows written, and attention over
    ``length + 1`` positions for each active slot (``lengths`` = tokens
    already written, one entry per active slot)."""
    d = dims(cfg)
    n = len(lengths)
    if n == 0:
        return 0.0, 0.0
    ctx = float(sum(lengths)) + n
    proj_w = layer_params(cfg)["attn"]
    flops = 2.0 * n * proj_w + 4.0 * d["H"] * d["HD"] * ctx
    kv_row = 2 * d["KV"] * d["HD"] * BYTES
    nbytes = (proj_w * BYTES                    # projection weights, once
              + ctx * kv_row                    # K/V read at live lengths
              + n * kv_row                      # this pass's K/V written
              + 2 * n * d["D"] * BYTES)         # activations in and out
    return d["L"] * flops, d["L"] * nbytes


def decode_pass_work(cfg: dict, lengths: Sequence[int]) -> tuple[float, float]:
    """(flops, bytes) of one whole decode pass: attention as above, the MLPs,
    the final norm and the logits, for ``len(lengths)`` active slots."""
    d = dims(cfg)
    n = len(lengths)
    if n == 0:
        return 0.0, 0.0
    a_flops, a_bytes = attn_decode_work(cfg, lengths)
    mlp_w = layer_params(cfg)["mlp"]
    flops = (a_flops + d["L"] * 2.0 * n * mlp_w
             + 2.0 * n * d["D"] * d["V"])
    nbytes = (a_bytes + d["L"] * mlp_w * BYTES
              + d["D"] * d["V"] * BYTES                    # lm head
              + n * d["D"] * BYTES                         # embedding rows
              + n * d["V"] * 4)                            # f32 logits
    return flops, nbytes


def prefill_chunk_work(cfg: dict, base: int, n_tokens: int
                       ) -> tuple[float, float]:
    """(flops, bytes) of one prefill chunk of ``n_tokens`` prompt tokens at
    positions ``base .. base + n_tokens - 1`` of one request (no logits:
    the chunk only fills the cache)."""
    d = dims(cfg)
    lp = layer_params(cfg)
    # causal attention: token i attends over base + i + 1 positions
    ctx = n_tokens * base + n_tokens * (n_tokens + 1) / 2.0
    flops = d["L"] * (2.0 * n_tokens * (lp["attn"] + lp["mlp"])
                      + 4.0 * d["H"] * d["HD"] * ctx)
    kv_row = 2 * d["KV"] * d["HD"] * BYTES
    nbytes = d["L"] * ((lp["attn"] + lp["mlp"]) * BYTES
                       + (base + n_tokens) * kv_row
                       + 2 * n_tokens * d["D"] * BYTES)
    return flops, nbytes
