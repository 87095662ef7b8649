"""The system under test, as the benchmark drives it: the program's
``Engine.serve`` on the paged KV pool, under the plan the program picks by
default.

This module is the only one that imports the program (``repro``, from the
checkout's ``src/``).  It builds the program's configuration from the
benchmark's configuration file, hands the program the benchmark's seeded
weights in the program's parameter layout, and wraps the two compiled
programs the serve loop calls (the pool decode step and the prefill chunk)
so that the harness sees when each call was made.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from bench import weights as W


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a dense decoder configuration."""
    from repro.configs.base import ArchConfig
    mt = cfg["model_type"]
    if mt == "stablelm":
        if cfg.get("use_qkv_bias"):
            raise ValueError("the program's attention has no q/k/v bias")
        extra = {"norm": "layernorm",
                 "qk_norm": bool(cfg.get("qk_layernorm", False)),
                 "partial_rotary": cfg["partial_rotary_factor"]}
    elif mt == "qwen3":
        extra = {"norm": "rmsnorm", "qk_norm": True}
    else:
        raise ValueError(f"no program mapping for model_type {mt!r}")
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or 0, d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        act="silu", glu=True,
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)), **extra)


def _program_tree(cfg: dict, layers: dict, glob: dict) -> dict:
    """The benchmark's layout -> the program's (layer-stacked) layout."""
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    HD = cfg.get("head_dim") or D // H
    attn = {"wq": layers["q"].reshape(L, D, H, HD),
            "wk": layers["k"].reshape(L, D, KV, HD),
            "wv": layers["v"].reshape(L, D, KV, HD),
            "wo": layers["o"].reshape(L, H, HD, D)}
    if "q_norm" in layers:
        attn.update(q_norm=layers["q_norm"], k_norm=layers["k_norm"])

    def norm(w, b):
        return {"scale": w} if b is None else {"scale": w, "bias": b}
    return {
        "embed": {"tokens": glob["embed"], "unembed": glob["lm_head"]},
        "blocks": {"attn": attn,
                   "mlp": {"gate": layers["gate"], "up": layers["up"],
                           "down": layers["down"]},
                   "norm1": norm(layers["ln1_w"], layers.get("ln1_b")),
                   "norm2": norm(layers["ln2_w"], layers.get("ln2_b"))},
        "final_norm": norm(glob["norm_w"], glob.get("norm_b")),
    }


def program_params(cfg: dict, kd) -> dict:
    """Every weight, built on the device from the seed in one jitted call."""
    build = jax.jit(lambda k: _program_tree(cfg, *W.stacked(cfg, k)))
    return jax.block_until_ready(build(jnp.asarray(kd, jnp.uint32)))


def build_engine(cfg: dict, params, engine: dict):
    from repro.models.model import build
    from repro.serve.engine import Engine, ServeConfig
    model = build(arch_config(cfg))
    scfg = ServeConfig(
        paged="on", max_len=engine["max_len"], max_slots=engine["slots"],
        page_size=engine["page_size"], kv_pages=engine["kv_pages"],
        prefill_chunk=engine["prefill_chunk"], temperature=0.0, seed=0)
    return Engine(model, params, serve_cfg=scfg)


def requests(specs: list, token_list) -> list:
    """The program's ``Request`` objects, each collecting its tokens into a
    list made by ``token_list(rid)``."""
    from repro.serve.scheduler import Request
    out = []
    for s in specs:
        r = Request(rid=s.rid, prompt=s.prompt,
                    max_new_tokens=s.max_new_tokens, arrival_s=s.arrival_s)
        r.out_tokens = token_list(s.rid)
        out.append(r)
    return out


class Timed:
    """A compiled program with a record of each call: (start, end of
    dispatch, kept arguments) on the harness clock, relative to ``t0``."""

    def __init__(self, fn, calls: list, t0_ref: list, keep=None,
                 annotate: str = ""):
        self.fn, self.calls, self.t0_ref = fn, calls, t0_ref
        self.keep, self.annotate = keep, annotate

    def __call__(self, *args):
        t = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(self.annotate):
                out = self.fn(*args)
        else:
            out = self.fn(*args)
        t0 = self.t0_ref[0]
        self.calls.append((t - t0, time.perf_counter() - t0,
                           self.keep(args) if self.keep else None))
        return out


def lengths_and_active(args, slots: int):
    """The pool decode step's ``lengths`` and ``active`` arguments, picked
    by their form: the one int32 and the one bool vector of ``slots``
    entries.  Any other form of the call raises, so that a change to the
    program's step cannot silently change what the metrics count."""
    def pick(dtype):
        got = [a for a in args if getattr(a, "shape", None) == (slots,)
               and getattr(a, "dtype", None) == dtype]
        if len(got) != 1:
            raise RuntimeError(
                f"the pool decode step has {len(got)} {dtype} arguments of "
                f"shape ({slots},); expected one")
        return got[0]
    return pick(jnp.int32), pick(jnp.bool_)


def instrument(engine, decode_calls: list, chunk_calls: list, t0_ref: list,
               traced: bool):
    """Wrap the warmed pool decode step and prefill-chunk program.  In a
    traced run each decode call also keeps its ``lengths`` and ``active``
    arguments (device arrays, read after the window) and each call is a
    host span in the profiler's trace."""
    slots = engine.cfg.max_slots
    keep = (lambda a: lengths_and_active(a, slots)) if traced else None
    engine._pool_step = Timed(engine._pool_step, decode_calls, t0_ref, keep,
                              "bench.decode_dispatch" if traced else "")
    engine._chunk_step = Timed(engine._chunk_fn(), chunk_calls, t0_ref, None,
                               "bench.chunk_dispatch" if traced else "")


def check_instrumented(engine, decode_calls: list):
    """After the window: the serve loop called the wrapped step for every
    decode step it counted.  A wrapper the engine replaced (a re-plan or a
    fallback swaps its step) raises instead of leaving the metrics short.
    The last call may lack its count: the window closes inside it."""
    if not isinstance(engine._pool_step, Timed):
        raise RuntimeError("the engine replaced the wrapped pool decode "
                           "step inside the window")
    steps = engine.health.taps["steps"]
    if len(decode_calls) - steps not in (0, 1):
        raise RuntimeError(f"{len(decode_calls)} decode calls recorded, "
                           f"the engine counted {steps} steps")


def module_texts(engine) -> dict:
    """role -> (module name, compiled HLO text) of the pool decode step and
    the prefill-chunk program, for scope attribution where trace events
    carry no op path.  The chunk program is lowered again with the avals
    the serve loop calls it with (a compile-cache hit)."""
    import jax.numpy as jnp
    pool, c = engine._pool, engine.cfg.prefill_chunk
    chunk = getattr(engine._chunk_step, "fn", engine._chunk_step)
    step = getattr(engine._pool_step, "fn", engine._pool_step)
    texts = {
        "decode": step.as_text(),
        "chunk": chunk.lower(
            engine._step_params, pool.pages, jnp.zeros((1, c), jnp.int32),
            jnp.zeros((pool.max_pages_per_slot,), jnp.int32),
            jnp.asarray(0, jnp.int32)).compile().as_text(),
    }
    return {role: (text.split(None, 2)[1].rstrip(","), text)
            for role, text in texts.items()}
