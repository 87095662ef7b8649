"""The comparison that decides ``correct``, at a size a CPU test run holds.

The program serves a tiny stablelm-like and a tiny qwen3-like model
through the same harness a chip run uses (the look for a chip skipped);
the widest gap of its served tokens below the float32 reference must stay
under the limit, and the fp8 control's must not.  A second test breaks
the timed path underneath the harness and sees ``correct`` come out false.
"""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import dense

DATA = Path(__file__).resolve().parent / "data"
MIXES = Path(__file__).resolve().parents[1] / "traffic"
# The limit at this size, from CPU readings over six seeds of each tiny
# model: the program's widest gap read at most 0.0304, the fp8 control's
# at least 0.0833; 0.06 lies between with room on both sides.
LIMIT = 0.06


def tiny_cell(cfg_name, mix_name, limit=LIMIT):
    cfg = json.loads((DATA / f"{cfg_name}.json").read_text())
    mix = json.loads((MIXES / f"{mix_name}.json").read_text())
    mix.update({"n_requests": 6, "rate_per_s": 4.0,
                "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                           "min": 4, "max": 60},
                "output": {"dist": "uniform", "min": 8, "max": 60}})
    return harness.Cell(
        name="tiny", chips=1, cfg=cfg, mix=mix,
        engine={"slots": 4, "page_size": 8, "prefill_chunk": 16,
                "max_len": 128, "kv_pages": 0},
        check={"compare_requests": 3, "limit": limit},
        metrics={"out_tok_s": {"unit": "tokens/s", "kind": "e2e"}})


def served(cell, seed, **kw):
    kept = {}

    def gaps(cfg, kd, seqs, n_prompt):
        kept.update(kd=kd, seqs=seqs, n_prompt=n_prompt)
        return dense.served_gaps(cfg, kd, seqs, n_prompt)

    out = harness.run_cell(cell, seed, 2.0, False, time.perf_counter(),
                           require_chip=False, gaps_fn=gaps, **kw)
    return out, kept


@pytest.mark.parametrize("cfg_name,mix_name,seed", [
    ("tiny-stablelm", "batch-decode", 3_000_000_011),
    ("tiny-qwen3", "chat", 3_000_000_012),
])
def test_program_passes_and_the_fp8_control_fails(cfg_name, mix_name, seed):
    out, kept = served(tiny_cell(cfg_name, mix_name), seed)
    assert out["notes"]["compared_tokens"] >= 40
    assert out["correct"], out["check"]
    ctl = dense.served_gaps(tiny_cell(cfg_name, mix_name).cfg, kept["kd"],
                            kept["seqs"], kept["n_prompt"], control=True)
    assert max(float(g.max()) for g in ctl) > LIMIT


def _break_tokens(engine):
    """A token altered where it is produced: the pool step's sampled token
    is shifted by one."""
    step = engine._pool_step

    def broken(*args):
        toks, finite, pages = step(*args)
        return (toks + 1) % engine.model.cfg.vocab_size, finite, pages
    engine._pool_step = broken


def _drop_state(engine):
    """A step that returns its state unchanged: the decode step's K/V
    writes are thrown away."""
    step = engine._pool_step

    def broken(params, pages, *rest):
        before = jax.tree.map(jnp.copy, pages)
        toks, finite, _ = step(params, pages, *rest)
        return toks, finite, before
    engine._pool_step = broken


@pytest.mark.parametrize("fault", [_break_tokens, _drop_state])
def test_a_broken_timed_path_is_not_correct(fault):
    out, _ = served(tiny_cell("tiny-qwen3", "chat"), 3_000_000_013,
                    on_engine=fault)
    assert not out["correct"], out["check"]
    assert out["check"]["logit_gap"]["value"] > LIMIT
