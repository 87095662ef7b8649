"""A narrow-head MHA model served end to end on the lane-dense K/V pool,
against the plain float32 reference.

A tiny stablelm-type configuration (hidden 320, 4 heads of 80 — the
published stablelm-3b-4e1t head width — MHA, partial rotary 0.25,
LayerNorm with bias, 2 layers) is served by ``Engine.serve`` on the paged
pool, once with the Pallas kernel pinned (interpreted on the CPU) and once
on the gather.  Prompts are prefilled in chunks and the outputs decoded
through the cache; four requests share three slots, so one waits for a
slot and dead slots run in the step.  Each served token's logit is
compared with the reference's best at its position
(``bench/reference/dense.py`` on the same seeded ``bench/weights.py``
weights), the comparison a chip run's ``correct`` makes.
"""
import numpy as np
import pytest

from bench import system
from bench import weights as W
from bench.reference import dense
from repro.core.policy import RegionConfig, RegionPlan
from repro.models.model import build
from repro.serve.engine import Engine, ServeConfig
from repro.serve.scheduler import Request

CFG = {"name": "tiny-stablelm-hd80", "reference": "dense",
       "model_type": "stablelm", "hidden_size": 320,
       "intermediate_size": 864, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "partial_rotary_factor": 0.25, "rope_theta": 10000,
       "layer_norm_eps": 1e-05, "vocab_size": 256,
       "tie_word_embeddings": False}
PROMPTS = (9, 23, 40, 61)
# The widest gap of a served token's logit below the reference's best.
# CPU readings over seven seeds: the program (bf16 weights, activations
# and K/V against float32) read at most 0.0288 on the kernel and 0.0250
# on the gather; the fp8 control at the same positions read at least
# 0.113.  0.06 lies between, about twice from each.
LIMIT = 0.06


def _serve(impl: str, seed: int):
    kd = W.key_data(seed)
    params = system.program_params(CFG, kd)
    eng = Engine(build(system.arch_config(CFG)), params,
                 plan=RegionPlan(region_configs={
                     "layer/attn": RegionConfig(attn_impl=impl)}),
                 serve_cfg=ServeConfig(paged="on", max_len=128, max_slots=3,
                                       page_size=8, prefill_chunk=16,
                                       temperature=0.0, seed=0))
    rng = np.random.default_rng(seed % 1000)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=40 - 5 * i)
            for i, n in enumerate(PROMPTS)]
    eng.serve(reqs)
    seqs = [np.concatenate([r.prompt, np.asarray(r.out_tokens, np.int32)])
            for r in reqs]
    return eng, kd, seqs, [r.prompt.size for r in reqs]


@pytest.mark.parametrize("seed", [3_000_000_201, 3_000_000_206])
@pytest.mark.parametrize("impl", ["paged", "gather"])
def test_served_logits_match_the_reference(impl, seed):
    eng, kd, seqs, n_prompt = _serve(impl, seed)
    assert eng.observability()["step"] == {"attn_impl": impl,
                                           "pool_layout": "lane_dense"}
    assert [s.size - n for s, n in zip(seqs, n_prompt)] == [40, 35, 30, 25]
    gaps = dense.served_gaps(CFG, kd, seqs, n_prompt)
    assert sum(g.size for g in gaps) == 130
    widest = max(float(g.max()) for g in gaps)
    assert widest <= LIMIT, widest
    # the same comparison fails one precision step below the program's
    ctl = dense.served_gaps(CFG, kd, seqs, n_prompt, control=True)
    assert max(float(g.max()) for g in ctl) > LIMIT
