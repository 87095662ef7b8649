"""Shape functions and peaks against hand arithmetic."""
import json
from pathlib import Path

import pytest

from bench import peaks, shapes

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


# the published widths of stablelm-2-1_6b, an MHA model the harness maps
STABLELM = {"model_type": "stablelm", "num_hidden_layers": 24,
            "hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 32, "intermediate_size": 5632,
            "vocab_size": 100352, "tie_word_embeddings": False}


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_kv_bytes_per_token():
    # 24 layers x K and V x 32 kv heads x 64 dims x 2 bytes
    assert shapes.kv_bytes_per_token(STABLELM) == 196_608
    # 18 layers x K and V x 8 kv heads x 128 dims x 2 bytes
    assert shapes.kv_bytes_per_token(cfg("qwen3-8b-h18")) == 73_728


def test_param_counts():
    q = shapes.param_count(cfg("qwen3-8b-h18"))
    # 18 x (41,943,040 attn + 150,994,944 mlp + 8,448 norms)
    #   + 2 x 151,936 x 4,096 + 4,096
    assert q == 18 * (41_943_040 + 150_994_944 + 8_448) \
        + 2 * 151_936 * 4_096 + 4_096
    assert round(q / 1e9, 2) == 4.72
    assert round(shapes.param_count(STABLELM) / 1e9, 2) == 1.64


def test_attention_work_by_hand():
    c = {"num_hidden_layers": 1, "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
         "vocab_size": 10, "model_type": "qwen3"}
    flops, nbytes = shapes.attn_decode_work(c, [3, 5])
    proj = 8 * (2 + 2) * 4 + 2 * 4 * 8          # 192 weights
    ctx = 3 + 5 + 2                              # positions attended
    assert flops == 2 * 2 * proj + 4 * 2 * 4 * ctx
    kv_row = 2 * 1 * 4 * 2
    assert nbytes == proj * 2 + ctx * kv_row + 2 * kv_row + 2 * 2 * 8 * 2
    assert shapes.attn_decode_work(c, []) == (0.0, 0.0)


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    t, bound = peaks.least_time_s(197e12, 819e9 / 2, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = peaks.least_time_s(1.0, 819e9, p)
    assert (t, bound) == (1.0, "memory")
