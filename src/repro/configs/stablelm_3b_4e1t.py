"""stablelm-3b-4e1t [dense] — MHA (kv=32) with 80-wide heads, partial rotary
(20 of 80 dims), LayerNorm with bias, no q/k/v bias.
[hf:stabilityai/stablelm-3b-4e1t config.json]"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="stablelm-3b-4e1t",
    family="dense",
    n_layers=32,
    d_model=2560,
    n_heads=32,
    n_kv_heads=32,
    head_dim=80,
    d_ff=6912,
    vocab_size=50304,
    rope_theta=10_000.0,
    partial_rotary=0.25,
    norm="layernorm",
    act="silu",
    glu=True,
)
