"""Model FLOPs of one cross-silo training step of a Qwen3 dense decoder,
from shapes.

Per token: 6 FLOPs per matmul parameter (forward 2, backward 4), the
tied output head included; causal attention adds, per layer, forward
2 * 2 * heads * head_dim * (mean keys attended) for scores and values,
times 3 for forward and backward. No recomputation is counted.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def step_flops(cfg: dict, rows: int, positions: int) -> float:
    """FLOPs of one step over ``rows`` sequences of ``positions`` tokens."""
    tokens = rows * positions
    mean_keys = (positions + 1) / 2
    attn = (3 * 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
            * mean_keys * cfg["num_hidden_layers"])
    return tokens * (6 * matmul_params(cfg) + attn)
