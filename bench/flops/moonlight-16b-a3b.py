"""Model FLOPs of one cross-silo training step of Moonlight-16B-A3B's
share on one chip, and the work of its grouped expert products, from
shapes and the step's routing counters.

Per token: 6 FLOPs per matmul parameter outside the routed experts
(forward 2, backward 4), the untied head included; causal attention
adds, per layer, forward 2 * heads * (qk head size + v head size) *
(mean keys attended), times 3 for forward and backward. The routed
experts add 6 * 3 * hidden * expert width per assignment that a held
expert computed, counted from the step's ``expert_rows`` (the ``rows``
of its ``moe.route`` span), not from an expectation. No recomputation is
counted.

The grouped products (gate, up, down of the held experts, forward and
both backward products) do 18 * rows * hidden * width FLOPs and move,
in float32, per product and pass, the rows read or written on each side
and the held experts' weights: 3 passes x 3 products x (rows * (hidden +
width) + held * hidden * width) elements a layer, counted by the
algorithm, not by padded tiles.
"""
from __future__ import annotations

BYTES = 4          # float32 operands, as the program holds them


def _mla_params(cfg: dict) -> int:
    d, n = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                         cfg["v_head_dim"], cfg["kv_lora_rank"])
    return (d * n * (nope + rope) + d * (r + rope) + r * n * (nope + vd)
            + n * vd * d)


def _expert_params(cfg: dict) -> int:
    """One held expert's matmul parameters."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def matmul_params(cfg: dict, *, routed: bool = True) -> int:
    """Matmul parameters of the share; ``routed=False`` leaves out the
    held experts."""
    d = cfg["hidden_size"]
    experts = cfg["n_routed_experts"] * cfg["expert_parallel"]
    dense = _mla_params(cfg) + 3 * d * cfg["intermediate_size"]
    moe = (_mla_params(cfg) + d * experts
           + cfg["n_shared_experts"] * _expert_params(cfg))
    if routed:
        moe += cfg["n_routed_experts"] * _expert_params(cfg)
    return (cfg["first_k_dense_replace"] * dense + moe_layers(cfg) * moe
            + 2 * d * cfg["vocab_size"])


def step_flops(cfg: dict, rows: int, positions: int,
               routed_rows: int) -> float:
    """FLOPs of one step over ``rows`` sequences of ``positions`` tokens,
    whose held experts computed ``routed_rows`` assignments in all."""
    tokens = rows * positions
    mean_keys = (positions + 1) / 2
    attn = (3 * 2 * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
               + cfg["v_head_dim"]) * mean_keys * cfg["num_hidden_layers"])
    return (tokens * (6 * matmul_params(cfg, routed=False) + attn)
            + 6 * routed_rows * _expert_params(cfg))


def gmm_flops(cfg: dict, routed_rows: int) -> float:
    """FLOPs of one step's grouped products over ``routed_rows``."""
    return 6.0 * routed_rows * _expert_params(cfg)


def gmm_bytes(cfg: dict, routed_rows: int) -> float:
    """HBM bytes of one step's grouped products over ``routed_rows``
    (summed over the expert layers)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = moe_layers(cfg) * cfg["n_routed_experts"] * d * f
    return float(BYTES * 9 * (routed_rows * (d + f) + weights))
