"""Operations and least bytes of the LFM2-MoE configuration's decode step
and of its expert matmuls alone: the numerators of
``moe_decode_step_roofline`` and ``expert_matmul_roofline.serve``.  As in
``shapes.py``, recomputed operations do not count and bytes are the least
traffic, so a share can only be understated by them.
"""

from __future__ import annotations


def params(cfg: dict) -> dict:
    """Parameter counts of the configuration as it is run."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    d = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    n_conv = cfg["layer_types"].count("conv")
    n_attn = layers - n_conv
    n_expert_layers = layers - cfg["num_dense_layers"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    conv = 3 * h * h + h * h + cfg["conv_L_cache"] * h
    attn = 2 * h * h + 2 * h * kv + 2 * d
    dense = 3 * h * cfg["intermediate_size"]
    router = h * cfg["num_experts"] + cfg["num_experts"]
    embedding = cfg["vocab_size"] * h
    outside = (n_conv * conv + n_attn * attn
               + cfg["num_dense_layers"] * dense + n_expert_layers * router
               + embedding + (2 * layers + 1) * h)
    return {"expert": expert, "expert_layers": n_expert_layers,
            "attention_layers": n_attn, "conv_layers": n_conv,
            "outside_experts": outside,
            "all": outside + n_expert_layers * cfg["num_experts"] * expert}


def expert_matmuls(cfg: dict, rows: float, experts_touched: float,
                   bytes_per_value: int = 2) -> dict:
    """The three grouped products of every expert layer for one decode step
    of ``rows`` rows: each touched expert's weights read once
    (``experts_touched`` counts them over all expert layers), each
    assignment's input row read and output row written once."""
    p = params(cfg)
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    assignments = rows * cfg["num_experts_per_tok"] * p["expert_layers"]
    return {"flops": 2 * p["expert"] * assignments,
            "bytes": bytes_per_value * (experts_touched * p["expert"]
                                        + assignments * (2 * h + 2 * f))}


def decode_step(cfg: dict, rows: float, live_tokens: float,
                experts_touched: float, bytes_per_value: int = 2) -> dict:
    """One decode step over ``rows`` sequences whose contexts hold
    ``live_tokens`` tokens together: every weight outside the experts read
    once, each touched expert's weights read once, the live K/V of the
    attention layers (K and V, the K/V heads' widths) and every row's conv
    state read once."""
    p = params(cfg)
    h = cfg["hidden_size"]
    kv_per_token = 2 * p["attention_layers"] * cfg["num_key_value_heads"] \
        * (h // cfg["num_attention_heads"])
    conv_state = rows * p["conv_layers"] * (cfg["conv_L_cache"] - 1) * h
    experts = expert_matmuls(cfg, rows, experts_touched, bytes_per_value)
    matmul_outside = p["outside_experts"]
    heads_flops = 2 * 2 * p["attention_layers"] * h * live_tokens
    return {"flops": 2 * matmul_outside * rows + heads_flops
            + experts["flops"],
            "bytes": bytes_per_value * (p["outside_experts"] + kv_per_token
                                        * live_tokens + conv_state)
            + experts["bytes"]}
