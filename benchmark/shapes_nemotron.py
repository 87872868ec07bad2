"""Operations and least bytes of the Nemotron-H configuration's decode step,
of its state-space kernel and of its expert products alone: the numerators
of ``ssm_moe_decode_step_roofline``, ``ssm_step_roofline`` and
``held_expert_matmul_roofline.serve``.  As in ``shapes.py``, recomputed
operations do not count and bytes are the least traffic WHATEVER
IMPLEMENTS THE STEP, so a share can only be understated by them: the
convolution's tail, the small operands of the state update and the
activations between layers are counted once or not at all.

The configuration file holds the chip's share: ``n_routed_experts`` is the
experts HELD (16 of the 128 ``num_experts`` the router scores) and
``vocab_size`` the slice.
"""

from __future__ import annotations

#: bytes of a state value (float32 by the configuration's ``assumed``)
STATE_BYTES = 4


def pattern(cfg: dict) -> dict:
    p = cfg["hybrid_override_pattern"]
    return {"mamba2": p.count("M"), "experts": p.count("E"),
            "attention": p.count("*")}


def params(cfg: dict) -> dict:
    """Parameter counts of the configuration as it is run."""
    h = cfg["hidden_size"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner = H * P
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    mamba = (h * (inner + conv + H) + (cfg["conv_kernel"] + 1) * conv
             + 3 * H + inner + inner * h + h)
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    attention = 2 * h * q + 2 * h * kv + h
    expert = 2 * h * cfg["moe_intermediate_size"]
    shared = 2 * h * cfg["moe_shared_expert_intermediate_size"]
    router = h * cfg["num_experts"] + cfg["num_experts"]
    n = pattern(cfg)
    embedding = cfg["vocab_size"] * h
    outside = (n["mamba2"] * mamba + n["attention"] * attention
               + n["experts"] * (shared + router + h) + h)
    return {"mamba2": mamba, "attention": attention, "expert": expert,
            "shared": shared, "router": router, "embedding": embedding,
            "head": embedding, "layers": n,
            "outside_experts": outside + 2 * embedding,
            "all": outside + 2 * embedding
            + n["experts"] * cfg["n_routed_experts"] * expert}


def state_values(cfg: dict) -> int:
    """Values of one row's SSM state in one layer."""
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]


def ssm_step(cfg: dict, rows: float) -> dict:
    """Every Mamba-2 layer's recurrent step for one decode step of ``rows``
    seated rows: the state read once and written once (float32), x, dt,
    B and C read and y written; per state value a decay, an update
    (multiply and add) and a multiply-add into y."""
    layers = pattern(cfg)["mamba2"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    small = 2 * H * P + 2 * H + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    values = state_values(cfg)
    return {"flops": layers * rows * values * 5,
            "bytes": layers * rows * STATE_BYTES * (2 * values + small),
            "state_bytes": layers * rows * STATE_BYTES * 2 * values}


def expert_matmuls(cfg: dict, rows: float, experts_touched: float,
                   bytes_per_value: int = 2) -> dict:
    """The two grouped products of every expert layer's HELD experts and
    the shared expert's two products, for one decode step of ``rows``
    rows: each touched held expert's weights read once
    (``experts_touched`` counts them over all expert layers) for the one
    assignment it has at least (its input row read, its output row
    written), the shared expert's weights read once a layer and computed
    for every row."""
    p = params(cfg)
    h = cfg["hidden_size"]
    f = cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    n = p["layers"]["experts"]
    return {"flops": 2 * p["expert"] * experts_touched
            + 2 * p["shared"] * rows * n,
            "bytes": bytes_per_value * (
                experts_touched * p["expert"] + n * p["shared"]
                + experts_touched * (2 * h + 2 * f)
                + n * rows * (2 * h + 2 * fs))}


def decode_step(cfg: dict, rows: float, live_tokens: float,
                experts_touched: float, bytes_per_value: int = 2) -> dict:
    """One decode step over ``rows`` sequences whose contexts hold
    ``live_tokens`` tokens together: every weight outside the routed
    experts and the head read once (one embedding row a sequence), each
    touched held expert's weights read once, the SSM state read and
    written once, the live K/V of the attention layers read once."""
    p = params(cfg)
    h = cfg["hidden_size"]
    n = p["layers"]
    kv_per_token = 2 * n["attention"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"]
    weights = p["outside_experts"] - p["embedding"] - n["experts"] \
        * p["shared"]
    experts = expert_matmuls(cfg, rows, experts_touched, bytes_per_value)
    ssm = ssm_step(cfg, rows)
    attend = 2 * 2 * n["attention"] * cfg["num_attention_heads"] \
        * cfg["head_dim"] * live_tokens
    return {"flops": 2 * weights * rows + attend + experts["flops"]
            + ssm["flops"],
            "bytes": bytes_per_value * (weights + rows * h + kv_per_token
                                        * live_tokens)
            + experts["bytes"] + ssm["bytes"]}
