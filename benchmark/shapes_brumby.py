"""Operations and least bytes of the Brumby configuration's decode step and
of its retention kernel alone: the numerators of
``retention_decode_step_roofline``, ``retention_step_roofline`` and the
yardstick of ``state_bytes_per_step.serve``.  As in ``shapes.py``,
recomputed operations do not count and bytes are the least traffic, so a
share can only be understated by them: the state is counted at the
untiled symmetric square's size, ``D = d (d + 1) / 2`` features a head
(8256 for 128), whatever padding the program's feature map carries.
"""

from __future__ import annotations

#: bytes of a state value (float32 by the configuration's ``assumed``)
STATE_BYTES = 4


def params(cfg: dict) -> dict:
    """Parameter counts of the configuration as it is run."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    d = cfg["head_dim"]
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    layer = (h * q + 2 * h * kv + q * h + h * cfg["num_key_value_heads"]
             + 3 * h * cfg["intermediate_size"] + 2 * h + 2 * d)
    embedding = cfg["vocab_size"] * h
    head = 0 if cfg["tie_word_embeddings"] else cfg["vocab_size"] * h
    return {"layer": layer, "embedding": embedding, "head": head,
            "all": layers * layer + embedding + head + h}


def state_values(cfg: dict) -> int:
    """Values of one row's retention state in one layer: per key/value
    head the ``D x d`` sums and the ``D`` normaliser."""
    d = cfg["head_dim"]
    return cfg["num_key_value_heads"] * (d * (d + 1) // 2) * (d + 1)


def retention_step(cfg: dict, rows: float) -> dict:
    """Every retention layer's step for one decode step of ``rows`` seated
    rows: the state read once and written once, q, k, v and the gate read,
    the products written (float32); per state value a decay, an update
    and one multiply-add per query head of the group."""
    layers = cfg["num_hidden_layers"]
    d, heads = cfg["head_dim"], cfg["num_attention_heads"]
    kvh = cfg["num_key_value_heads"]
    values = state_values(cfg)
    small = (2 * heads + 2 * kvh) * d + kvh + heads
    return {"flops": layers * rows * values * (3 + 2 * heads // kvh),
            "bytes": layers * rows * STATE_BYTES * (2 * values + small),
            "state_bytes": layers * rows * STATE_BYTES * 2 * values}


def decode_step(cfg: dict, rows: float, bytes_per_value: int = 2) -> dict:
    """One decode step over ``rows`` seated rows: every layer's weights
    and the head read once, one embedding row a sequence, the state read
    and written once."""
    p = params(cfg)
    h = cfg["hidden_size"]
    weights = cfg["num_hidden_layers"] * p["layer"] \
        + (p["head"] or p["embedding"]) + h
    step = retention_step(cfg, rows)
    return {"flops": 2 * weights * rows + step["flops"],
            "bytes": bytes_per_value * (weights + rows * h) + step["bytes"]}
