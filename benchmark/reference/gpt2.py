"""Plain reference for the ``gpt2-xl`` configuration: the GPT-2 decoder
(Radford et al. 2019; the public ``gpt2-xl`` ``config.json``) as one full
forward pass in straightforward ``jax.numpy``, float32, with
``default_matmul_precision("highest")``: no cache, no batching, no kernels.
Pre-LayerNorm blocks, learned positions, tanh-approximated GELU
(``gelu_new``), LayerNorm epsilon 1e-5, output head tied to the token
embedding.

It imports nothing of the program and takes nothing the program made.  The
weights are :func:`make_weights`' (from the seed, in the type they are
served in; the program is *given* the same tree, whose names follow what
the program's model reads), rounded to that type and then held in float32.

A served stream is scored by teacher forcing: ONE forward over prompt +
served tokens gives, at every served position, how far the served token's
logit falls short of the reference's best, in standard deviations of that
position's logits.  The control (``quant="fp8"``) rounds every matrix
product's inputs to ``float8_e4m3fn`` and reports the same shortfall for
the token *it* puts first.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.child import seed_key

#: limits of the comparisons, each set from chip readings (my chip runs,
#: PR 24; PERF.md section 2), above the sound runs' largest and below the
#: fp8 control's smallest with room on both sides:
#: - ``served_gap_sigmas`` (worst served token): sound 0 .. 0.041 over 10
#:   seeds (bf16 near-ties), control 1.78 .. 2.92 over 5 seeds;
#: - ``served_gap_mean_sigmas``: sound 0 .. 1.6e-4, control 0.31 .. 0.55.
LIMITS = {"served_gap_sigmas": 0.25, "served_gap_mean_sigmas": 0.004}

LN_EPS_DEFAULT = 1e-5


def _stacked_specs(cfg: dict) -> list[tuple]:
    """(path within a layer, shape, std or None for LayerNorm-like)."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg.get("init_std", 0.02)      # GPT-2's; a toy width needs more
    out_std = std / math.sqrt(2 * cfg["num_layers"])
    return [(("ln1", "scale"), (h,), "scale"), (("ln1", "bias"), (h,), 0.02),
            (("attn", "query", "kernel"), (h, h), std),
            (("attn", "query", "bias"), (h,), 0.02),
            (("attn", "key", "kernel"), (h, h), std),
            (("attn", "key", "bias"), (h,), 0.02),
            (("attn", "value", "kernel"), (h, h), std),
            (("attn", "value", "bias"), (h,), 0.02),
            (("attn", "out", "kernel"), (h, h), out_std),
            (("attn", "out", "bias"), (h,), 0.02),
            (("ln2", "scale"), (h,), "scale"), (("ln2", "bias"), (h,), 0.02),
            (("mlp_up", "kernel"), (h, i), std),
            (("mlp_up", "bias"), (i,), 0.02),
            (("mlp_down", "kernel"), (i, h), out_std),
            (("mlp_down", "bias"), (h,), 0.02)]


def _put(tree: dict, path: tuple, leaf) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = leaf


def make_stacked(seed: int, cfg: dict) -> dict:
    """The weights from the seed with every block's leaf stacked on a
    leading layer axis (what the reference scans over): ``{"layers": {...},
    "tok_emb", "pos_emb", "ln_f"}``, in ``cfg["dtype"]``.  GPT-2's own
    initialisation: normal(0.02), residual projections scaled by
    1/sqrt(2 L); LayerNorm scales near 1.  Call under ``jax.jit``."""
    key = seed if hasattr(seed, "dtype") else seed_key(seed)
    dtype = jnp.dtype(cfg["dtype"])
    L, h = cfg["num_layers"], cfg["hidden_size"]

    def draw(i, shape, std):
        noise = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
        if std == "scale":
            return (1.0 + 0.02 * noise).astype(dtype)
        return (std * noise).astype(dtype)

    layers: dict = {}
    for i, (path, shape, std) in enumerate(_stacked_specs(cfg)):
        _put(layers, path, draw(i, (L,) + shape, std))
    return {"layers": layers,
            "tok_emb": draw(100, (cfg["vocab_size"], h), 0.02),
            "pos_emb": draw(101, (cfg["max_position_embeddings"], h), 0.01),
            "ln_f": {"scale": draw(102, (h,), "scale"),
                     "bias": draw(103, (h,), 0.02)}}


def make_weights(seed: int, cfg: dict) -> dict:
    """The same weights as the tree the program's model reads:
    ``layer_<i>/...``, ``tok_emb/embedding``, ``pos_emb``, ``ln_f``."""
    s = make_stacked(seed, cfg)
    params = {"tok_emb": {"embedding": s["tok_emb"]}, "pos_emb": s["pos_emb"],
              "ln_f": s["ln_f"]}
    for i in range(cfg["num_layers"]):
        params[f"layer_{i}"] = jax.tree.map(lambda a: a[i], s["layers"])
    return params


# ------------------------------------------------------------------ forward

def _q(x, quant):
    if quant is None:
        return x
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if quant == "bf16":       # the configuration's own precision
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f"unknown control precision {quant!r}")


def _ln(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) \
        * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _dense(x, p, quant):
    return _q(x, quant) @ _q(p["kernel"].astype(jnp.float32), quant) \
        + p["bias"].astype(jnp.float32)


def forward(stacked: dict, ids, cfg: dict, quant=None):
    """Logits ``[B, T, V]`` (float32) of the full causal forward pass."""
    B, T = ids.shape
    H = cfg["num_heads"]
    D = cfg["hidden_size"] // H
    eps = cfg.get("norm_eps", LN_EPS_DEFAULT)
    table = stacked["tok_emb"].astype(jnp.float32)
    x = table[ids] + stacked["pos_emb"].astype(jnp.float32)[:T]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def block(x, p):
        y = _ln(x, p["ln1"], eps)
        q = _dense(y, p["attn"]["query"], quant).reshape(B, T, H, D)
        k = _dense(y, p["attn"]["key"], quant).reshape(B, T, H, D)
        v = _dense(y, p["attn"]["value"], quant).reshape(B, T, H, D)
        s = jnp.einsum("bthd,bshd->bhts", _q(q, quant), _q(k, quant)) \
            / math.sqrt(D)
        s = jnp.where(causal[None, None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhts,bshd->bthd", _q(a, quant), _q(v, quant))
        x = x + _dense(ctx.reshape(B, T, H * D), p["attn"]["out"], quant)
        y = _ln(x, p["ln2"], eps)
        y = jax.nn.gelu(_dense(y, p["mlp_up"], quant), approximate=True)
        return x + _dense(y, p["mlp_down"], quant), None

    x, _ = jax.lax.scan(block, x, stacked["layers"])
    x = _ln(x, stacked["ln_f"], eps)
    return jnp.einsum("bth,vh->btv", _q(x, quant), _q(table, quant))


def score(cfg: dict, seed: int, items: list, control: str | None = None
          ) -> dict:
    """Teacher-force ``items`` (``[(prompt ids, served ids)]``) in one
    batched forward at one padded shape.  Returns, over every served
    position, the worst and mean shortfall (in standard deviations of the
    position's logits) of the served token below the reference's best;
    with ``control``, the same for the token the lower precision puts
    first."""
    width = -(-max(len(p) + len(s) for p, s in items) // 64) * 64
    width = min(width, cfg["max_position_embeddings"])
    ids = np.zeros((len(items), width), np.int32)
    served = np.zeros((len(items), width), bool)   # at the PREDICTING position
    for row, (prompt, stream) in enumerate(items):
        n = len(prompt) + len(stream)
        ids[row, :n] = np.concatenate([prompt, stream])
        served[row, len(prompt) - 1:n - 1] = True

    with jax.default_matmul_precision("highest"):
        stacked = jax.jit(lambda key: make_stacked(key, cfg))(seed_key(seed))

        @jax.jit
        def shortfall(stacked, ids):
            logits = forward(stacked, ids, cfg)[:, :-1]
            nxt = jnp.take_along_axis(logits, ids[:, 1:, None], -1)[..., 0]
            return (logits.max(-1) - nxt) / logits.std(-1)

        gaps = np.asarray(shortfall(stacked, jnp.asarray(ids)))
        out = _summary(gaps, served[:, :-1])
        if control:
            @jax.jit
            def control_shortfall(stacked, ids):
                logits = forward(stacked, ids, cfg)[:, :-1]
                low = forward(stacked, ids, cfg, quant=control)[:, :-1]
                pick = jnp.take_along_axis(
                    logits, low.argmax(-1)[..., None], -1)[..., 0]
                return (logits.max(-1) - pick) / logits.std(-1)

            out["control"] = _summary(np.asarray(control_shortfall(
                stacked, jnp.asarray(ids))), served[:, :-1])
    return out


def _summary(gaps: np.ndarray, mask: np.ndarray) -> dict:
    picked = gaps[mask]
    per_stream = [float(g[m].max()) for g, m in zip(gaps, mask)]
    return {"served_gap_sigmas": float(picked.max()),
            "served_gap_mean_sigmas": float(picked.mean()),
            "tokens": int(picked.size), "per_stream_worst": per_stream}
