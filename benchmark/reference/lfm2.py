"""Plain reference for ``"reference": "lfm2"`` configurations: the LFM2-MoE
decoder (LiquidAI ``LFM2-8B-A1B``; ``transformers``' ``modeling_lfm2_moe``)
as one full forward pass in straightforward ``jax.numpy``, float32, with
``default_matmul_precision("highest")``: no cache, no batching, no kernels,
no grouped matmuls.  Written from the equations, not from the program.

RMSNorm everywhere (``norm_eps``, learned scale).  For layer *l*::

    h = x + Op_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

- ``Op`` = short convolution where ``layer_types[l] == "conv"``: ``[b, c,
  v] = split3(u W_in)``; ``z = b * v``; ``s_t = sum_j w[j] * z_{t-(L-1)+j}``
  (depthwise, causal, ``L = conv_L_cache`` taps, zeros before the start);
  ``(c * s) W_out``.  No biases.
- ``Op`` = attention where ``"full_attention"``: q, k, v, out without
  bias; RMSNorm over each head's values of q and of k before the rotation
  (rotate-half pairing, ``rope_theta``, the whole head); grouped K/V heads;
  scale 1/sqrt(head size); causal.
- ``FFN`` = SwiGLU of ``intermediate_size`` for ``l < num_dense_layers``;
  after that ``num_experts`` SwiGLU experts of ``moe_intermediate_size``:
  ``p = sigmoid(u W_g)``; the chosen are the top ``num_experts_per_tok`` of
  ``p + bias`` (the bias only selects); weights ``p[sel] / (sum p[sel] +
  1e-6)``, ``routed_scaling_factor`` 1.  Every expert is computed for every
  token and weighted by 0 where it was not chosen: plain, and with no
  capacity anywhere.

After the last layer one RMSNorm, then the head, tied to the embedding.

It imports nothing of the program and takes nothing the program made.  The
weights are made from the seed LAYER BY LAYER in the type they are served
in (5.4 B parameters at once in float32 would be 21 GB), and ``score``
walks the layers one at a time, making each layer's weights again from the
key and upcasting only that layer (one expert layer in float32 is 1.4 GB).
The program is *given* :func:`make_weights`' tree, whose names follow what
the program's model reads.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.child import seed_key

#: limits of the comparisons, each set from chip readings (my chip runs,
#: PR 28; PERF.md section 2), above the sound runs' largest and below the
#: fp8 control's smallest with room on both sides.  They are wider than
#: gpt2-xl's for a reason the runs print beside them: rounding every matrix
#: product's inputs to bfloat16 in THIS reference changes the chosen experts
#: in 15-18 % of the (expert layer, position) decisions (a near-tie among 32
#: sigmoid scores of randomly initialised routers flips, and each flip
#: moves every later layer), and the token that pass puts first falls 0.59
#: .. 1.44 deviations (worst) and 0.033 .. 0.051 (mean) short of the
#: float32 best over 26 seeds: the stated precision alone costs that much.
#: - ``served_gap_sigmas`` (worst served token): sound 0.94 .. 1.23 over
#:   the 5 seeds the limit was set from (0.74 .. 1.26 over 26 since),
#:   control 4.31 .. 4.99 over 6 seeds;
#: - ``served_gap_mean_sigmas``: sound 0.039 .. 0.067, control 1.63 .. 1.66:
#:   the control fails this one by 5.4 times, sound seeds pass it by 4.5.
LIMITS = {"served_gap_sigmas": 3.0, "served_gap_mean_sigmas": 0.3}


def _kind(cfg: dict, layer: int) -> tuple[str, str]:
    return (cfg["layer_types"][layer],
            "dense" if layer < cfg["num_dense_layers"] else "experts")


def _layer_specs(cfg: dict, kind: tuple[str, str]) -> list[tuple]:
    """(path within a layer, shape, std | "scale", float32?)."""
    h = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    std = cfg.get("init_std", 0.02)
    out_std = std / math.sqrt(2 * cfg["num_hidden_layers"])
    specs = [(("ln1", "scale"), (h,), "scale", False),
             (("ln2", "scale"), (h,), "scale", False)]
    if kind[0] == "conv":
        specs += [(("conv", "in_proj", "kernel"), (h, 3 * h), std, False),
                  (("conv", "conv_kernel"), (cfg["conv_L_cache"], h),
                   cfg.get("conv_std", 0.5), False),
                  (("conv", "out_proj", "kernel"), (h, h), out_std, False)]
    else:
        specs += [(("attn", "query", "kernel"), (h, h), std, False),
                  (("attn", "key", "kernel"), (h, kv * d), std, False),
                  (("attn", "value", "kernel"), (h, kv * d), std, False),
                  (("attn", "out", "kernel"), (h, h), out_std, False),
                  (("attn", "q_norm", "scale"), (d,), "scale", False),
                  (("attn", "k_norm", "scale"), (d,), "scale", False)]
    if kind[1] == "dense":
        i = cfg["intermediate_size"]
        specs += [(("mlp_gate", "kernel"), (h, i), std, False),
                  (("mlp_up", "kernel"), (h, i), std, False),
                  (("mlp_down", "kernel"), (i, h), out_std, False)]
    else:
        e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        # the router and its bias stay float32 (a buffer and a small
        # matrix; the program routes in float32 too).  The bias is drawn
        # wide enough to change some selections (the 4th and 5th of 32
        # scores lie ~0.02 apart) and no wider: at 0.05 some experts were
        # persistently unpopular, the experts a decode step touched varied
        # 422 .. 427 of 448 between seeds, and the rate followed them
        # (0.06 ms an expert, 0.55 % over six seeds; PERF.md section 6)
        specs += [(("moe", "router"), (h, e), std, True),
                  (("moe", "expert_bias"), (e,),
                   cfg.get("expert_bias_std", 0.01), True),
                  (("moe", "w_gate"), (e, h, f), std, False),
                  (("moe", "w_up"), (e, h, f), std, False),
                  (("moe", "w_down"), (e, f, h), out_std, False)]
    return specs


def _draw(key, i: int, shape, std, dtype):
    noise = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    if std == "scale":
        return (1.0 + 0.02 * noise).astype(dtype)
    return (std * noise).astype(dtype)


@partial(jax.jit, static_argnames=("kind", "cfg_items"))
def _make_layer(key, layer, *, kind, cfg_items):
    cfg = dict(cfg_items)
    key = jax.random.fold_in(key, 1000 + layer)
    dtype = jnp.dtype(cfg["dtype"])
    tree: dict = {}
    for i, (path, shape, std, f32) in enumerate(_layer_specs(cfg, kind)):
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = _draw(key, i, shape, std,
                               jnp.float32 if f32 else dtype)
    return tree


def _hashable(cfg: dict) -> tuple:
    """The configuration's plain values as a static argument of a jitted
    maker (its notes, dicts of prose, are left out)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, list))))


def make_layer(key, cfg: dict, layer: int) -> dict:
    """Layer ``layer``'s weights from the key: one compiled program per
    KIND of layer (operator x feed-forward), the layer's number an
    argument."""
    return _make_layer(key, jnp.asarray(layer, jnp.int32),
                       kind=_kind(cfg, layer), cfg_items=_hashable(cfg))


@partial(jax.jit, static_argnames=("cfg_items",))
def _make_top(key, *, cfg_items):
    cfg = dict(cfg_items)
    dtype = jnp.dtype(cfg["dtype"])
    h = cfg["hidden_size"]
    return {"tok_emb": {"embedding": _draw(key, 100, (cfg["vocab_size"], h),
                                           cfg.get("init_std", 0.02), dtype)},
            "ln_f": {"scale": _draw(key, 102, (h,), "scale", dtype)}}


def make_weights(key, cfg: dict) -> dict:
    """The tree the program's model reads: ``layer_<i>/...``,
    ``tok_emb/embedding``, ``ln_f``.  NOT to be called under one
    ``jax.jit``: it makes the layers one compiled call at a time, so that
    what is live while a leaf is drawn is that layer's float32 noise and
    never the model's."""
    if not hasattr(key, "dtype"):
        key = seed_key(key)
    params = _make_top(key, cfg_items=_hashable(cfg))
    for layer in range(cfg["num_hidden_layers"]):
        params[f"layer_{layer}"] = make_layer(key, cfg, layer)
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


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _mm(x, w, quant):
    return _q(x, quant) @ _q(w, quant)


def _rope(x, theta):
    """``x [B, T, heads, D]``: rotate-half pairing over the whole head."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def short_conv(u, p, cfg, quant=None):
    T, L = u.shape[1], cfg["conv_L_cache"]
    b, c, v = jnp.split(_mm(u, p["in_proj"]["kernel"], quant), 3, axis=-1)
    z = jnp.pad(b * v, ((0, 0), (L - 1, 0), (0, 0)))
    s = sum(p["conv_kernel"][j] * z[:, j:j + T] for j in range(L))
    return _mm(c * s, p["out_proj"]["kernel"], quant)


def attention(u, p, cfg, quant=None):
    B, T, h = u.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, eps = h // H, cfg["norm_eps"]
    q = _mm(u, p["query"]["kernel"], quant).reshape(B, T, H, D)
    k = _mm(u, p["key"]["kernel"], quant).reshape(B, T, Hkv, D)
    v = _mm(u, p["value"]["kernel"], quant).reshape(B, T, Hkv, D)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), cfg["rope_theta"])
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), cfg["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", _q(q, quant), _q(k, quant)) \
        / math.sqrt(D)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    a = jax.nn.softmax(jnp.where(causal[None, None], s, -1e30), axis=-1)
    ctx = jnp.einsum("bhts,bshd->bthd", _q(a, quant), _q(v, quant))
    return _mm(ctx.reshape(B, T, H * D), p["out"]["kernel"], quant)


def swiglu(u, gate, up, down, quant=None):
    return _mm(jax.nn.silu(_mm(u, gate, quant)) * _mm(u, up, quant), down,
               quant)


def route(u, p, cfg):
    """``(chosen [..., k], gate [..., E])``: the gate holds each chosen
    expert's weight and 0 elsewhere.  Never quantised: the router is
    float32 in the configuration."""
    prob = jax.nn.sigmoid(u @ p["router"])
    _, sel = jax.lax.top_k(prob + p["expert_bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(prob, sel, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    gate = jnp.sum(jax.nn.one_hot(sel, cfg["num_experts"]) * w[..., None],
                   axis=-2)
    return sel, gate


def experts(u, p, cfg, quant=None):
    sel, gate = route(u, p, cfg)

    def one(acc, e):
        w1, w3, w2, g = e
        return acc + g[..., None] * swiglu(u, w1, w3, w2, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (p["w_gate"], p["w_up"], p["w_down"],
                           jnp.moveaxis(gate, -1, 0)))
    return out, jnp.sort(sel, axis=-1)


def layer_forward(x, p, cfg: dict, kind: tuple[str, str], quant=None):
    """One block on ``x [B, T, H]`` (float32); ``p`` in any type.  Returns
    ``(y, chosen experts [B, T, k] sorted | None)``."""
    p = _f32(p)
    eps = cfg["norm_eps"]
    u = _rms(x, p["ln1"]["scale"], eps)
    h = x + (short_conv(u, p["conv"], cfg, quant) if kind[0] == "conv"
             else attention(u, p["attn"], cfg, quant))
    u = _rms(h, p["ln2"]["scale"], eps)
    if kind[1] == "dense":
        return h + swiglu(u, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                          p["mlp_down"]["kernel"], quant), None
    out, sel = experts(u, p["moe"], cfg, quant)
    return h + out, sel


def forward(params: dict, ids, cfg: dict, quant=None):
    """Logits ``[B, T, V]`` (float32) of the full causal forward pass over
    a whole tree of weights (the CPU tests' entry; ``score`` walks the
    layers itself)."""
    table = params["tok_emb"]["embedding"].astype(jnp.float32)
    x = table[ids]
    for layer in range(cfg["num_hidden_layers"]):
        x, _ = layer_forward(x, params[f"layer_{layer}"], cfg,
                             _kind(cfg, layer), quant)
    x = _rms(x, params["ln_f"]["scale"].astype(jnp.float32), cfg["norm_eps"])
    return jnp.einsum("bth,vh->btv", _q(x, quant), _q(table, quant))


def _walk(key, ids, cfg: dict, quant=None):
    """``(logits, [chosen experts per expert layer])``, the weights made
    and dropped one layer at a time."""
    items = _hashable(cfg)
    top = _make_top(key, cfg_items=items)
    table = top["tok_emb"]["embedding"]

    @partial(jax.jit, static_argnames=("kind",))
    def step(x, p, *, kind):
        return layer_forward(x, p, cfg, kind, quant)

    x = jax.jit(lambda t, i: t.astype(jnp.float32)[i])(table, ids)
    chosen = []
    for layer in range(cfg["num_hidden_layers"]):
        p = make_layer(key, cfg, layer)
        x, sel = step(x, p, kind=_kind(cfg, layer))
        jax.block_until_ready(x)      # one layer's float32 at a time
        del p
        if sel is not None:
            chosen.append(sel)

    @jax.jit
    def head(x, table, scale):
        x = _rms(x, scale.astype(jnp.float32), cfg["norm_eps"])
        t = table.astype(jnp.float32)
        return jnp.einsum("bth,vh->btv", _q(x, quant), _q(t, quant))

    return head(x, table, top["ln_f"]["scale"]), chosen


def score(cfg: dict, seed: int, items: list, control: str | None = None
          ) -> dict:
    """Teacher-force ``items`` (``[(prompt ids, served ids)]``) in one
    batched forward at one padded shape.  Returns, over every served
    position, the worst and mean shortfall (in standard deviations of the
    position's logits) of the served token below the reference's best;
    with ``control``, the same for the token the lower precision puts
    first.  ``routing_differs_share`` is the share of (expert layer,
    served position) routing decisions whose chosen set differs between
    this float32 pass and the same pass with every matrix product's inputs
    rounded to the configuration's own type: what a served bfloat16
    near-tie does to the choice of experts.  ``own_precision`` is that
    pass's shortfall (of the token IT puts first): what the stated
    precision alone costs, which a sound served stream reads about."""
    width = -(-max(len(p) + len(s) for p, s in items) // 64) * 64
    width = min(width, cfg["max_position_embeddings"])
    ids = np.zeros((len(items), width), np.int32)
    served = np.zeros((len(items), width), bool)   # at the PREDICTING position
    for row, (prompt, stream) in enumerate(items):
        n = len(prompt) + len(stream)
        ids[row, :n] = np.concatenate([prompt, stream])
        served[row, len(prompt) - 1:n - 1] = True
    key = seed_key(seed)
    dev_ids = jnp.asarray(ids)

    @jax.jit
    def served_shortfall(logits, ids):
        logits = logits[:, :-1]
        got = jnp.take_along_axis(logits, ids[:, 1:, None], -1)[..., 0]
        return (logits.max(-1) - got) / logits.std(-1)

    @jax.jit
    def control_shortfall(logits, low):
        logits = logits[:, :-1]
        pick = jnp.take_along_axis(
            logits, low[:, :-1].argmax(-1)[..., None], -1)[..., 0]
        return (logits.max(-1) - pick) / logits.std(-1)

    with jax.default_matmul_precision("highest"):
        logits, chosen = _walk(key, dev_ids, cfg)
        out = _summary(np.asarray(served_shortfall(logits, dev_ids)),
                       served[:, :-1])
        if chosen:
            own_logits, own = _walk(key, dev_ids, cfg, quant="bf16")
            differ = np.stack([np.asarray((a != b).any(-1))
                               for a, b in zip(chosen, own)])
            out["routing_differs_share"] = float(differ[:, served].mean())
            out["own_precision"] = _summary(
                np.asarray(control_shortfall(logits, own_logits)),
                served[:, :-1])
            del own, own_logits
        if control:
            low, _ = _walk(key, dev_ids, cfg, quant=control)
            out["control"] = _summary(
                np.asarray(control_shortfall(logits, low)), served[:, :-1])
    return out


def _summary(gaps: np.ndarray, mask: np.ndarray) -> dict:
    picked = gaps[mask]
    per_stream = [float(g[m].max()) for g, m in zip(gaps, mask)]
    return {"served_gap_sigmas": float(picked.max()),
            "served_gap_mean_sigmas": float(picked.mean()),
            "tokens": int(picked.size), "per_stream_worst": per_stream}
