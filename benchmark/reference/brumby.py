"""Plain reference for ``"reference": "brumby"`` configurations: the
Brumby-14B-Base decoder (Manifest AI; a Qwen3-14B-shaped decoder in which
every attention layer is a POWER RETENTION layer, arXiv:2507.04239) as one
full forward pass in straightforward ``jax.numpy``, float32, with
``default_matmul_precision("highest")``: the ATTENTION form, no cache, no
state, no kernel, no feature map.  Written from the equations, not from the
program.

For token t with input x_t in R^hidden, u = RMSNorm(x_t):

- q_{t,h} = RoPE_t(RMSNorm_128((W_q u)_h)), h = 0..39; k_{t,m} =
  RoPE_t(RMSNorm_128((W_k u)_m)), v_{t,m} = (W_v u)_m, g_{t,m} = log
  sigma((W_g u)_m) <= 0, m = 0..7; query head h uses key/value head m(h) =
  h // 5.  No biases.
- attention form (this file): a_{t,j,h} = exp(sum_{l=j+1..t} g_{l,m(h)}) *
  (q_{t,h} . k_{j,m(h)} / sqrt(128))^p for j <= t, p = 2; y_{t,h} = sum_j
  a_{t,j,h} v_{j,m(h)} / (sum_j a_{t,j,h} + eps).
- recurrent form (the program): S_{t,m} = e^{g_{t,m}} S_{t-1,m} +
  phi(k_{t,m}) v_{t,m}^T in R^{D x 128}, z_{t,m} = e^{g_{t,m}} z_{t-1,m} +
  phi(k_{t,m}); y_{t,h} = phi(q_{t,h})^T S_{t,m(h)} / (phi(q_{t,h})^T
  z_{t,m(h)} + eps), where phi: R^128 -> R^D is any map with phi(a) . phi(b)
  = (a . b)^2 / 128 (the symmetric square, off-diagonal pairs weighted sqrt
  2, D = 8256; a tiled variant with a few per cent of padding is the
  program's choice).  Prefill runs the chunked form: within a chunk the
  attention form, across chunks the state.  The two forms are the same
  function; that is what the tests check.
- block: x' = x + W_o . concat_h(y_{t,h}); out = x' + W_down(silu(W_gate n)
  * W_up n), n = RMSNorm(x'); final RMSNorm, then the UNTIED head.

It imports nothing of the program and takes nothing the program made.  The
weights are made from the seed LAYER BY LAYER in the type they are served
in (4.2 B parameters at once in float32 would be 17 GB), ``score`` walks
the layers one at a time, upcasting only that layer, attends in blocks of
query positions and multiplies by the head in blocks of positions (the
logits of four streams of 1152 tokens over 151936 words are 2.8 GB).  The
program is *given* :func:`make_weights`' tree, whose names follow what the
program's model reads.  The helpers that are no part of this model's
mathematics (seeded draws, the lower-precision rounding, RMSNorm, the
rotation, SwiGLU, the summary) are ``reference/lfm2.py``'s.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.child import seed_key
from benchmark.reference import lfm2 as _base

#: limits of the comparisons, set from chip readings (my chip runs, PR 32;
#: PERF.md section 2), above the sound runs' largest and below the fp8
#: control's smallest with room on both sides
LIMITS = {"served_gap_sigmas": 0.6, "served_gap_mean_sigmas": 0.02}

#: query positions attended at once, and positions multiplied by the head
#: at once
QUERY_BLOCK = 256
HEAD_BLOCK = 128


def _layer_specs(cfg: dict) -> list[tuple]:
    """(path within a layer, shape, std | "scale")."""
    h = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, i = cfg["head_dim"], cfg["intermediate_size"]
    std = cfg.get("init_std", 0.02)
    out_std = std / math.sqrt(2 * cfg["num_hidden_layers"])
    return [(("ln1", "scale"), (h,), "scale"),
            (("ln2", "scale"), (h,), "scale"),
            (("ret", "query", "kernel"), (h, heads * d), std),
            (("ret", "key", "kernel"), (h, kv * d), std),
            (("ret", "value", "kernel"), (h, kv * d), std),
            (("ret", "gate", "kernel"), (h, kv), cfg.get("gate_std", std)),
            (("ret", "out", "kernel"), (heads * d, h), out_std),
            (("ret", "q_norm", "scale"), (d,), "scale"),
            (("ret", "k_norm", "scale"), (d,), "scale"),
            (("mlp_gate", "kernel"), (h, i), std),
            (("mlp_up", "kernel"), (h, i), std),
            (("mlp_down", "kernel"), (i, h), out_std)]


@partial(jax.jit, static_argnames=("cfg_items",))
def _make_layer(key, layer, *, cfg_items):
    cfg = dict(cfg_items)
    key = jax.random.fold_in(key, 1000 + layer)
    dtype = jnp.dtype(cfg["dtype"])
    tree: dict = {}
    for i, (path, shape, std) in enumerate(_layer_specs(cfg)):
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = _base._draw(key, i, shape, std, dtype)
    return tree


def make_layer(key, cfg: dict, layer: int) -> dict:
    """Layer ``layer``'s weights from the key: one compiled program, the
    layer's number an argument."""
    return _make_layer(key, jnp.asarray(layer, jnp.int32),
                       cfg_items=_base._hashable(cfg))


@partial(jax.jit, static_argnames=("cfg_items", "what"))
def _make_top(key, *, cfg_items, what):
    """One of the three leaves outside the layers (the embedding and the
    head are 1.56 GB each and their float32 noise twice that: one at a
    time)."""
    cfg = dict(cfg_items)
    dtype = jnp.dtype(cfg["dtype"])
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    std = cfg.get("init_std", 0.02)
    if what == "tok_emb":
        return _base._draw(key, 100, (v, h), std, dtype)
    if what == "lm_head":
        return _base._draw(key, 101, (h, v), std, dtype)
    return _base._draw(key, 102, (h,), "scale", dtype)


def make_top(key, cfg: dict) -> dict:
    items = _base._hashable(cfg)
    return {"tok_emb": {"embedding": _make_top(key, cfg_items=items,
                                               what="tok_emb")},
            "lm_head": _make_top(key, cfg_items=items, what="lm_head"),
            "ln_f": {"scale": _make_top(key, cfg_items=items, what="ln_f")}}


def make_weights(key, cfg: dict) -> dict:
    """The tree the program's model reads: ``layer_<i>/...``,
    ``tok_emb/embedding``, ``lm_head`` (``[hidden, vocab]``), ``ln_f``.
    NOT to be called under one ``jax.jit``: the layers are made one
    compiled call at a time."""
    if not hasattr(key, "dtype"):
        key = seed_key(key)
    params = make_top(key, cfg)
    for layer in range(cfg["num_hidden_layers"]):
        params[f"layer_{layer}"] = make_layer(key, cfg, layer)
    return params


# ------------------------------------------------------------------ forward

def retention(u, p, cfg, quant=None):
    """The attention form of one power-retention layer on ``u [B, T,
    hidden]`` (float32), in blocks of query positions."""
    B, T, _ = u.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, eps, power = cfg["head_dim"], cfg["rms_norm_eps"], cfg["power"]
    q = _base._mm(u, p["query"]["kernel"], quant).reshape(B, T, H, D)
    k = _base._mm(u, p["key"]["kernel"], quant).reshape(B, T, Hkv, D)
    v = _base._mm(u, p["value"]["kernel"], quant).reshape(B, T, Hkv, D)
    g = jax.nn.log_sigmoid(_base._mm(u, p["gate"]["kernel"], quant))
    q = _base._rope(_base._rms(q, p["q_norm"]["scale"], eps),
                    cfg["rope_theta"])
    k = _base._rope(_base._rms(k, p["k_norm"]["scale"], eps),
                    cfg["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)          # head h uses m(h) = h // G
    v = jnp.repeat(v, H // Hkv, axis=2)
    # c_t = sum_{l<=t} g_l, so sum_{l=j+1..t} g_l = c_t - c_j
    c = jnp.repeat(jnp.cumsum(g, axis=1), H // Hkv, axis=2)   # [B, T, H]
    c = c.transpose(0, 2, 1)                                  # [B, H, T]
    kq, vq = _base._q(k, quant), _base._q(v, quant)
    out = []
    for lo in range(0, T, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, T)
        s = jnp.einsum("bthd,bshd->bhts", _base._q(q[:, lo:hi], quant), kq) \
            / math.sqrt(D)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(T)[None, :]
        decay = jnp.exp(jnp.where(causal, c[:, :, lo:hi, None]
                                  - c[:, :, None, :], 0.0))
        a = jnp.where(causal, decay * s ** power, 0.0)
        num = jnp.einsum("bhts,bshd->bthd", _base._q(a, quant), vq)
        den = jnp.sum(a, axis=-1).transpose(0, 2, 1)          # [B, t, H]
        out.append(num / (den[..., None] + cfg["retention_eps"]))
    y = jnp.concatenate(out, axis=1).reshape(B, T, H * D)
    return _base._mm(y, p["out"]["kernel"], quant)


def layer_forward(x, p, cfg: dict, quant=None):
    """One block on ``x [B, T, hidden]`` (float32); ``p`` in any type."""
    p = _base._f32(p)
    eps = cfg["rms_norm_eps"]
    h = x + retention(_base._rms(x, p["ln1"]["scale"], eps), p["ret"], cfg,
                      quant)
    n = _base._rms(h, p["ln2"]["scale"], eps)
    return h + _base.swiglu(n, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                            p["mlp_down"]["kernel"], quant)


def hidden_after(params: dict, ids, cfg: dict, layers: int, quant=None):
    """The residual stream ``[B, T, hidden]`` after the first ``layers``
    layers of a whole tree of weights (no final norm)."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[ids]
    for layer in range(layers):
        x = layer_forward(x, params[f"layer_{layer}"], cfg, quant)
    return x


def forward(params: dict, ids, cfg: dict, quant=None):
    """Logits ``[B, T, V]`` (float32) of the full causal forward pass over
    a whole tree of weights (the CPU tests' entry; ``score`` walks the
    layers itself)."""
    x = hidden_after(params, ids, cfg, cfg["num_hidden_layers"], quant)
    x = _base._rms(x, params["ln_f"]["scale"].astype(jnp.float32),
                   cfg["rms_norm_eps"])
    return _base._mm(x, params["lm_head"].astype(jnp.float32), quant)


def _walk(key, ids, cfg: dict, quant=None):
    """``(final hidden states after the last norm [B, T, hidden], head)``,
    the weights made and dropped one layer at a time."""
    items = _base._hashable(cfg)
    table = _make_top(key, cfg_items=items, what="tok_emb")
    x = jax.jit(lambda t, i: t.astype(jnp.float32)[i])(table, ids)
    del table
    step = jax.jit(lambda x, p: layer_forward(x, p, cfg, quant))
    for layer in range(cfg["num_hidden_layers"]):
        p = make_layer(key, cfg, layer)
        x = step(x, p)
        jax.block_until_ready(x)      # one layer's float32 at a time
        del p
    scale = _make_top(key, cfg_items=items, what="ln_f")
    x = jax.jit(lambda x, s: _base._rms(x, s.astype(jnp.float32),
                                        cfg["rms_norm_eps"]))(x, scale)
    return x, _make_top(key, cfg_items=items, what="lm_head")


def _head_blocks(x, head, quant, reduce):
    """``reduce(logits block [B, n, V], lo, hi)`` over blocks of positions;
    its results concatenated along the positions."""
    block = jax.jit(lambda xb, w: _base._mm(xb, w.astype(jnp.float32),
                                            quant))
    parts = [reduce(block(x[:, lo:lo + HEAD_BLOCK], head), lo,
                    min(lo + HEAD_BLOCK, x.shape[1]))
             for lo in range(0, x.shape[1], HEAD_BLOCK)]
    return jax.tree.map(lambda *a: np.concatenate(a, axis=1), *parts)


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
    key = seed_key(seed)
    dev_ids = jnp.asarray(ids)
    nxt = np.concatenate([ids[:, 1:], ids[:, :1]], axis=1)    # [B, T]

    @jax.jit
    def shortfall(logits, pick):
        got = jnp.take_along_axis(logits, pick[..., None], -1)[..., 0]
        return (logits.max(-1) - got) / logits.std(-1)

    with jax.default_matmul_precision("highest"):
        low_first = None
        if control:
            x, head = _walk(key, dev_ids, cfg, quant=control)
            low_first = _head_blocks(
                x, head, control,
                lambda lg, lo, hi: np.asarray(lg.argmax(-1)))
            del x, head
        x, head = _walk(key, dev_ids, cfg)

        def reduce(lg, lo, hi):
            out = {"served": np.asarray(shortfall(lg,
                                                  jnp.asarray(nxt[:, lo:hi])))}
            if low_first is not None:
                out["control"] = np.asarray(shortfall(
                    lg, jnp.asarray(low_first[:, lo:hi])))
            return out

        gaps = _head_blocks(x, head, None, reduce)
    out = _base._summary(gaps["served"][:, :-1], served[:, :-1])
    if control:
        out["control"] = _base._summary(gaps["control"][:, :-1],
                                        served[:, :-1])
    return out
