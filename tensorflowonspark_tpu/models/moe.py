"""The expert layer inside a served decoder block (``GPTConfig.num_experts``).

``SparseMoE`` is the LFM2-MoE feed-forward: a sigmoid router whose
selection (and only the selection) is shifted by a per-expert bias, the
top ``num_experts_per_tok`` experts per token with weights normalised over
the chosen, and SwiGLU experts of width ``moe_intermediate_size``::

    p      = sigmoid(W_g u)                          # float32, [E]
    sel    = top_k(p + bias)                         # bias selects only
    weight = p[sel] / (sum(p[sel]) + 1e-6)
    FFN(u) = sum_k weight_k * W2_e(silu(W1_e u) * W3_e u),  e = sel_k

It is DROPLESS: the ``tokens x k`` assignments are sorted by expert and the
three matrix products run as grouped matmuls over the sorted rows (one
group per expert, of whatever size the router made it), so no assignment is
lost to a capacity.  On one TPU the grouped products are this repo's kernel
(``ops.grouped_matmul``, device operation ``tfos_grouped_matmul``: gate and
up in one call with the activation, down in another; each touched expert's
weights streamed once a row tile, an untouched expert's never read);
elsewhere they are ``jax.lax.ragged_dot``.  :func:`streams_experts_once` is
the rule, decided at trace time from what the step can see, and both paths
compute the same products from the same parameters.  All experts live on
this chip.  ``parallel/moe.py`` is another layer (softmax router, capacity,
``shard_map`` over ``ep``) and shares nothing with this one.

The router's logits, sigmoid and top-k are float32 at full matmul
precision (a bfloat16 near-tie would pick another expert than the float32
reference); the expert products are ``cfg.dtype`` with float32
accumulation, as ``nn.Dense`` does them.

Named scopes (op metadata; docs/observability.md "Profiler spans"):
``moe/router``, ``moe/dispatch``, ``moe/experts``, ``moe/combine``.  Each
call also sows three int32 into the ``moe_stats`` collection (read by the
batcher with the tokens, ``models/serving.py``): assignments made, the
busiest expert's assignments, experts that got at least one.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models.bert import _context_mesh
from tensorflowonspark_tpu.ops.flash_attention import _on_tpu
from tensorflowonspark_tpu.ops.grouped_matmul import (grouped_dot,
                                                      grouped_swiglu)

#: the collection ``SparseMoE`` sows its per-call counts into
STATS = "moe_stats"

#: ``tfos_grouped_matmul`` calls of one expert layer where the kernel runs:
#: gate and up with the activation, then down
KERNEL_CALLS_PER_LAYER = 2


def streams_experts_once() -> bool:
    """Whether an expert layer traced here runs its grouped products
    through ``ops.grouped_matmul`` and not through ``jax.lax.ragged_dot``:
    the backend is the TPU the kernel is written for (elsewhere it would
    run under the Pallas interpreter) and no mesh of several devices is in
    scope (a ``pallas_call`` is not partitioned).  The number of
    assignments does not enter: on the chip the kernel is the faster at a
    decode step's few rows an expert and at a prefill's hundreds alike
    (PERF.md, PR 34), its tiles chosen from the shapes it is called
    with."""
    if not _on_tpu():
        return False
    mesh = _context_mesh()
    return mesh is None or mesh.size == 1


def grouped_matmul_calls(cfg) -> int:
    """``tfos_grouped_matmul`` calls in one forward of ``cfg``'s model
    traced here: what ``tfos_replica_grouped_matmul_calls_total`` adds per
    dispatched step (0 = the ``ragged_dot`` path, or no expert layer)."""
    return KERNEL_CALLS_PER_LAYER * cfg.num_expert_layers \
        if cfg.num_experts and streams_experts_once() else 0


def route(u, router, bias, k: int):
    """``u [N, H] -> (experts [N, k] int32, weights [N, k] float32)``."""
    logits = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(p + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(p, sel, axis=-1)
    return sel.astype(jnp.int32), w / (jnp.sum(w, -1, keepdims=True) + 1e-6)


class SparseMoE(nn.Module):
    cfg: "GPTConfig"  # noqa: F821 (models.gpt imports this module lazily)

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        E, K, F = (cfg.num_experts, cfg.num_experts_per_tok,
                   cfg.moe_intermediate_size)
        B, T, H = x.shape
        N = B * T
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (H, E))
        bias = self.param("expert_bias", nn.initializers.zeros, (E,))
        w_gate = self.param("w_gate", init, (E, H, F))
        w_up = self.param("w_up", init, (E, H, F))
        w_down = self.param("w_down", init, (E, F, H))
        u = x.reshape(N, H)
        with jax.named_scope("router"):
            sel, weight = route(u, router, bias, K)
        with jax.named_scope("dispatch"):
            flat = sel.reshape(N * K)
            order = jnp.argsort(flat, stable=True)
            counts = jnp.sum(flat[:, None] == jnp.arange(E)[None, :],
                             axis=0, dtype=jnp.int32)
            rows = u[order // K].astype(cfg.dtype)           # [N*K, H]
        self.sow(STATS, "counts", jnp.stack(
            [jnp.asarray(N * K, jnp.int32), jnp.max(counts),
             jnp.sum(counts > 0, dtype=jnp.int32)]))
        with jax.named_scope("experts"):
            w_gate, w_up, w_down = (w.astype(cfg.dtype)
                                    for w in (w_gate, w_up, w_down))
            if streams_experts_once():
                h = grouped_swiglu(rows, w_gate, w_up, counts,
                                   out_dtype=cfg.dtype)
                y = grouped_dot(h, w_down, counts)           # float32
            else:
                def grouped(lhs, rhs):
                    return jax.lax.ragged_dot(
                        lhs, rhs, counts,
                        preferred_element_type=jnp.float32)

                h = (nn.silu(grouped(rows, w_gate))
                     * grouped(rows, w_up)).astype(cfg.dtype)
                y = grouped(h, w_down)                       # float32
        with jax.named_scope("combine"):
            back = jnp.argsort(order)      # assignment n*K + k -> its row
            y = y[back].reshape(N, K, H) * weight[:, :, None]
            return jnp.sum(y, axis=1).astype(cfg.dtype).reshape(B, T, H)
