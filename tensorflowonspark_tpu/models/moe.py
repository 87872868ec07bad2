"""The expert layer inside a served decoder block (``GPTConfig.num_experts``).

``SparseMoE`` is the feed-forward of LFM2-MoE and of Nemotron-H: a sigmoid
router whose selection (and only the selection) is shifted by a per-expert
bias, the top ``num_experts_per_tok`` experts per token with weights
normalised over the chosen and scaled, and experts of width
``moe_intermediate_size``, gated (``moe_activation`` ``"swiglu"``, three
matrices) or not (``"relu2"``, two)::

    p      = sigmoid(W_g u)                          # float32, [E]
    sel    = top_k(p + bias)                         # bias selects only
    weight = routed_scaling_factor * p[sel] / (sum(p[sel]) + 1e-6)
    FFN(u) = sum_{k: sel_k held} weight_k * Expert_{sel_k}(u)  [+ Shared(u)]
    Expert(u) = W2 (silu(W1 u) * W3 u)   |   W2 relu(W1 u)**2

THE EXPERTS HELD.  The router scores all ``num_experts``; this chip holds
the weights of ``experts_held = (first, count)`` of them (None = all), the
chip's share of a deployment that divides every expert layer over several
chips.  An assignment to an expert that is not held is dropped from the
sorted rows, on both paths below, and what that expert would have added is
left out of the layer's output; there is no exchange and nothing stands in
for the absent chips.  A SHARED expert (``moe_shared_intermediate_size``),
the same form at another width, is computed for every token and added
unweighted: every chip of the deployment computes it alike.

It is DROPLESS among the held: the ``tokens x k`` assignments are sorted by
expert (those to absent experts last) and the matrix products run as
grouped matmuls over the sorted rows (one group per held expert, of
whatever size the router made it), so no assignment is lost to a
capacity.  On one TPU the grouped products are this repo's kernel
(``ops.grouped_matmul``, device operation ``tfos_grouped_matmul``: up (and
gate) in one call with the activation, down in another; each touched
expert's weights streamed once a row tile, an untouched expert's never
read); elsewhere they are ``jax.lax.ragged_dot``.
:func:`streams_experts_once` is the rule, decided at trace time from what
the step can see, and both paths compute the same products from the same
parameters.  ``parallel/moe.py`` is another layer (softmax router,
capacity, ``shard_map`` over ``ep``) and shares nothing with this one.

The router's logits, sigmoid and top-k are float32 at full matmul
precision (a bfloat16 near-tie would pick another expert than the float32
reference); the expert products are ``cfg.dtype`` with float32
accumulation, as ``nn.Dense`` does them.

Named scopes (op metadata; docs/observability.md "Profiler spans"):
``moe/router``, ``moe/dispatch``, ``moe/experts``, ``moe/combine``,
``moe/shared``.  Each call also sows :data:`STATS_PER_LAYER` int32 into the
``moe_stats`` collection (read by the batcher with the tokens,
``models/serving.py``): assignments made, the busiest HELD expert's
assignments, held experts that got at least one, assignments that fell to
held experts.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models.bert import _context_mesh
from tensorflowonspark_tpu.ops.flash_attention import _on_tpu
from tensorflowonspark_tpu.ops.grouped_matmul import (grouped_dot,
                                                      grouped_relu2,
                                                      grouped_swiglu)

#: the collection ``SparseMoE`` sows its per-call counts into
STATS = "moe_stats"

#: int32 counts one call sows: assignments made, the busiest held expert's,
#: held experts touched, assignments to held experts
STATS_PER_LAYER = 4

#: ``tfos_grouped_matmul`` calls of one expert layer where the kernel runs:
#: up (and gate) with the activation, then down
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


def route(u, router, bias, k: int, scale: float = 1.0):
    """``u [N, H] -> (experts [N, k] int32, weights [N, k] float32)``."""
    logits = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(p + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(p, sel, axis=-1)
    return sel.astype(jnp.int32), \
        scale * w / (jnp.sum(w, -1, keepdims=True) + 1e-6)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


class SparseMoE(nn.Module):
    cfg: "GPTConfig"  # noqa: F821 (models.gpt imports this module lazily)

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        E, K, F = (cfg.num_experts, cfg.num_experts_per_tok,
                   cfg.moe_intermediate_size)
        first, held = cfg.experts_held or (0, E)
        gated = cfg.moe_activation == "swiglu"
        B, T, H = x.shape
        N = B * T
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (H, E))
        bias = self.param("expert_bias", nn.initializers.zeros, (E,))
        # the first matrices as the configuration says they are stored
        # (``GPTConfig.moe_up_transposed``): the hidden axis last where the
        # width is not whole lane tiles
        up_t = cfg.moe_up_transposed
        first_shape = (held, F, H) if up_t else (held, H, F)
        w_gate = self.param("w_gate", init, first_shape) if gated else None
        w_up = self.param("w_up", init, first_shape)
        w_down = self.param("w_down", init, (held, F, H))
        u = x.reshape(N, H)
        with jax.named_scope("router"):
            sel, weight = route(u, router, bias, K,
                                cfg.routed_scaling_factor)
        with jax.named_scope("dispatch"):
            # an assignment to an expert this chip does not hold sorts
            # after every held expert's: past ``sum(counts)``, where both
            # grouped products give zeros
            local = sel.reshape(N * K) - first
            flat = jnp.where((local >= 0) & (local < held), local, held)
            order = jnp.argsort(flat, stable=True)
            counts = jnp.sum(flat[:, None] == jnp.arange(held)[None, :],
                             axis=0, dtype=jnp.int32)
            rows = u[order // K].astype(cfg.dtype)           # [N*K, H]
        self.sow(STATS, "counts", jnp.stack(
            [jnp.asarray(N * K, jnp.int32), jnp.max(counts),
             jnp.sum(counts > 0, dtype=jnp.int32), jnp.sum(counts)]))
        with jax.named_scope("experts"):
            w_up, w_down = w_up.astype(cfg.dtype), w_down.astype(cfg.dtype)
            if gated:
                w_gate = w_gate.astype(cfg.dtype)
            if streams_experts_once():
                h = grouped_swiglu(rows, w_gate, w_up, counts,
                                   out_dtype=cfg.dtype, transposed=up_t) \
                    if gated else grouped_relu2(
                        rows, w_up, counts, out_dtype=cfg.dtype,
                        transposed=up_t)
                y = grouped_dot(h, w_down, counts)           # float32
            else:
                def grouped(lhs, rhs, transposed=False):
                    return jax.lax.ragged_dot(
                        lhs, jnp.swapaxes(rhs, 1, 2) if transposed else rhs,
                        counts, preferred_element_type=jnp.float32)

                h = nn.silu(grouped(rows, w_gate, up_t)) \
                    * grouped(rows, w_up, up_t) if gated \
                    else _relu2(grouped(rows, w_up, up_t))
                y = grouped(h.astype(cfg.dtype), w_down)     # float32
        with jax.named_scope("combine"):
            back = jnp.argsort(order)      # assignment n*K + k -> its row
            y = y[back].reshape(N, K, H) * weight[:, :, None]
            y = jnp.sum(y, axis=1)
        if cfg.moe_shared_intermediate_size is not None:
            with jax.named_scope("shared"):
                y = y + self._shared(u.astype(cfg.dtype), gated)
        return y.astype(cfg.dtype).reshape(B, T, H)

    def _shared(self, u, gated: bool):
        """The shared expert on every token, float32 out: the experts'
        form at its own width, as plain products (one expert, every row:
        nothing to group)."""
        cfg = self.cfg
        F = cfg.moe_shared_intermediate_size
        init = nn.initializers.normal(0.02)

        def matrix(name, shape):
            return self.param(name, init, shape).astype(cfg.dtype)

        def dot(a, w):
            return jnp.dot(a, w, preferred_element_type=jnp.float32)

        up = dot(u, matrix("shared_up", (u.shape[-1], F)))
        h = nn.silu(dot(u, matrix("shared_gate", (u.shape[-1], F)))) * up \
            if gated else _relu2(up)
        return dot(h.astype(cfg.dtype), matrix("shared_down",
                                               (F, u.shape[-1])))
