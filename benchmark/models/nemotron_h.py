"""The program under test for ``"model": "nemotron_h"`` configurations: the
repo's ``models.GPT`` with one mixer a block (``mixer_only``): Mamba-2
mixers (``layer_types`` ``"mamba2"``, ``ops/ssm.py``), the expert layer as a
block's only operator with this chip's share of the experts
(``experts_held``), a shared expert and relu² experts (``models/moe.py``),
and attention without positions, behind ``ServingCluster`` /
``ContinuousBatcher``.

The asker is ``models/gpt2.py``'s, the verify worker ``models/lfm2.py``'s
(it says the ``routing`` fact) and the observer ``models/brumby.py``'s,
loaded from those files and not copied.  What differs here: the weights are
``reference/nemotron_h.make_weights``', the observer also reads the held
experts' counter, and a traced run's reduced trace gains the device
seconds of the state-space kernel and of the ``ssm/`` and ``moe/`` scopes
(``benchmark/trace_ssm``).
"""

from __future__ import annotations

import os
import time

from benchmark import child, harness

if not os.path.exists(os.path.join(harness.ROOT, "tensorflowonspark_tpu",
                                   "ops", "ssm.py")):
    # said here, in the driver process and before anything is booted, so
    # that a program from before the state-space layer fails at once
    raise RuntimeError("this checkout's program has no state-space layer "
                       "(tensorflowonspark_tpu/ops/ssm.py): it cannot run "
                       "a \"model\": \"nemotron_h\" configuration")

gpt2 = harness.load_module("models", "gpt2")
lfm2 = harness.load_module("models", "lfm2")
brumby = harness.load_module("models", "brumby")
Asker = gpt2.Asker
verify_worker = lfm2.verify_worker

#: the counters read beside ``brumby.Observer``'s (which has the state's
#: bytes, and from ``lfm2.Observer`` the experts' three and the rows
#: seated): the assignments that fell to the experts held here, and the
#: part of the experts touched that prefills account for
ENGINE_COUNTERS = ("tfos_replica_expert_assignments_held_total",
                   "tfos_replica_prefill_experts_touched_total")

LAYER_TYPES = {"M": "mamba2", "E": "experts", "*": "full_attention"}


def gpt_config(cfg: dict):
    """The program's ``GPTConfig`` of a configuration file that holds the
    public ``config.json``'s keys, ``n_routed_experts`` the experts held
    here and ``num_experts`` the router's width."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPTConfig

    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        attn_head_dim=cfg["head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["dtype"]), pos_encoding="none", norm="rmsnorm",
        norm_eps=cfg["layer_norm_epsilon"], use_bias=False,
        layer_types=tuple(LAYER_TYPES[c]
                          for c in cfg["hybrid_override_pattern"]),
        mixer_only=True, ssm_num_heads=cfg["mamba_num_heads"],
        ssm_head_dim=cfg["mamba_head_dim"], ssm_groups=cfg["n_groups"],
        ssm_state_size=cfg["ssm_state_size"],
        ssm_conv_kernel=cfg["conv_kernel"], ssm_chunk=cfg["chunk_size"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        experts_held=(cfg["experts_held_first"], cfg["n_routed_experts"]),
        moe_shared_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        moe_activation=cfg["mlp_hidden_act"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        # as ``reference/nemotron_h.make_weights`` lays the first matrices
        moe_up_transposed=cfg["moe_intermediate_size"] % 128 != 0)


class Observer(brumby.Observer):
    """``brumby.Observer`` (the engine counters, the state's bytes, the
    phase clocks, the device seconds by ``trace_scopes``' scopes) that
    also reads the held experts' counters and joins the state-space
    kernel's and scopes' device seconds to the reduced trace."""

    def counters(self) -> dict:
        from tensorflowonspark_tpu import metrics

        out = super().counters()
        reg = metrics.get_registry()
        out.update({name: float(reg.counter(name).value())
                    for name in ENGINE_COUNTERS})
        return out

    def answer(self, ask: dict) -> dict:
        out = super().answer(ask)
        if ask["op"] == "trace_result" and out.get("trace"):
            from benchmark import trace, trace_ssm

            out["trace"]["ssm"] = trace_ssm.reduce_file(
                trace.find_xplane(self.trace_dir))
        return out


def builder(args):
    """``model_builder(args) -> (cfg, params)`` of the serving tier."""
    t_child = time.monotonic()
    import jax

    log = child.CompileLog()
    bench = args["bench"]
    cfg = bench["cfg"]
    devices = jax.devices()
    why = child.check_chip(devices, bench["chips"], bench["require_tpu"])
    if why:
        with open(os.path.join(bench["ctl"], "no_chip"), "w") as f:
            f.write(why)
        raise RuntimeError(why)
    devices = devices[:bench["chips"]]
    ref = harness.load_module("reference", cfg["reference"])
    params = ref.make_weights(child.seed_key(bench["seed"]), cfg)
    jax.block_until_ready(params)
    Observer(bench["ctl"], log, devices, t_child).start()
    return gpt_config(cfg), params
