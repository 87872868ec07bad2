"""The program under test for ``"model": "lfm2"`` configurations: the repo's
``models.GPT`` with a layer pattern (short-convolution and attention
layers), per-head q/k norm and the dropless expert layer
(``models/moe.py``), behind ``ServingCluster`` / ``ContinuousBatcher``.

The observer, the asker and the verify worker are ``models/gpt2.py``'s,
loaded from that file and not copied.  What differs here: the weights are
made layer by layer (``reference/lfm2.make_weights``: 5.4 B parameters in
one jitted call would hold the float32 noise of all of them), the observer
also reads the expert and conv-state counters, and a traced run's reduced
trace gains the device seconds by named scope (``benchmark/trace_scopes``).
"""

from __future__ import annotations

import os
import time

from benchmark import child, harness

if not os.path.exists(os.path.join(harness.ROOT, "tensorflowonspark_tpu",
                                   "models", "moe.py")):
    # said here, in the driver process and before anything is booted, so
    # that a program from before the expert layer fails at once
    raise RuntimeError("this checkout's program has no expert layer "
                       "(tensorflowonspark_tpu/models/moe.py): it cannot "
                       "run a \"model\": \"lfm2\" configuration")

gpt2 = harness.load_module("models", "gpt2")
Asker = gpt2.Asker

#: the counters the expert layers and the conv state add
#: (``serving/replica.py``)
ENGINE_COUNTERS = ("tfos_replica_expert_assignments_total",
                   "tfos_replica_expert_peak_assignments_total",
                   "tfos_replica_experts_touched_total",
                   "tfos_replica_state_rows_seated_total")


def gpt_config(cfg: dict):
    """The program's ``GPTConfig`` of a configuration file that holds the
    public ``config.json``'s keys."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPTConfig

    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["dtype"]), pos_encoding="rope",
        rope_base=float(cfg["rope_theta"]), norm="rmsnorm",
        norm_eps=cfg["norm_eps"], mlp="swiglu", use_bias=False,
        qk_norm=True, layer_types=tuple(cfg["layer_types"]),
        conv_L_cache=cfg["conv_L_cache"],
        num_dense_layers=cfg["num_dense_layers"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"])


class Observer(gpt2.Observer):
    """``gpt2.Observer`` (the five counters, the steps queued ahead, the
    phase clocks) that also reads the engine counters and joins the device
    seconds by named scope to the reduced trace."""

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
            from benchmark import trace, trace_scopes

            out["trace"]["scopes"] = trace_scopes.reduce_file(
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


def verify_worker(args, ctx):
    """``gpt2.verify_worker``, and the share of routing decisions that a
    bfloat16 near-tie changes said beside the comparison."""
    import json

    gpt2.verify_worker(args, ctx)
    with open(args["bench"]["report"]) as f:
        out = json.load(f)
    if "routing_differs_share" in out:
        own = out.get("own_precision", {})
        harness.say("routing", differs_share=out["routing_differs_share"],
                    of="(expert layer, served position) choices, float32 "
                       "against bfloat16 matrix products",
                    bfloat16_reference_gap_sigmas=own.get(
                        "served_gap_sigmas"),
                    bfloat16_reference_gap_mean_sigmas=own.get(
                        "served_gap_mean_sigmas"))
