"""The program under test for ``"model": "brumby"`` configurations: the
repo's ``models.GPT`` with power-retention layers (``layer_types``
``"retention"``, ``ops/power_retention.py``) and an untied head, behind
``ServingCluster`` / ``ContinuousBatcher``.

The asker and the verify worker are ``models/gpt2.py``'s and the observer
``models/lfm2.py``'s, loaded from those files and not copied.  What differs
here: the weights are ``reference/brumby.make_weights``', the observer also
reads the state's byte counter, and a traced run's reduced trace gains the
device seconds of the retention kernel and of the ``ret/`` scopes
(``benchmark/trace_kernels``).
"""

from __future__ import annotations

import os
import time

from benchmark import child, harness

if not os.path.exists(os.path.join(harness.ROOT, "tensorflowonspark_tpu",
                                   "ops", "power_retention.py")):
    # said here, in the driver process and before anything is booted, so
    # that a program from before the retention layer fails at once
    raise RuntimeError("this checkout's program has no retention layer "
                       "(tensorflowonspark_tpu/ops/power_retention.py): it "
                       "cannot run a \"model\": \"brumby\" configuration")

gpt2 = harness.load_module("models", "gpt2")
lfm2 = harness.load_module("models", "lfm2")
Asker = gpt2.Asker
verify_worker = gpt2.verify_worker

#: the counter read beside ``lfm2.Observer``'s (which has the rows
#: seated, and from ``gpt2.Observer`` the phase clocks and the steps queued
#: ahead): the state's bytes
ENGINE_COUNTERS = ("tfos_replica_state_bytes_moved_total",)


def gpt_config(cfg: dict):
    """The program's ``GPTConfig`` of a configuration file that holds the
    public ``config.json``'s keys."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPTConfig

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("the program takes head_dim as hidden_size / "
                         "num_attention_heads")
    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["dtype"]), pos_encoding="rope",
        rope_base=float(cfg["rope_theta"]), norm="rmsnorm",
        norm_eps=cfg["rms_norm_eps"], mlp="swiglu", use_bias=False,
        qk_norm=True, layer_types=tuple(cfg["layer_types"]),
        retention_chunk=cfg["retention_chunk"],
        retention_eps=cfg["retention_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"])


class Observer(lfm2.Observer):
    """``lfm2.Observer`` (the engine counters, the phase clocks, the device
    seconds by ``trace_scopes``' scopes) that also reads the state's byte
    counter and joins the retention kernel's and scopes' device seconds to
    the reduced trace."""

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
            from benchmark import trace, trace_kernels

            out["trace"]["kernels"] = trace_kernels.reduce_file(
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
