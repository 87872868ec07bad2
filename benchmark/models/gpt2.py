"""The program under test for ``"model": "gpt2"`` configurations: the repo's
``models.GPT`` behind ``ServingCluster`` / ``ContinuousBatcher``.

``builder`` is the ``model_builder`` the tier calls inside the replica
process (which owns the chip): it makes the weights from the seed on the
device in one jitted call, in the type they are served in
(``reference/<reference>.make_weights``: the benchmark's, given to the
program), and starts the benchmark's small observer thread there, because
only the process that holds the chip can read its compile events, its
memory, its counters at an instant, and trace it.
"""

from __future__ import annotations

import json
import os
import select
import threading
import time

from benchmark import child, harness

#: the program's counters the serve metrics read (``serving/replica.py``);
#: the last, the decode steps dispatched behind a running one, is read by
#: no metric and printed with the others in the ``serve window`` fact
COUNTERS = ("tfos_replica_steps_total", "tfos_replica_tokens_total",
            "tfos_replica_decode_dispatches_total",
            "tfos_replica_prefill_dispatches_total",
            "tfos_replica_requests_total",
            "tfos_replica_decode_ahead_dispatches_total")


def gpt_config(cfg: dict):
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPTConfig

    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        norm_eps=cfg["norm_eps"], dtype=jnp.dtype(cfg["dtype"]))


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
    # the seed goes in as an argument: baked in, every seed would be a
    # program of its own, compiled anew (53 s at gpt2-xl)
    params = jax.jit(lambda key: ref.make_weights(key, cfg))(
        child.seed_key(bench["seed"]))
    jax.block_until_ready(params)
    Observer(bench["ctl"], log, devices, t_child).start()
    return gpt_config(cfg), params


class Observer(threading.Thread):
    """Answers the driver's questions from inside the replica process.  The
    driver writes ``ask-<n>.json`` into the control directory and a byte
    into the ``wake`` FIFO there; the answer is ``answer-<n>.json``.
    Between questions the thread sleeps in ``select`` on the FIFO and takes
    no turn from the serving loop; only while a trace runs does it wake
    every ``POLL_S`` to see whether the traced steps are done.  Questions:
    ``snapshot`` (time, counters, compile log, memory),
    ``trace_start`` (stops by itself after ``steps`` runs of the serving
    loop or ``max_s`` seconds; the runner starts it where a request is
    near its end, so that the session holds an admission),
    ``trace_result`` (the reduced trace)."""

    POLL_S = 0.01

    def __init__(self, ctl: str, log, devices, t_child: float):
        super().__init__(name="bench-observer", daemon=True)
        self.ctl, self.log, self.devices = ctl, log, devices
        self.t_child = t_child
        self.trace_dir = os.path.join(ctl, "trace")
        self.tracing_until: tuple | None = None
        self.traced = False

    def counters(self) -> dict:
        from tensorflowonspark_tpu import metrics

        reg = metrics.get_registry()
        out = {name: float(reg.counter(name).value()) for name in COUNTERS}
        # the loop thread's phase clocks (docs/observability.md), as
        # ``phase_seconds.<phase>``: their window deltas are printed in the
        # run's "serve window" fact, say which phase of a turn a slow run
        # lost its time in, and ``host_turn_ms.serve`` reads them
        from tensorflowonspark_tpu import observability

        out.update({f"phase_seconds.{name.rsplit('/', 1)[1]}":
                    float(observability.phase_seconds(name).value())
                    for name in observability.REPLICA_PHASES})
        return out

    def snapshot(self) -> dict:
        return {"t": time.monotonic(), "t_child": self.t_child,
                "counters": self.counters(), "compiles": self.log.snapshot(),
                "memory_stats": child.memory_stats(self.devices),
                "device": child.device_report(self.devices)}

    def answer(self, ask: dict) -> dict:
        import jax

        op = ask["op"]
        if op == "snapshot":
            return self.snapshot()
        if op == "trace_start":
            child.start_trace(self.trace_dir)
            self.tracing_until = (self.steps() + ask["steps"],
                                  time.monotonic() + ask["max_s"])
            return {"t": time.monotonic()}
        if op == "trace_result":
            self._stop_trace(force=True)
            if not self.traced:
                return {"trace": None}
            from benchmark import trace

            return {"trace": trace.reduce_dir(self.trace_dir)}
        raise ValueError(f"unknown question {op!r}")

    def steps(self) -> float:
        from tensorflowonspark_tpu import metrics

        return float(metrics.get_registry().counter(
            "tfos_replica_steps_total").value())

    def _stop_trace(self, force: bool = False) -> None:
        import jax

        if self.tracing_until is None:
            return
        steps, deadline = self.tracing_until
        if force or time.monotonic() >= deadline or self.steps() >= steps:
            jax.profiler.stop_trace()
            self.tracing_until, self.traced = None, True

    def run(self) -> None:
        # read-write, so that the open never waits for a writer and the
        # FIFO never reads as closed
        wake = os.open(os.path.join(self.ctl, "wake"), os.O_RDWR)
        n = 0
        while True:
            ask_path = os.path.join(self.ctl, f"ask-{n}.json")
            if not os.path.exists(ask_path):
                if select.select([wake], [], [], self.POLL_S if
                                 self.tracing_until else None)[0]:
                    os.read(wake, 4096)
                self._stop_trace()
                continue
            with open(ask_path) as f:
                ask = json.load(f)
            try:
                out = self.answer(ask)
            except Exception as e:       # the driver raises it on its side
                out = {"error": f"{type(e).__name__}: {e}"}
            tmp = os.path.join(self.ctl, f"answer-{n}.tmp")
            with open(tmp, "w") as f:
                json.dump(out, f)
            os.replace(tmp, os.path.join(self.ctl, f"answer-{n}.json"))
            n += 1


class Asker:
    """The driver's side of :class:`Observer`."""

    def __init__(self, ctl: str):
        self.ctl, self.n = ctl, 0
        os.mkfifo(os.path.join(ctl, "wake"))
        self.wake = os.open(os.path.join(ctl, "wake"), os.O_RDWR)

    def ask(self, op: str, timeout: float = 120.0, **fields) -> dict:
        tmp = os.path.join(self.ctl, f"ask-{self.n}.tmp")
        with open(tmp, "w") as f:
            json.dump({"op": op, **fields}, f)
        os.replace(tmp, os.path.join(self.ctl, f"ask-{self.n}.json"))
        os.write(self.wake, b"\n")
        path = os.path.join(self.ctl, f"answer-{self.n}.json")
        self.n += 1
        deadline = time.monotonic() + timeout
        while not os.path.exists(path):
            if time.monotonic() > deadline:
                raise TimeoutError(f"the replica did not answer {op!r}")
            time.sleep(0.002)
        with open(path) as f:
            out = json.load(f)
        if "error" in out:
            raise RuntimeError(f"replica observer: {out['error']}")
        return out

    def close(self) -> None:
        os.close(self.wake)


def verify_worker(args, ctx):
    """Scores served streams with the plain reference, in a process of its
    own started after the tier shut down (it has the chip to itself)."""
    t0 = time.monotonic()
    import numpy as np

    bench = args["bench"]
    cfg = bench["cfg"]
    ref = harness.load_module("reference", cfg["reference"])
    items = [(np.asarray(p, np.int32), np.asarray(s, np.int32))
             for p, s in bench["items"]]
    out = ref.score(cfg, bench["seed"], items,
                    control=cfg["control_precision"] if bench["control"]
                    else None)
    out["seconds"] = time.monotonic() - t0
    out["limits"] = harness.limits_for(ref.LIMITS, cfg)
    with open(bench["report"], "w") as f:
        json.dump(out, f)
