"""Runner of ``"kind": "serve-closed"`` traffic: a closed loop of streaming
callers against one serving tier.

This (driver) process never imports jax.  It boots ``ServingCluster.run(
builder, 1, max_batch=..., batcher_kwargs=...)`` (the replica owns the
chip), warms every admission-group program the window can need, then runs
``clients`` ``ServeClient`` callers, each sending its next request when the
last completed.  Request lengths are the traffic file's literal list
(``traffic_gen.client_entries``); the seed makes the weights and the token
ids.  Tokens are timed where a caller receives them.

The window opens once every caller's stream is decoding and
``open_after_completions`` requests have completed (the callers start
together, so until the first completes no prompt joins a step and the
stream is not yet what a steady service sees).  Both of its edges are laid
between two serving steps (``_between_steps``).  When it closes the callers
finish the request they are in and stop (the program cannot cancel a
seated request: the replica decodes every row to its end before it exits,
whatever its callers do); the tier is shut down; a process of its own then
scores a seeded sample of the streams completed in the window, the longest
among them, with the configuration's plain reference.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import tempfile
import threading
import time

import numpy as np

from benchmark import harness, traffic_gen
from benchmark.trace import DECODE_PROGRAMS, PREFILL_PROGRAMS

#: streams the reference scores after a run (one batched forward)
SAMPLE = 4


class Caller(threading.Thread):
    """One closed-loop caller: sends its list's entries in order, records
    for every request the send time and each token's receive time."""

    def __init__(self, serving, index: int, entries: list, seed: int,
                 vocab: int, stop: threading.Event, arrivals: list):
        super().__init__(name=f"bench-caller-{index}", daemon=True)
        self.serving, self.index, self.entries = serving, index, entries
        self.seed, self.vocab, self.stop_event = seed, vocab, stop
        self.arrivals = arrivals            # every caller's receive times
        self.requests: list[dict] = []      # appended when a request starts
        self.errors: list[str] = []

    def run(self) -> None:
        k = 0
        try:
            with self.serving.client() as client:
                while not self.stop_event.is_set():
                    plen, budget = self.entries[k % len(self.entries)]
                    prompt = traffic_gen.prompt_ids(self.seed, self.index, k,
                                                    plen, self.vocab)
                    rec = {"client": self.index, "k": k, "prompt": prompt,
                           "budget": budget, "sent": time.monotonic(),
                           "recv": [], "tokens": [], "done": None}
                    self.requests.append(rec)
                    for delta in client.generate_stream(prompt, budget,
                                                        timeout=900):
                        now = time.monotonic()
                        self.arrivals.append(now)
                        rec["recv"].extend([now] * len(delta))
                        rec["tokens"].extend(int(t) for t in delta)
                    rec["done"] = time.monotonic()
                    k += 1
        except Exception as e:    # counted as a failed request by run()
            self.errors.append(f"caller {self.index} request {k}: {e!r}")


def _burst(serving, prompts: list, budget: int) -> None:
    """Send ``prompts`` at once, one caller each, and read every stream."""
    errors: list[str] = []

    def one(i: int) -> None:
        try:
            with serving.client() as c:
                for _ in c.generate_stream(prompts[i], budget, timeout=1500):
                    pass
        except Exception as e:
            errors.append(f"warm-up request {i}: {e!r}")

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(1800)
    if errors or any(t.is_alive() for t in threads):
        raise RuntimeError(f"warm-up requests failed or hung: {errors}")


def _warm_up(serving, asker, cell: dict, seed: int) -> dict:
    """Run one admission group of every size the window can see (the
    batcher compiles one prefill program per power-of-two group size per
    prompt bucket), checking by the program's own prefill-dispatch counter
    that each burst really was admitted as ONE group; a burst that split
    is sent again."""
    traffic, cfg = cell["traffic_data"], cell["config_data"]
    plen = max(p for p, _ in traffic["requests"])
    t_first = None
    sizes = {}
    for size in traffic["warm_groups"]:
        for attempt in range(2):
            before = asker.ask("snapshot")["counters"] if t_first else None
            prompts = [traffic_gen.prompt_ids(seed, 10_000 + size, attempt
                                              * 64 + i, plen,
                                              cfg["vocab_size"])
                       for i in range(size)]
            _burst(serving, prompts, int(traffic["warm_tokens"]))
            if t_first is None:
                t_first = time.monotonic()
            after = asker.ask("snapshot")["counters"]
            groups = None if before is None else (
                after["tfos_replica_prefill_dispatches_total"]
                - before["tfos_replica_prefill_dispatches_total"])
            sizes.setdefault(size, []).append(groups)
            if groups is None or groups == 1:
                break
    return {"t_first_stream": t_first, "groups_per_burst": sizes}


def _between_steps(arrivals: list, after: float, half_step: float) -> None:
    """Sleep until half a step past the first tokens received at or after
    ``after``.  A serving step's tokens reach all callers together, so a
    window's edge laid on the clock alone falls at a chance phase of a
    step: at 172.03 steps to the window (my chip runs, PR 24) a few
    milliseconds decided whether it held 172 steps' tokens or 171, 0.58 %
    of the rate.  Both edges are laid in the quiet between two steps; the
    window then holds whole steps and is up to a step longer than asked."""
    i, deadline = len(arrivals), time.monotonic() + 300
    while True:
        n = len(arrivals)
        hit = min((t for t in arrivals[i:n] if t >= after), default=None)
        if hit is not None:
            break
        if time.monotonic() > deadline:
            raise RuntimeError("no token reached a caller for 300 s")
        i = n
        time.sleep(0.001)
    time.sleep(max(0.0, hit + half_step - time.monotonic()))


def _until_a_finish_is_near(callers: list, within: int, deadline: float
                            ) -> None:
    """Sleep until some caller's request has at most ``within`` tokens to
    go, or ``deadline``."""
    def near() -> bool:
        return any(c.requests and c.requests[-1]["done"] is None and
                   c.requests[-1]["budget"] - len(c.requests[-1]["tokens"])
                   <= within for c in callers)

    while not near() and time.monotonic() < deadline:
        time.sleep(0.005)


def _serve(cell: dict, opts: dict) -> dict:
    """One life of the tier: boot, warm up, window, drain, shut down."""
    from tensorflowonspark_tpu.serving import ServingCluster

    traffic, cfg = cell["traffic_data"], cell["config_data"]
    adapter = harness.load_module("models", cfg["model"])
    workdir = tempfile.mkdtemp(prefix="bench_serve_")
    ctl = os.path.join(workdir, "ctl")
    os.makedirs(ctl)
    bench = {"cfg": cfg, "seed": opts["seed"], "chips": cell["chips"],
             "require_tpu": opts["require_tpu"], "ctl": ctl}
    asker = adapter.Asker(ctl)
    out: dict = {}
    serving = None
    try:
        try:
            serving = ServingCluster.run(
                adapter.builder, 1, max_batch=int(traffic["max_batch"]),
                batcher_kwargs=dict(traffic["batcher_kwargs"]),
                replica_args={"bench": bench}, working_dir=workdir,
                worker_env=opts.get("worker_env"), reservation_timeout=900)
            warm = _warm_up(serving, asker, cell, opts["seed"])
        except Exception:
            if os.path.exists(os.path.join(ctl, "no_chip")):
                with open(os.path.join(ctl, "no_chip")) as f:
                    raise harness.NoChip(f.read())
            raise
        snap = asker.ask("snapshot")
        out.update(warm=warm, device=snap["device"],
                   warmup_s=warm["t_first_stream"] - snap["t_child"])
        if snap["compiles"]["misses"] and opts["restart_after_compile"]:
            out.update(restart=True, compiles=snap["compiles"])
            return out

        # ---- the closed loop
        stop = threading.Event()
        arrivals: list[float] = []
        callers = [Caller(serving, i, traffic_gen.client_entries(traffic, i),
                          opts["seed"], cfg["vocab_size"], stop, arrivals)
                   for i in range(int(traffic["clients"]))]
        for c in callers:
            c.start()

        def ready() -> bool:
            first = all(c.requests and c.requests[0]["recv"]
                        for c in callers)
            done = sum(1 for c in callers for r in c.requests if r["done"])
            return first and done >= int(traffic["open_after_completions"])

        deadline = time.monotonic() + 900
        while not ready():
            if any(c.errors for c in callers) or time.monotonic() > deadline:
                raise RuntimeError(f"the closed loop did not reach its "
                                   f"steady state: {[c.errors for c in callers]}")
            time.sleep(0.005)
        half_step = 0.5 * statistics.median(harness.token_gaps(
            [r for c in callers for r in c.requests], 0.0, float("inf")))
        _between_steps(arrivals, time.monotonic(), half_step)
        s0 = asker.ask("snapshot")
        t0 = s0["t"]
        if opts["trace"]:
            # the callers started together, so their admissions come in
            # waves a request's length apart: the session starts where a
            # finish, and with it an admission, is near
            _until_a_finish_is_near(callers, int(traffic["trace_steps"]) // 2,
                                    t0 + 0.5 * opts["seconds"])
            asker.ask("trace_start", steps=int(traffic["trace_steps"]),
                      max_s=max(5.0, t0 + opts["seconds"] - 2.0
                                - time.monotonic()))
        time.sleep(max(0.0, t0 + opts["seconds"] - time.monotonic()))
        _between_steps(arrivals, t0 + opts["seconds"], half_step)
        s1 = asker.ask("snapshot")
        t1 = s1["t"]
        stop.set()
        reduced = asker.ask("trace_result", timeout=600)["trace"] \
            if opts["trace"] else None
        for c in callers:
            c.join(600)
        hung = [c.index for c in callers if c.is_alive()]
        s2 = asker.ask("snapshot")
        out.update(t0=t0, t1=t1, s0=s0, s1=s1, s2=s2, trace=reduced,
                   hung=hung, errors=[e for c in callers for e in c.errors],
                   requests=[r for c in callers for r in c.requests])
        return out
    finally:
        try:
            if serving is not None:
                serving.shutdown(timeout=600)
        finally:
            asker.close()
            shutil.rmtree(workdir, ignore_errors=True)


def _verify(cell: dict, opts: dict, items: list) -> dict:
    """Score ``items`` in a process of its own (it owns the chip alone)."""
    from tensorflowonspark_tpu import InputMode, TPUCluster

    cfg = cell["config_data"]
    adapter = harness.load_module("models", cfg["model"])
    workdir = tempfile.mkdtemp(prefix="bench_verify_")
    report = os.path.join(workdir, "report.json")
    try:
        cluster = TPUCluster.run(
            adapter.verify_worker,
            {"bench": {"cfg": cfg, "seed": opts["seed"], "report": report,
                       "control": opts["control"],
                       "items": [(p.tolist(), list(s)) for p, s in items]}},
            num_workers=1, input_mode=InputMode.TENSORFLOW,
            working_dir=workdir, worker_env=opts.get("worker_env"),
            reservation_timeout=600)
        cluster.shutdown(timeout=1500)
        with open(report) as f:
            return json.load(f)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _pairs(requests: list[dict], client: int):
    """Caller ``client``'s consecutive requests, the first of each pair
    completed."""
    own = sorted((r for r in requests if r["client"] == client),
                 key=lambda r: r["k"])
    return [(a, b) for a, b in zip(own, own[1:]) if a["done"]]


def _sample(finished: list[dict], seed: int) -> list[dict]:
    """At most SAMPLE of the finished requests, drawn from the seed, the
    longest always among them."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (r["client"], r["k"]))
    longest = max(order, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in order if r is not longest]
    rng = np.random.default_rng([int(seed), 77])
    picks = rng.permutation(len(rest))[:SAMPLE - 1]
    return [longest] + [rest[i] for i in sorted(picks)]


def run(cell: dict, opts: dict, started: float) -> dict:
    """Drive one run of a serve-closed cell; returns the run's account."""
    traffic, cfg = cell["traffic_data"], cell["config_data"]
    for attempt in range(2):
        out = _serve(cell, dict(opts, restart_after_compile=(
            attempt == 0 and opts.get("restart_after_compile", True))))
        if not out.get("restart"):
            break
        took = sorted((s for _, s in out["compiles"]["compiles"]),
                      reverse=True)[:6]
        harness.say("the replica compiled and is started again",
                    cache_misses=out["compiles"]["misses"],
                    longest_compiles_s=took,
                    missed=out["compiles"]["missed"])
    t0, t1 = out["t0"], out["t1"]
    window_s = t1 - t0
    requests = out["requests"]

    # ---- what the callers saw
    tokens_in_window = sum(1 for r in requests for t in r["recv"]
                           if t0 <= t < t1)
    gaps = harness.token_gaps(requests, t0, t1)
    ttft = [r["recv"][0] - r["sent"] for r in requests
            if r["recv"] and t0 <= r["recv"][0] < t1]
    finished = [r for r in requests if r["done"] and t0 <= r["done"] < t1]
    touched = [r for r in requests if r["sent"] < t1 and
               (r["done"] is None or r["done"] >= t0)]
    complete = [r for r in requests if r["done"]]
    wrong_length = sum(1 for r in complete if len(r["tokens"]) != r["budget"])
    failed = len(out["errors"]) + len(out["hung"])
    counters = {k: out["s1"]["counters"][k] - out["s0"]["counters"][k]
                for k in out["s0"]["counters"]}
    quantiles = {str(q): 1e3 * harness.nearest_rank(gaps, q)
                 for q in (50, 75, 90, 92, 95, 98, 99)} if gaps else {}
    steps = counters["tfos_replica_steps_total"]
    # how late the generator ran: a closed-loop caller's own time from one
    # request's end to the next one's send
    turnaround = [1e3 * (b["sent"] - a["done"]) for c in range(
        int(traffic["clients"])) for a, b in _pairs(requests, c)
        if t0 <= b["sent"] < t1]
    # the loop thread's phase clocks, where the observer reads them
    phases = {k.split(".", 1)[1]: 1e3 * v / steps
              for k, v in counters.items()
              if k.startswith("phase_seconds.") and steps}
    # the context a served token saw, and with the rows of a step what the
    # pool held live: a pool's reserve and a start-up peak are not that
    live = [len(r["prompt"]) + i for r in requests
            for i, t in enumerate(r["recv"]) if t0 <= t < t1]
    decodes = counters["tfos_replica_decode_dispatches_total"]
    longest = sorted(((b - a, b - t0, r["client"]) for r in requests
                      for a, b in zip(r["recv"], r["recv"][1:])
                      if t0 <= b < t1), reverse=True)[:4]
    harness.say("serve window", seconds=window_s, tokens=tokens_in_window,
                longest_gaps_ms_at_s_caller=[[1e3 * g, at, c]
                                             for g, at, c in longest],
                requests_finished=len(finished), gaps=len(gaps),
                gaps_over_twice_p95=sum(
                    1 for g in gaps if g > 2e-3 * quantiles["95"]),
                gap_quantiles_ms=quantiles,
                gap_modes=sorted(harness.gap_modes(gaps),
                                 key=lambda m: -m["share"])[:8],
                first_tokens=len(ttft), warm=out["warm"]["groups_per_burst"],
                turns_per_s=steps / window_s,
                live_context_tokens=statistics.fmean(live) * counters[
                    "tfos_replica_tokens_total"] / decodes
                if live and decodes else None,
                caller_turnaround_ms={
                    "n": len(turnaround),
                    "p50": harness.nearest_rank(turnaround, 50),
                    "p95": harness.nearest_rank(turnaround, 95)}
                if turnaround else {},
                phase_ms_per_step=phases,
                host_turn_ms=harness.load_module(
                    "layer_metrics", "host_turn_ms.serve").read(
                        {"kind": "serve-closed", "counters": counters}),
                counters=counters,
                memory_stats=out["s2"]["memory_stats"][0]
                if out["s2"]["memory_stats"] else {})

    if gaps:
        harness.say("tail", metric="gap_p99_ms",
                    **harness.tail_in_mode(gaps, 99))

    # ---- the plain reference, after the tier has freed the chip
    picked = _sample(finished, opts["seed"])
    checks = harness.Comparisons()
    if picked:
        scored = _verify(cell, opts, [(r["prompt"], r["tokens"])
                                      for r in picked])
        harness.say("reference", seconds=scored["seconds"],
                    streams=[[r["client"], r["k"], len(r["prompt"]),
                              len(r["tokens"])] for r in picked],
                    tokens=scored["tokens"],
                    per_stream_worst=scored["per_stream_worst"])
        if "control" in scored:
            harness.say("control", precision=cfg["control_precision"],
                        **scored["control"])
        for name in ("served_gap_sigmas", "served_gap_mean_sigmas"):
            checks.add(name, scored[name], *scored["limits"][name])
    checks.add("streams_finished_in_window_missing",
               0 if finished else 1, 0)
    checks.add("streams_of_wrong_length", wrong_length, 0)
    checks.add("failed_requests", failed, 0)
    compiles = sum(1 for end, _ in out["s2"]["compiles"]["compiles"]
                   if t0 <= end <= t1)
    checks.add("compiles_in_window", compiles, 0)

    values = {"tokens_per_s": tokens_in_window / window_s,
              "gap_p99_ms": quantiles.get("99"),
              "setup_s": t0 - started}
    device = dict(out["device"], memory_peak_bytes=harness.memory_peak_bytes(
        out["s2"]["memory_stats"]))
    reduced, idle = out["trace"], None
    if reduced:
        busy = harness.device_seconds(reduced, {
            DECODE_PROGRAMS: counters["tfos_replica_decode_dispatches_total"],
            PREFILL_PROGRAMS: counters[
                "tfos_replica_prefill_dispatches_total"]}, steps)
        idle = harness.idle_share(reduced, busy["seconds"], window_s)
        if busy["missing"]:
            harness.say("programs the window ran and the session did not",
                        programs=busy["missing"])
    return {"correct": checks.correct, "compared": checks.rows,
            "attempted": len(touched),
            "failed": failed, "values": values, "device": device,
            "kind": "serve-closed", "cell": cell, "window_s": window_s,
            "steps": steps, "counters": counters, "trace": reduced,
            "idle": idle,
            "warmup_s": out["warmup_s"],
            "ttft_ms": [1e3 * t for t in ttft],
            "mean_context_tokens": statistics.fmean(live) if live else None,
            "spans": {}, "report": {}}
