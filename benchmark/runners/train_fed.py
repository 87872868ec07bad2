"""Runner of ``"kind": "train-fed"`` traffic: training fed from the driver.

This (driver) process never imports jax.  It calls ``TPUCluster.run(worker,
num_workers=1, input_mode=InputMode.SPARK)`` and feeds global batches from a
pool it made from the seed through ``cluster.train`` -> queues/shm ->
``DataFeed``; the one worker owns the chips the cell asks for and takes
steps of the configuration's program (``models/<model>.build_train``).

The worker builds ONE program (compiled step + state), drives it from the
seed through its first steps with the window's own call and feed (set-up),
hands the same object to the window, and after the window, with the
program's state freed, follows those first steps with the configuration's
plain reference.  Losses stay on the device: the host keeps at most
``sync_lag`` steps in flight (it waits on the loss of the step ``sync_lag``
back, which never drains the pipeline) and fetches the finished losses in
one transfer every ``loss_fetch_steps`` steps.  A traced run traces one
whole fetch period after its window: at 31 MB of trace and 0.4 GB of host
memory a step (my chip run, PR 24) ten steps are what a 40 GiB host holds.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import time

from benchmark import child, harness, traffic_gen

#: steps the reference follows (and the program takes before the window)
FIRST_STEPS = 3


# ------------------------------------------------------------------ worker

def worker(args, ctx):
    """The train worker (owns the chips); writes its report to
    ``args["report"]`` and prints its comparisons itself."""
    t_child = time.monotonic()
    import jax
    import numpy as np

    log = child.CompileLog()
    cell, cfg, traffic = args["cell"], args["cfg"], args["traffic"]
    chips = int(cell["chips"])
    feed = ctx.get_data_feed()
    report: dict = {"t_child": t_child}

    def finish(**extra):
        report.update(extra)
        with open(args["report"], "w") as f:
            json.dump(report, f)

    devices = jax.devices()
    why = child.check_chip(devices, chips, args["require_tpu"])
    if why:
        feed.terminate()
        return finish(no_chip=why)
    devices = devices[:chips]
    report["device"] = child.device_report(devices)
    report["cache_dir"] = jax.config.jax_compilation_cache_dir

    program = harness.load_module("models", cfg["model"]).build_train(
        cfg, args["seed"], devices)
    batch_size = int(traffic["batch_per_chip"]) * chips
    spans = child.Spans(annotate=bool(args["trace"]))
    if args["trace"]:
        # the first annotation of a process wakes the profiler's back end;
        # let that happen in set-up and not in the window
        with jax.profiler.TraceAnnotation("bench/setup"):
            pass

    def next_batch():
        with spans("feed_wait"):
            arrays = feed.next_batch_arrays(batch_size, timeout=600)
        if arrays is None or len(arrays[0]) != batch_size:
            raise RuntimeError("the feed ended or gave a short batch")
        with spans("shard_batch"):
            return program.shard(arrays)

    # ---- set-up: the first steps, through the window's own call and feed
    params0 = program.params_copy()
    first_losses, grad_norms = [], None
    for i in range(FIRST_STEPS):
        first_losses.append(program.step(next_batch()))
        if i == 0:
            grad_norms = program.grad_norms()
            jax.block_until_ready(first_losses[0])
            report["warmup_s"] = time.monotonic() - t_child
    delta_norms = program.delta_norms(params0)
    del params0
    observed = {
        "losses": [float(x) for x in jax.device_get(first_losses)],
        "grad_norms": {k: float(v) for k, v in
                       jax.device_get(grad_norms).items()},
        "delta_norms": {k: float(v) for k, v in
                        jax.device_get(delta_norms).items()}}
    if log.misses and args["restart_after_compile"]:
        # a process that compiled runs its window a few per cent slower
        # than one that loaded the same programs (PERF.md section 6): the
        # driver starts the worker again, and every program is in the cache
        feed.terminate()
        return finish(restart=True, compiles=log.snapshot())
    for _ in range(int(traffic["warm_steps"])):
        last = program.step(next_batch())
    jax.block_until_ready(last)

    # ---- the window
    seconds = float(args["seconds"])
    lag = int(traffic["sync_lag"])
    fetch_every = int(traffic["loss_fetch_steps"])
    on_device: list = []                       # losses not fetched yet
    fetched: list[float] = []
    steps = 0

    def one_step() -> None:
        nonlocal steps, on_device, fetched
        batch = next_batch()
        with spans("step_dispatch"):
            on_device.append(program.step(batch))
        steps += 1
        if len(on_device) > lag:
            with spans("sync"):
                on_device[-1 - lag].block_until_ready()
        if steps % fetch_every == 0:
            with spans("loss_fetch"):
                fetched += [float(x) for x in
                            jax.device_get(on_device[:-lag])]
                on_device = on_device[-lag:]

    spans.recording = True
    report["t_window"] = t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        one_step()
    program.ready()
    t1 = time.monotonic()
    window_steps, window_spans = steps, spans.summary()

    # ---- a traced run goes on past its window, under the profiler, from
    # one loss fetch on: one whole fetch period, and the steps the device
    # runs behind the host
    traced = bool(args["trace"])
    if traced:
        while steps % fetch_every:
            one_step()
        child.start_trace(args["trace_dir"])
        for _ in range(fetch_every + lag + 2):
            one_step()
        program.ready()
        jax.profiler.stop_trace()
    spans.recording = False
    fetched += [float(x) for x in jax.device_get(on_device)]
    steps = window_steps
    feed.terminate()

    report.update(
        window_s=t1 - t0, steps=steps, samples=steps * batch_size,
        global_batch=batch_size,
        compiles_in_window=log.between(t0, t1), compiles=log.snapshot(),
        nonfinite_losses=int(np.sum(~np.isfinite(fetched))),
        last_loss=fetched[-1], spans=window_spans,
        shm_conns=int(ctx.mgr.shm_conns),
        param_shard_devices=program.param_shard_devices(),
        memory_stats=child.memory_stats(devices), observed=observed)
    harness.say("train window", steps=steps, seconds=t1 - t0,
                first_losses=observed["losses"], last_loss=fetched[-1],
                spans=report["spans"], compiles_in_window=report[
                    "compiles_in_window"], cache=[log.hits, log.misses],
                shm_conns=report["shm_conns"],
                memory_stats=report["memory_stats"][0])
    finish()                  # the window's account is safe before the rest

    if traced:
        from benchmark import trace as trace_mod

        t_r = time.monotonic()
        report["trace"] = trace_mod.reduce_dir(args["trace_dir"],
                                               period=fetch_every)
        report["trace_reduce_s"] = time.monotonic() - t_r

    # ---- the plain reference, with the program's state freed
    program.free()
    ref = program.ref
    batches = [traffic_gen.train_batch(args["seed"], j, traffic, cfg, chips)
               for j in range(FIRST_STEPS)]
    t_r = time.monotonic()
    reference = ref.first_steps(cfg, args["seed"], batches,
                                lr=cfg["learning_rate"],
                                momentum=cfg["momentum"])
    report["reference_s"] = time.monotonic() - t_r
    report["compared"] = ref.compare(observed, reference)
    report["limits"] = harness.limits_for(ref.LIMITS, cfg)
    harness.say("reference", seconds=report["reference_s"],
                losses=reference["losses"], **report["compared"])
    if args["control"]:
        control = ref.first_steps(cfg, args["seed"], batches,
                                  lr=cfg["learning_rate"],
                                  momentum=cfg["momentum"],
                                  quant=cfg["control_precision"])
        report["control"] = ref.compare(control, reference)
        harness.say("control", precision=cfg["control_precision"],
                    **report["control"])
    finish()


# ------------------------------------------------------------------ driver

def _boot(cell: dict, opts: dict, rows: list, batch_size: int) -> dict:
    """One life of the worker: boot, feed until it ends the feed, join."""
    from tensorflowonspark_tpu import InputMode, TPUCluster

    workdir = tempfile.mkdtemp(prefix="bench_train_")
    report_path = os.path.join(workdir, "report.json")
    args = dict(cell={k: cell[k] for k in ("name", "chips")},
                cfg=cell["config_data"], traffic=cell["traffic_data"],
                seed=opts["seed"], seconds=opts["seconds"],
                trace=opts["trace"], control=opts["control"],
                require_tpu=opts["require_tpu"],
                restart_after_compile=opts["restart_after_compile"],
                trace_dir=os.path.join(workdir, "trace"), report=report_path)
    try:
        cluster = TPUCluster.run(
            worker, args, num_workers=1, input_mode=InputMode.SPARK,
            working_dir=workdir, worker_env=opts.get("worker_env"),
            reservation_timeout=600,
            queue_depth=int(cell["traffic_data"]["queue_depth"]))
        failure: list = []

        def feed():
            try:
                cluster.train(rows, num_epochs=0, chunk_size=batch_size,
                              feed_timeout=900)
            except Exception as e:     # surfaced by shutdown() below
                failure.append(e)

        feeder = threading.Thread(target=feed, name="bench-feeder",
                                  daemon=True)
        feeder.start()
        try:
            feeder.join()
        finally:
            cluster.shutdown(timeout=1500)    # re-raises a worker error
        if failure:
            raise failure[0]
        with open(report_path) as f:
            return json.load(f)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def run(cell: dict, opts: dict, started: float) -> dict:
    """Drive one run of a train-fed cell; returns the run's account for
    ``benchmark.run`` to print."""
    cfg, traffic, chips = cell["config_data"], cell["traffic_data"], \
        cell["chips"]
    rows = traffic_gen.train_pool(opts["seed"], traffic, cfg, chips)
    batch_size = int(traffic["batch_per_chip"]) * chips
    for attempt in range(2):
        report = _boot(cell, dict(opts, restart_after_compile=(
            attempt == 0 and opts.get("restart_after_compile", True))),
            rows, batch_size)
        if report.get("no_chip"):
            raise harness.NoChip(report["no_chip"])
        if not report.get("restart"):
            break
        took = sorted((s for _, s in report["compiles"]["compiles"]),
                      reverse=True)[:6]
        harness.say("the worker compiled and is started again",
                    cache_misses=report["compiles"]["misses"],
                    longest_compiles_s=took,
                    missed=report["compiles"]["missed"])
    if "compared" not in report:
        raise RuntimeError("the worker ended before the reference ran")

    checks = harness.Comparisons()
    for name in ("loss_rel", "grad_norm_rel", "delta_norm_rel"):
        checks.add(name, report["compared"][name], *report["limits"][name])
    checks.add("compiles_in_window", report["compiles_in_window"], 0)
    checks.add("nonfinite_losses", report["nonfinite_losses"], 0)
    checks.add("params_not_on_every_chip",
               abs(report["param_shard_devices"] - chips), 0)
    checks.add("feed_not_on_shm", 0 if report["shm_conns"] >= 1 else 1, 0)

    window_s = report["window_s"]
    values = {
        "samples_per_s_per_chip": report["samples"] / window_s / chips,
        "setup_s": report["t_window"] - started}
    device = dict(report["device"], memory_peak_bytes=harness.
                  memory_peak_bytes(report["memory_stats"]))
    reduced, idle = report.get("trace"), None
    if reduced:
        device_s = harness.device_seconds(
            reduced, {(reduced["main_program"],): report["steps"]},
            report["steps"])["seconds"]
        idle = harness.idle_share(reduced, device_s, window_s)
    return {"correct": checks.correct, "compared": checks.rows,
            "attempted": report["steps"],
            "failed": 0, "values": values, "device": device,
            "kind": "train-fed", "cell": cell, "report": report,
            "window_s": window_s, "steps": report["steps"],
            "spans": report["spans"], "trace": reduced, "idle": idle,
            "warmup_s": report["warmup_s"], "counters": {}}
