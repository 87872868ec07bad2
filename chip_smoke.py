"""chip_smoke.py — the two main paths, end to end, on the attached TPU.

The quickest proof that the system still starts on the chip.  It drives
the entry points a user calls, one chip-owning process at a time, while
this (driver) process never imports jax:

- **train**: ``TPUCluster.run(map_fun, ..., num_workers=1,
  input_mode=InputMode.SPARK)``; the one worker owns the chip, builds
  ResNet-50 (224 px, batch 128, bf16) under ``DataParallelStrategy`` and
  takes a few SGD steps on a batch the driver feeds through
  ``cluster.train`` -> queues/shm -> ``DataFeed``.
- **serve**: ``ServingCluster.run(builder, 1, ...)`` with GPT-2-124M
  widths (seeded random weights) and a paged KV cache; a ``ServeClient``
  per request streams a handful of prompts of different lengths
  concurrently; every stream is compared token for token with
  ``models.gpt`` greedy generation computed inside the replica process,
  and may leave it only at a near-tie of the reference's own logits
  (``TIE_TOL_SIGMAS``; then a process started after the tier shut down
  scores the rest of the stream by teacher forcing).  The tier is booted
  three times:
  plain ``jax.jit``, then twice with ``aot_cache=True`` (the last boot
  must LOAD its serve-step executables and read the persistent cache).

``--chips 4`` runs, instead, only the path across chips and what it is
compared with: the train phase at dp=1 and dp=4 (same seed and global
batch, losses compared step for step) and the serve phase on one chip and
behind a ``mesh={"tp": 4}`` gang (streams compared token for token).

Per-phase facts go on earlier lines; the LAST line of stdout is only
``{"ok": true, "device": {...}}``, filled from what the worker that held
the chip reported.  Any failed check, or a device that is not a TPU, exits
non-zero without that line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

#: ResNet-50 (``models/resnet.py``; BASELINE.md's north-star model)
RESNET50 = dict(stage_sizes=(3, 4, 6, 3), num_filters=64, num_classes=1000,
                image=224, batch=128)
#: GPT-2-124M widths — what ``models/convert.py`` maps the public
#: checkpoint onto
GPT2_124M = dict(vocab_size=50257, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072,
                 max_position_embeddings=1024)
#: prompt lengths / new-token budget of the serve phase's requests
PROMPT_LENS = (5, 23, 60, 150)
NEW_TOKENS = 24
#: a served token that differs from greedy ``generate`` must be a NEAR-TIE
#: of the reference model: its logit within this many standard deviations
#: (of that position's logits) of the reference argmax.  bf16 rounds every
#: activation to 8 bits, so two correct programs that sum in another order
#: (batched + paged + bucket-padded vs the scan reference; four tp shards vs
#: one chip) disagree where the top two logits are closer than their own
#: rounding noise (~0.2-1 % of a deviation); a wrong token — a bad KV page,
#: a wrong position — is several deviations away.
TIE_TOL_SIGMAS = 0.05
#: dp=4 vs dp=1 loss agreement, per step, relative: bf16 activations with
#: the batch statistics reduced across four shards in another order
DP_LOSS_RTOL = 2e-2


def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def off_jax(phase, **kwargs) -> dict:
    """Run one phase function with this (driver) process proven off jax
    before and after it: the chip belongs to the phase's worker, and a
    parent that holds it starves its children."""
    def check(when: str) -> None:
        if "jax" in sys.modules:
            raise RuntimeError(f"the driver process imported jax ({when} "
                               f"{phase.__name__})")

    check("before")
    result = phase(**kwargs)
    check("after")
    return result


# --------------------------------------------------------------- train phase

def train_map_fun(args, ctx):
    """The train worker (owns the chip): ResNet under
    ``DataParallelStrategy``, batches from ``DataFeed``; writes its report
    (device, losses, shard placement, shm) to ``args["report"]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.models.resnet import ResNet
    from tensorflowonspark_tpu.parallel import sharding as sh
    from tensorflowonspark_tpu.parallel.strategy import DataParallelStrategy

    devices = jax.devices()
    report = {"device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)},
              "cache_dir": jax.config.jax_compilation_cache_dir}
    if args["require_tpu"] and devices[0].platform != "tpu":
        raise RuntimeError(f"no TPU: jax reports {report['device']}")
    dp = int(args["dp"])
    strategy = DataParallelStrategy(devices=devices[:dp])
    model = ResNet(stage_sizes=tuple(args["stage_sizes"]),
                   num_filters=args["num_filters"],
                   num_classes=args["num_classes"], dtype=jnp.bfloat16)

    def init(collection):
        # born on the mesh: init_state jits this with the strategy's
        # out_shardings (XLA drops the collection that is not returned)
        sample = jnp.zeros(
            (args["batch"], args["image"], args["image"], 3), jnp.bfloat16)
        return model.init(jax.random.key(args["seed"]), sample,
                          train=True)[collection]

    state = strategy.init_state(lambda: init("params"),
                                optax.sgd(0.05, momentum=0.9))
    state.extras["batch_stats"] = jax.jit(
        lambda: init("batch_stats"),
        out_shardings=sh.replicated(strategy.mesh))()

    def loss_fn(params, batch, extras):
        x, y = batch
        x = x.astype(jnp.bfloat16) / 127.5 - 1.0          # uint8 -> [-1, 1]
        logits, updates = model.apply(
            {"params": params, "batch_stats": extras["batch_stats"]}, x,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, {"extras": {"batch_stats": updates["batch_stats"]}}
    loss_fn.has_aux = True
    step = strategy.build_train_step(loss_fn)

    def spread(tree):
        """Fewest distinct devices any leaf of ``tree`` has shards on."""
        return min(len({s.device for s in leaf.addressable_shards})
                   for leaf in jax.tree.leaves(tree))

    feed = ctx.get_data_feed()
    losses, step_secs = [], []
    while not feed.should_stop():
        arrays = feed.next_batch_arrays(args["batch"], timeout=300)
        if arrays is None:
            break
        if len(arrays[0]) != args["batch"]:
            raise RuntimeError(f"short batch from DataFeed: {len(arrays[0])}")
        batch = strategy.shard_batch(arrays)
        t0 = time.monotonic()
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))             # waits for the step
        step_secs.append(round(time.monotonic() - t0, 3))
        report["batch_shard_devices"] = spread(batch)
    report.update(
        losses=losses, step_secs=step_secs,
        param_shard_devices=spread(state.params),
        n_params=int(sum(np.prod(p.shape)
                         for p in jax.tree.leaves(state.params))),
        shm_conns=int(ctx.mgr.shm_conns))
    with open(args["report"], "w") as f:
        json.dump(report, f)


def run_train_phase(*, dp: int = 1, steps: int = 6, seed: int = 0,
                    require_tpu: bool = True, worker_env: dict | None = None,
                    batch: int = RESNET50["batch"],
                    image: int = RESNET50["image"],
                    stage_sizes=RESNET50["stage_sizes"],
                    num_filters: int = RESNET50["num_filters"],
                    num_classes: int = RESNET50["num_classes"]) -> dict:
    """Drive the train path once; returns the worker's report.  Raises on
    any failed check.  Widths are arguments so the CPU rehearsal
    (``tests/test_chip_smoke.py``) can run the same code tiny."""
    import numpy as np

    from tensorflowonspark_tpu import InputMode, TPUCluster

    rng = np.random.default_rng(seed)
    images = rng.integers(0, 256, (batch, image, image, 3), dtype=np.uint8)
    labels = rng.integers(0, num_classes, (batch,)).astype(np.int32)
    data = list(zip(images, labels))          # ONE batch, repeated per epoch
    workdir = tempfile.mkdtemp(prefix="chip_smoke_train_")
    report_path = os.path.join(workdir, "train_report.json")
    args = dict(dp=dp, seed=seed, batch=batch, image=image,
                stage_sizes=list(stage_sizes), num_filters=num_filters,
                num_classes=num_classes, require_tpu=require_tpu,
                report=report_path)
    t0 = time.monotonic()
    cluster = TPUCluster.run(train_map_fun, args, num_workers=1,
                             input_mode=InputMode.SPARK, working_dir=workdir,
                             worker_env=worker_env, reservation_timeout=300)
    try:
        cluster.train(data, num_epochs=steps, chunk_size=batch)
    finally:
        cluster.shutdown(timeout=900)         # re-raises a worker error
    with open(report_path) as f:
        report = json.load(f)
    report["wall_secs"] = round(time.monotonic() - t0, 1)

    losses = report["losses"]
    if len(losses) != steps:
        raise RuntimeError(f"train: {len(losses)} steps ran, fed {steps}")
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"train: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train: loss did not fall on a repeated batch: "
                           f"{losses}")
    if report["shm_conns"] < 1:
        raise RuntimeError("train: the feed did not ride the shm transport")
    for what in ("param_shard_devices", "batch_shard_devices"):
        if report[what] != dp:
            raise RuntimeError(f"train: {what}={report[what]}, want {dp} — "
                               "arrays are not spread over the dp devices")
    return report


# --------------------------------------------------------------- serve phase

def gpt_builder(args):
    """Serving model builder (runs in the replica / gang-leader process):
    seeded random GPT at ``args["smoke_gpt"]`` widths.  Also the home of
    everything that must be computed where the chip is and never in the
    driver: the device report, the greedy ``generate`` reference streams,
    and the persistent-compile-cache hit count of this boot."""
    import jax
    import jax.monitoring
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.models import greedy_generate

    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1
    jax.monitoring.register_event_listener(on_event)

    devices = jax.devices()
    if args["smoke_require_tpu"] and devices[0].platform != "tpu":
        raise RuntimeError(f"no TPU: jax reports {devices[0]}")
    t0 = time.monotonic()
    cfg, params = _build_gpt(args)
    reference = []
    for prompt, n in args["smoke_requests"]:
        out = greedy_generate(cfg, params,
                              jnp.asarray(prompt, jnp.int32)[None, :], n)
        reference.append(np.asarray(out)[0, len(prompt):].tolist())
    report = {"device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)},
              "cache_dir": jax.config.jax_compilation_cache_dir,
              "reference": reference,
              "reference_secs": round(time.monotonic() - t0, 1),
              "persistent_cache": dict(cache_events),
              "n_params": int(sum(np.prod(p.shape)
                                  for p in jax.tree.leaves(params)))}
    with open(args["smoke_report"], "w") as f:
        json.dump(report, f)
    return cfg, params


def _build_gpt(args):
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(dtype=jnp.dtype(args["smoke_dtype"]),
                    **args["smoke_gpt"])
    params = jax.jit(lambda: GPT(cfg).init(
        jax.random.key(args["smoke_seed"]),
        jnp.ones((1, 4), jnp.int32))["params"])()
    return cfg, params


def verify_map_fun(args, ctx):
    """Teacher-forced check of served streams, in a process of its own
    started after the tier shut down (it owns the chip alone): ONE full
    forward of the reference model over ``prompt + served stream`` scores
    every served token against the reference's own logits at its position
    — ``gap`` = (max logit − served token's logit) / std(logits)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.models import GPT

    cfg, params = _build_gpt(args)
    items = args["smoke_items"]
    width = -(-max(len(p) + len(s) for p, s in items) // 64) * 64
    ids = np.zeros((len(items), width), np.int32)
    for row, (prompt, stream) in enumerate(items):
        ids[row, :len(prompt) + len(stream)] = prompt + stream

    @jax.jit
    def score(params, ids):
        logits = GPT(cfg).apply({"params": params}, ids)      # [B, T, V] f32
        nxt = jnp.take_along_axis(logits[:, :-1], ids[:, 1:, None],
                                  axis=-1)[..., 0]
        return ((logits[:, :-1].max(-1) - nxt) / logits[:, :-1].std(-1),
                logits[:, :-1].argmax(-1))

    gaps, best = (np.asarray(a) for a in score(params, jnp.asarray(ids)))
    out = []
    for row, (prompt, stream) in enumerate(items):
        sl = slice(len(prompt) - 1, len(prompt) - 1 + len(stream))
        out.append({"gaps": [float(g) for g in gaps[row, sl]],
                    "argmax": [int(t) for t in best[row, sl]]})
    with open(args["smoke_report"], "w") as f:
        json.dump(out, f)


def run_verify_phase(items, *, gpt: dict, dtype: str, seed: int,
                     worker_env: dict | None = None) -> list[dict]:
    """Score ``items`` (``[(prompt, served_stream)]``) against the reference
    model by teacher forcing; returns one ``{"gaps", "argmax"}`` per item.
    Runs through ``TPUCluster.run`` like any job: one worker, which owns
    the chip."""
    from tensorflowonspark_tpu import InputMode, TPUCluster

    workdir = tempfile.mkdtemp(prefix="chip_smoke_verify_")
    report_path = os.path.join(workdir, "verify_report.json")
    cluster = TPUCluster.run(
        verify_map_fun,
        {"smoke_gpt": dict(gpt), "smoke_dtype": dtype, "smoke_seed": seed,
         "smoke_items": [(list(p), list(s)) for p, s in items],
         "smoke_report": report_path},
        num_workers=1, input_mode=InputMode.TENSORFLOW, working_dir=workdir,
        worker_env=worker_env, reservation_timeout=300)
    cluster.shutdown(timeout=900)
    with open(report_path) as f:
        return json.load(f)


def _maps_accelerator_runtime(pid: int) -> bool:
    """Whether process ``pid`` has jaxlib or libtpu mapped — i.e. has
    imported jax at all.  Read from outside, so it needs no cooperation
    from the process it judges."""
    with open(f"/proc/{pid}/maps") as f:
        maps = f.read()
    return "libtpu" in maps or "jaxlib" in maps


def _aot_counts(serving, eid: int) -> dict:
    """AOT load/compile counts of replica ``eid`` as carried to the driver
    by its heartbeat (``tfos_replica_aot_resolves_total``)."""
    node = serving.metrics()["nodes"].get(eid, {})
    entry = (node.get("metrics") or {}).get(
        "tfos_replica_aot_resolves_total") or {}
    out = {"load": 0, "compile": 0, "error": 0}
    for labels, value in entry.get("samples", []):
        out[labels["outcome"]] = int(value)
    return out


def run_serve_phase(*, aot_cache: bool = False, mesh: dict | None = None,
                    seed: int = 0, require_tpu: bool = True,
                    worker_env: dict | None = None,
                    gpt: dict = GPT2_124M, dtype: str = "bfloat16",
                    prompt_lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS,
                    kv_page_tokens: int = 16) -> dict:
    """Boot the serving tier once, stream the requests concurrently, check
    every stream against the replica-side greedy reference, shut down.
    Returns the replica's report plus the streams and AOT counts."""
    import numpy as np

    from tensorflowonspark_tpu.serving import ServingCluster

    rng = np.random.default_rng(seed)
    requests = [(rng.integers(0, gpt["vocab_size"], (n,)).tolist(),
                 new_tokens) for n in prompt_lens]
    workdir = tempfile.mkdtemp(prefix="chip_smoke_serve_")
    report_path = os.path.join(workdir, "serve_report.json")
    t0 = time.monotonic()
    serving = ServingCluster.run(
        gpt_builder, 1, max_batch=4,
        batcher_kwargs={"kv_page_tokens": kv_page_tokens},
        replica_args={"smoke_gpt": dict(gpt), "smoke_dtype": dtype,
                      "smoke_seed": seed, "smoke_requests": requests,
                      "smoke_require_tpu": require_tpu,
                      "smoke_report": report_path},
        aot_cache=aot_cache, mesh=mesh, working_dir=workdir,
        worker_env=worker_env, reservation_timeout=300)
    streams: dict[int, list[int]] = {}
    errors: list[str] = []
    members_off_chip = None
    try:
        def run_client(i: int) -> None:
            prompt, n = requests[i]
            try:
                with serving.client() as c:
                    deltas = list(c.generate_stream(
                        np.asarray(prompt, np.int32), n, timeout=900))
                streams[i] = [int(t) for d in deltas for t in d]
            except Exception as e:  # surfaced below, with the request
                errors.append(f"request {i}: {e!r}")

        threads = [threading.Thread(target=run_client, args=(i,),
                                    daemon=True)
                   for i in range(len(requests))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        if errors or any(t.is_alive() for t in threads):
            raise RuntimeError(f"serve: requests failed or hung: {errors}")
        boot_to_done = round(time.monotonic() - t0, 1)
        if mesh:
            # ranks >= 1 of the gang must never have touched jax: the
            # leader's process owns every chip of the host
            procs = serving.cluster.backend.procs
            leader, members = procs[0], procs[1:]
            if not _maps_accelerator_runtime(leader.pid):
                raise RuntimeError("serve: the gang leader shows no jax "
                                   "runtime — the /proc check is blind")
            on_chip = [p.pid for p in members
                       if _maps_accelerator_runtime(p.pid)]
            if on_chip or not members:
                raise RuntimeError(f"serve: gang members {on_chip} loaded "
                                   f"the jax runtime ({len(members)} members)")
            members_off_chip = len(members)
        aot = _aot_counts(serving, 0)
        deadline = time.monotonic() + 30      # counters ride the heartbeat
        while aot_cache and aot["load"] + aot["compile"] == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.5)
            aot = _aot_counts(serving, 0)
    finally:
        serving.shutdown(timeout=300)
    with open(report_path) as f:
        report = json.load(f)

    for i, (prompt, n) in enumerate(requests):
        if len(streams[i]) != n:
            raise RuntimeError(f"serve: request {i} returned "
                               f"{len(streams[i])} of {n} tokens")
    # token for token against greedy generate; a stream that leaves the
    # reference must do so at a near-tie of the reference's own logits, and
    # is then checked to its end by teacher forcing (TIE_TOL_SIGMAS)
    reference = report.pop("reference")
    strayed = [i for i in range(len(requests)) if streams[i] != reference[i]]
    near_ties = []
    if strayed:
        scored = run_verify_phase(
            [(requests[i][0], streams[i]) for i in strayed],
            gpt=gpt, dtype=dtype, seed=seed, worker_env=worker_env)
        for i, score in zip(strayed, scored):
            at = next(k for k, (a, b) in enumerate(zip(streams[i],
                                                       reference[i]))
                      if a != b)
            worst = max(score["gaps"])
            near_ties.append({"request": i, "first_diff_at": at,
                              "gap_there": score["gaps"][at],
                              "worst_gap": worst})
            if worst > TIE_TOL_SIGMAS:
                k = score["gaps"].index(worst)
                raise RuntimeError(
                    f"serve: request {i} (prompt {len(requests[i][0])} "
                    f"tokens) token {k} = {streams[i][k]} is {worst:.3g} "
                    f"logit deviations below the reference's choice "
                    f"{score['argmax'][k]} (tolerance {TIE_TOL_SIGMAS}); "
                    f"first difference from greedy generate at token {at}:"
                    f"\n got {streams[i]}\nwant {reference[i]}")
    report.update(
        prompts=[prompt for prompt, _ in requests],
        streams=[streams[i] for i in range(len(requests))],
        tokens_compared=sum(n for _, n in requests),
        tokens_identical=sum(a == b for i in range(len(requests))
                             for a, b in zip(streams[i], reference[i])),
        streams_identical=len(requests) - len(strayed), near_ties=near_ties,
        aot=aot, boot_to_done_secs=boot_to_done,
        members_off_chip=members_off_chip)
    return report


# ------------------------------------------------------------------ the runs

def _tfrecord_codec() -> str:
    from tensorflowonspark_tpu import tfrecord

    return tfrecord.codec()


def _serve_facts(r: dict) -> dict:
    return dict(device=r["device"], tokens_compared=r["tokens_compared"],
                tokens_identical=r["tokens_identical"],
                streams_identical=r["streams_identical"],
                near_ties=r["near_ties"],
                requests=len(r["streams"]), aot=r["aot"],
                persistent_cache=r["persistent_cache"],
                cache_dir=r["cache_dir"], n_params=r["n_params"],
                reference_secs=r["reference_secs"],
                boot_to_done_secs=r["boot_to_done_secs"])


def _train_facts(r: dict) -> dict:
    return dict(device=r["device"], steps=len(r["losses"]),
                losses=r["losses"], step_secs=r["step_secs"],
                shm_conns=r["shm_conns"],
                param_shard_devices=r["param_shard_devices"],
                batch_shard_devices=r["batch_shard_devices"],
                cache_dir=r["cache_dir"], n_params=r["n_params"],
                wall_secs=r["wall_secs"])


def one_chip() -> dict:
    say("host", tfrecord_codec=_tfrecord_codec())
    train = off_jax(run_train_phase)
    say("train resnet50 b128@224 bf16 dp=1", **_train_facts(train))
    plain = off_jax(run_serve_phase)
    say("serve gpt2-124m jit", **_serve_facts(plain))
    first = off_jax(run_serve_phase, aot_cache=True)
    say("serve gpt2-124m aot boot 1", **_serve_facts(first))
    second = off_jax(run_serve_phase, aot_cache=True)
    say("serve gpt2-124m aot boot 2", **_serve_facts(second))
    if second["aot"]["load"] < 1 or second["aot"]["compile"] != 0:
        raise RuntimeError(f"serve: second AOT boot did not load its "
                           f"executables: {second['aot']}")
    if second["persistent_cache"]["hits"] < 1:
        raise RuntimeError(f"serve: second boot read nothing from the "
                           f"persistent cache: {second['persistent_cache']}")
    devices = [train["device"], plain["device"], first["device"],
               second["device"]]
    return _same_device(devices, want_count=1)


def four_chips() -> dict:
    single = off_jax(run_train_phase, dp=1)
    say("train resnet50 b128@224 bf16 dp=1", **_train_facts(single))
    spread = off_jax(run_train_phase, dp=4)
    say("train resnet50 b128@224 bf16 dp=4", **_train_facts(spread))
    worst = max(abs(a - b) / abs(a)
                for a, b in zip(single["losses"], spread["losses"]))
    say("train dp=4 vs dp=1", max_rel_loss_diff=worst, rtol=DP_LOSS_RTOL)
    if worst > DP_LOSS_RTOL:
        raise RuntimeError(f"train: dp=4 losses {spread['losses']} differ "
                           f"from dp=1 {single['losses']} by {worst:.3g}")
    # tp shards the vocabulary: pad it to a multiple of 128 x tp, the
    # Megatron convention (50257 does not divide by 4)
    gpt = dict(GPT2_124M, vocab_size=50304)
    solo = off_jax(run_serve_phase, gpt=gpt)
    say("serve gpt2-124m(vocab 50304) one chip", **_serve_facts(solo))
    gang = off_jax(run_serve_phase, gpt=gpt, mesh={"tp": 4})
    say("serve gpt2-124m(vocab 50304) gang tp=4",
        members_off_chip=gang["members_off_chip"], **_serve_facts(gang))
    # each run was held to the same greedy reference above (identical, or
    # astray only at near-ties of the reference's logits); against each
    # other they are reported token for token
    say("serve tp=4 vs one chip",
        tokens_compared=gang["tokens_compared"],
        tokens_identical=sum(a == b for s, g in zip(solo["streams"],
                                                    gang["streams"])
                             for a, b in zip(s, g)),
        streams_identical=sum(s == g for s, g in zip(solo["streams"],
                                                     gang["streams"])))
    return _same_device([single["device"], spread["device"], solo["device"],
                         gang["device"]], want_count=4)


def _same_device(devices: list[dict], want_count: int) -> dict:
    device = devices[0]
    if any(d != device for d in devices):
        raise RuntimeError(f"phases ran on different devices: {devices}")
    if device["platform"] != "tpu" or device["count"] != want_count:
        raise RuntimeError(f"not {want_count} TPU chip(s): {device}")
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the path across chips (dp=4 train, tp=4 "
                         "serving gang) and what it is compared with")
    chips = ap.parse_args().chips
    device = one_chip() if chips == 1 else four_chips()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
