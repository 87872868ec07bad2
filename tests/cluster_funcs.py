"""Top-level map_fun fixtures for cluster integration tests.

Must live in an importable module so ``multiprocessing`` spawn can pickle
them — the same constraint Spark puts on closures shipped to executors.
Mirrors the reference's tiny inline map_funs (SURVEY.md §4: orchestration is
tested with trivial functions, real models live in examples/).
"""

import os


def fn_noop(args, ctx):
    """Registers, does nothing, exits cleanly."""


def fn_write_role(args, ctx):
    """Record each node's role assignment for template assertions."""
    path = os.path.join(ctx.working_dir, f"role.{ctx.executor_id}")
    with open(path, "w") as f:
        f.write(f"{ctx.job_name}:{ctx.task_index}:{int(ctx.is_chief)}:{ctx.num_workers}")


def fn_sum_feed(args, ctx):
    """Consume the feed, write the running sum (train-mode round trip)."""
    feed = ctx.get_data_feed(train_mode=True)
    total = 0
    count = 0
    while not feed.should_stop():
        batch = feed.next_batch(args["batch_size"], timeout=30)
        total += sum(batch)
        count += len(batch)
    with open(os.path.join(ctx.working_dir, f"sum.{ctx.executor_id}"), "w") as f:
        f.write(f"{total}:{count}")


def fn_square_inference(args, ctx):
    """Echo x**2 for every sample (inference round trip)."""
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
        batch = feed.next_batch(4, timeout=30)
        if batch:
            feed.batch_results([x * x for x in batch])


def fn_tiny_batch_inference(args, ctx):
    """Emit one result message per sample — maximal output-queue pressure
    (regression: inference must drain results while its puts are blocked)."""
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
        batch = feed.next_batch(1, timeout=30)
        if batch:
            feed.batch_results([x + 1000 for x in batch])


def fn_crash(args, ctx):
    raise ValueError("deliberate failure for error-propagation test")


def fn_crash_infra(args, ctx):
    """Crash with an infra-shaped error (retried by run_with_recovery's
    classifier, unlike fn_crash's deterministic ValueError)."""
    raise ConnectionError("injected infra failure")


def fn_report_steps(args, ctx):
    """Step loop that reports progress to the health monitor — the chaos
    tests' 'training': deterministic steps the TFOS_CHAOS plan can target."""
    import time

    total = int(args.get("total_steps", 100))
    for s in range(1, total + 1):
        ctx.report_step(s)
        time.sleep(float(args.get("step_secs", 0.1)))
    with open(os.path.join(ctx.working_dir, f"steps.{ctx.executor_id}"), "w") as f:
        f.write(str(total))


def fn_goodput_metrics_steps(args, ctx):
    """Telemetry-plane workload: a step loop recording goodput via
    ``ctx.goodput()`` and a registry counter — both must become visible
    from the DRIVER through the heartbeat-carried snapshots.  Loops until
    the driver sets kv ``stop_goodput`` (or ``max_secs`` elapses)."""
    import time

    from tensorflowonspark_tpu import metrics as tpu_metrics

    rec = ctx.goodput()
    demo = tpu_metrics.get_registry().counter(
        "tfos_test_worker_steps_total", "steps run by the test map_fun")
    deadline = time.monotonic() + float(args.get("max_secs", 30))
    step = 0
    while time.monotonic() < deadline:
        if ctx.mgr is not None and ctx.mgr.kv_get("stop_goodput"):
            break
        step += 1
        with rec.time("step"):
            time.sleep(0.02)
        demo.inc()
        ctx.report_step(step)
        time.sleep(0.02)


def fn_report_then_sleep(args, ctx):
    """Report a couple of steps (arming the hang watchdog / giving a
    chaos ``stall`` its trigger), then block — the wedged-worker shape."""
    import time

    ctx.report_step(1)
    ctx.report_step(2)
    time.sleep(float(args.get("sleep_secs", 120)))


def fn_train_ckpt_report(args, ctx):
    """Deterministic 'training' with per-step orbax checkpoints and
    ``ctx.report_step`` progress — the kill/restore chaos workload.  Unlike
    ``fn_train_checkpoint_crash_once`` it injects nothing itself: the
    TFOS_CHAOS plan supplies the fault.  Appends ``<wall_time> <start>``
    per attempt to ``resume.<id>`` so tests/bench assert resume points and
    restart-to-first-step latency."""
    import time

    import numpy as np

    from tensorflowonspark_tpu.checkpoint import CheckpointManager

    total = int(args["total_steps"])
    ckpt = CheckpointManager(args["model_dir"])
    start, w = 0, np.zeros(())
    if ckpt.latest_step() is not None:
        state = ckpt.restore()
        start, w = int(state["step"]), np.asarray(state["w"])
    with open(os.path.join(ctx.working_dir, f"resume.{ctx.executor_id}"), "a") as f:
        f.write(f"{time.time():.6f} {start}\n")

    for s in range(start, total):
        w = w + 1.0
        step = s + 1
        if ctx.is_chief:
            ckpt.save(step, {"step": np.asarray(step), "w": w}, force=True)
            ckpt.wait()  # durable BEFORE report_step can fire a chaos kill
        ctx.report_step(step)
        time.sleep(float(args.get("step_secs", 0.05)))
    if ctx.is_chief:
        ckpt.close()


def fn_crash_before_register(args, ctx):  # pragma: no cover - not called
    raise RuntimeError("unused")


def fn_train_linear_export(args, ctx):
    """Train y ≈ w·x + b from the feed; chief exports a serving signature.

    The pipeline-test workload (reference model: the small Keras model in
    ``tests/test_pipeline.py`` upstream): real SGD on the fed data followed
    by a chief-only export that TFModel.transform loads back.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    feed = ctx.get_data_feed(train_mode=True)
    w = jnp.zeros(())
    b = jnp.zeros(())
    lr = args.lr

    @jax.jit
    def step(w, b, x, y):
        def loss(w, b):
            return jnp.mean((w * x + b - y) ** 2)

        gw, gb = jax.grad(loss, argnums=(0, 1))(w, b)
        return w - lr * gw, b - lr * gb

    while not feed.should_stop():
        batch = feed.next_batch_arrays(args.batch_size, timeout=30)
        if batch is None:
            break
        x, y = batch
        w, b = step(w, b, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32))

    if ctx.is_chief:
        from tensorflowonspark_tpu.checkpoint import export_model

        def serve(p, x):
            return p["w"] * x + p["b"]

        export_model(args.export_dir, serve, {"w": w, "b": b},
                     [np.zeros((2,), np.float32)],
                     input_names=["x"], output_names=["y"], is_chief=True)


def fn_terminating_consumer(args, ctx):
    """Read a few batches then terminate early (early-stop semantics)."""
    feed = ctx.get_data_feed()
    feed.next_batch(4, timeout=30)
    feed.terminate(drain_secs=1.0)
    with open(os.path.join(ctx.working_dir, f"term.{ctx.executor_id}"), "w") as f:
        f.write("terminated")


def fn_distributed_pjit_train(args, ctx):
    """Cross-process SPMD training: ``ctx.initialize_distributed()`` over
    loopback (CPU backend, gloo collectives) + one jitted train step whose
    mesh spans BOTH worker processes.

    Exercises the composed path SURVEY.md §4 calls the "local-cluster
    pattern": agents/local procs + coordination service + cross-process
    collectives (reference analogue: TF_CONFIG + MultiWorkerMirrored over
    two Spark executors).  Writes ``dist.<id>`` with the final loss/weights
    so the driver can compare against the single-process value.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    ctx.initialize_distributed()

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.process_count() == ctx.num_workers, jax.process_count()
    devs = jax.devices()  # global device list, across processes
    mesh = Mesh(np.array(devs), ("dp",))
    rep = NamedSharding(mesh, P())

    rng = np.random.default_rng(0)
    X = rng.standard_normal((8, 4)).astype(np.float32)
    y = (X @ np.arange(1.0, 5.0, dtype=np.float32)).astype(np.float32)
    xsh = NamedSharding(mesh, P("dp"))
    Xg = jax.make_array_from_callback(X.shape, xsh, lambda i: X[i])
    yg = jax.make_array_from_callback(y.shape, xsh, lambda i: y[i])

    lr = 0.1

    @jax.jit
    def train_step(w, X, y):
        def loss_fn(w):
            return jnp.mean((X @ w - y) ** 2)  # mean over the GLOBAL batch

        loss, g = jax.value_and_grad(loss_fn)(w)
        return w - lr * g, loss

    w = jax.device_put(jnp.zeros((4,), jnp.float32), rep)
    for _ in range(int(args.get("steps", 3))):
        w, loss = train_step(w, Xg, yg)

    path = os.path.join(ctx.working_dir, f"dist.{ctx.executor_id}")
    w_host = np.asarray(jax.device_get(w))
    with open(path, "w") as f:
        f.write(f"{jax.process_count()}:{len(devs)}:{float(loss):.8f}:"
                + ",".join(f"{v:.8f}" for v in w_host))


def fn_distributed_multidev_train(args, ctx):
    """Multi-process × MULTI-DEVICE GSPMD: 2 processes × 4 CPU devices each
    → one 8-device global mesh — the actual TPU-pod regime (SURVEY.md §7
    hard part 1) that neither the 2×1-device tests nor the single-process
    8-device dryrun reach.

    Two mesh layouts, switched by ``args["span_process_boundary"]``:
      False — dp2 ACROSS the processes, fsdp2·tp2 INSIDE each (the layout
        a pod would use: high-traffic axes on-host);
      True — device order transposed so every tp PAIR spans the process
        boundary (tp collectives ride the inter-process link) — the
        composition no single-process test can exercise.

    Trains a tanh MLP and writes loss trajectory + a replicated parameter
    fingerprint; the driver compares both against a numpy oracle.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    ctx.initialize_distributed()

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.parallel import make_mesh
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec

    assert jax.process_count() == 2, jax.process_count()
    devs = jax.devices()
    assert len(devs) == 8, f"need 2 procs x 4 devices, got {len(devs)}"
    span = bool(args.get("span_process_boundary"))
    if span:
        # transpose the device grid: tp pairs become (proc0_dev, proc1_dev)
        grid = np.array(devs).reshape(2, 4).T.reshape(-1)
        mesh = make_mesh(MeshSpec(dp=4, fsdp=1, tp=2), devices=grid)
        pairs = mesh.devices.reshape(4, 2)
        for pair in pairs:
            procs = {d.process_index for d in pair}
            assert procs == {0, 1}, f"tp pair does not span processes: {procs}"
        w1_spec, data_spec = P(None, "tp"), P("dp")
    else:
        mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2), devices=devs)
        outer = mesh.devices.reshape(2, -1)
        assert {d.process_index for d in outer[0]} == {0}
        assert {d.process_index for d in outer[1]} == {1}
        w1_spec, data_spec = P("fsdp", "tp"), P(("dp", "fsdp"))

    _mlp_train_and_write(args, ctx, mesh, w1_spec=w1_spec,
                         data_spec=data_spec, out_prefix="mdev")


def _mlp_train_and_write(args, ctx, mesh, *, w1_spec, data_spec,
                         out_prefix):
    """Shared tanh-MLP parity harness for the multi-process mesh workers:
    same seeds/lr/shapes as ``tests.test_distributed._mlp_oracle``, so
    every caller's output file compares against the one oracle.  Writes
    ``<out_prefix>.<executor_id>`` with the loss trajectory + a replicated
    parameter fingerprint (the sharded weights themselves are not
    addressable from any single process)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = jax.devices()
    rng = np.random.default_rng(0)
    X_np = rng.standard_normal((8, 4)).astype(np.float32)
    y_np = rng.standard_normal((8,)).astype(np.float32)
    W1_np = (rng.standard_normal((4, 8)) * 0.5).astype(np.float32)
    W2_np = (rng.standard_normal((8,)) * 0.5).astype(np.float32)

    def put(a, spec):
        sh = NamedSharding(mesh, spec)
        return jax.make_array_from_callback(a.shape, sh, lambda i: a[i])

    X = put(X_np, data_spec)
    y = put(y_np, P(data_spec[0]) if data_spec else P())
    W1 = put(W1_np, w1_spec)
    W2 = put(W2_np, P("tp"))

    lr = 0.1

    @jax.jit
    def train_step(W1, W2, X, y):
        def loss_fn(W1, W2):
            h = jnp.tanh(X @ W1)
            return jnp.mean((h @ W2 - y) ** 2)

        loss, (g1, g2) = jax.value_and_grad(loss_fn, argnums=(0, 1))(W1, W2)
        return W1 - lr * g1, W2 - lr * g2, loss

    losses = []
    for _ in range(int(args.get("steps", 3))):
        W1, W2, loss = train_step(W1, W2, X, y)
        losses.append(float(loss))
    fp = float(jax.jit(lambda a, b: jnp.sum(a ** 2) + jnp.sum(b ** 2))(W1, W2))

    path = os.path.join(ctx.working_dir, f"{out_prefix}.{ctx.executor_id}")
    with open(path, "w") as f:
        f.write(f"{jax.process_count()}:{len(devs)}:"
                + ",".join(f"{v:.8f}" for v in losses) + f":{fp:.8f}")


def fn_distributed_hybrid_mesh_train(args, ctx):
    """``make_hybrid_mesh`` with its ``process_index`` slice fallback, on a
    REAL process boundary: 2 processes × 4 CPU devices = 2 "slices", no
    ``slice_key`` override — dp lands across the processes (the DCN
    analogue), fsdp·tp inside each.  Same MLP math as
    ``fn_distributed_multidev_train`` so the driver compares against the
    same single-process oracle."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    ctx.initialize_distributed()

    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.parallel import make_hybrid_mesh

    assert jax.process_count() == 2, jax.process_count()
    devs = jax.devices()
    assert len(devs) == 8, f"need 2 procs x 4 devices, got {len(devs)}"
    mesh = make_hybrid_mesh(ici=dict(fsdp=2, tp=2), dcn=dict(dp=2))
    assert dict(mesh.shape) == {"pp": 1, "dp": 2, "fsdp": 2, "ep": 1,
                                "sp": 1, "tp": 2}, dict(mesh.shape)
    # each dp block must be exactly one process's devices (slice = process)
    blocks = mesh.devices.reshape(2, -1)
    assert {d.process_index for d in blocks[0]} == {0}
    assert {d.process_index for d in blocks[1]} == {1}

    _mlp_train_and_write(args, ctx, mesh, w1_spec=P("fsdp", "tp"),
                         data_spec=P(("dp", "fsdp")), out_prefix="hybrid")


def fn_distributed_pipeline_multidev(args, ctx):
    """GPipe across processes WITH multi-device stages: mesh pp2·dp2·tp2
    over 2 processes × 4 devices — each pipeline stage lives on one
    process and is itself Megatron-tp·dp-sharded
    (``make_transformer_stage``), so the stage-hop ppermute crosses the
    process boundary while tp psums stay inside each stage."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    ctx.initialize_distributed()

    import numpy as np
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.parallel import (make_mesh, pipeline_apply,
                                                make_transformer_stage,
                                                stack_stage_params)
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec

    devs = jax.devices()
    assert len(devs) == 8 and jax.process_count() == 2
    mesh = make_mesh(MeshSpec(pp=2, dp=2, tp=2), devices=devs)
    stages = mesh.devices.reshape(2, -1)  # pp outermost -> one per process
    assert {d.process_index for d in stages[0]} == {0}
    assert {d.process_index for d in stages[1]} == {1}

    hid, heads, ffn, seq, vocab = 32, 4, 64, 8, 64
    num_mb, steps = 2, int(args.get("steps", 2))
    stage_fn, init_fn, param_specs = make_transformer_stage(
        hid, heads, ffn, tp=2, causal=True)
    tx = optax.adamw(1e-3)
    batch = 2 * num_mb * 2  # 2 rows per microbatch per dp shard
    data_spec = P(("dp", "fsdp"), "sp", None)
    rng = np.random.default_rng(0)
    ids_np = rng.integers(0, vocab, (batch, seq)).astype(np.int32)

    def init_params():
        keys = jax.random.split(jax.random.key(0), 2)
        return {
            "emb": jax.random.normal(jax.random.key(1), (vocab, hid)) * 0.02,
            "stages": stack_stage_params([init_fn(k) for k in keys]),
        }

    p_sh = {
        "emb": NamedSharding(mesh, P()),
        "stages": jax.tree.map(
            lambda s: NamedSharding(mesh, P("pp", *s)), param_specs,
            is_leaf=lambda s: isinstance(s, P)),
    }

    with mesh:
        params = jax.jit(init_params, out_shardings=p_sh)()
        opt_state = jax.jit(tx.init)(params)
        ids = jax.make_array_from_callback(
            ids_np.shape, NamedSharding(mesh, P(("dp", "fsdp"), None)),
            lambda i: ids_np[i])

        def loss_fn(p):
            x = p["emb"][ids]
            y = pipeline_apply(mesh, stage_fn, p["stages"], x,
                               num_microbatches=num_mb,
                               param_specs=param_specs, data_spec=data_spec)
            logits = jnp.einsum("bsh,vh->bsv", y, p["emb"])
            labels = jnp.roll(ids, -1, axis=1)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()

        @jax.jit
        def train_step(p, o):
            loss, grads = jax.value_and_grad(loss_fn)(p)
            updates, o = tx.update(grads, o, p)
            return optax.apply_updates(p, updates), o, loss

        losses = []
        for _ in range(steps):
            params, opt_state, loss = train_step(params, opt_state)
            losses.append(float(loss))

    path = os.path.join(ctx.working_dir, f"mpipe.{ctx.executor_id}")
    with open(path, "w") as f:
        f.write(":".join(f"{v:.8f}" for v in losses))


def fn_train_checkpoint_crash_once(args, ctx):
    """Deterministic 'training' with orbax checkpoints; injects ONE chief
    crash mid-run on the first attempt (sentinel file) so
    ``run_with_recovery``'s relaunch-then-resume path is exercised.

    Appends each attempt's start step to ``resume.<id>`` — the test asserts
    the relaunch resumed from the checkpoint, not step 0.
    """
    import numpy as np

    from tensorflowonspark_tpu.checkpoint import CheckpointManager

    total, crash_at = args["total_steps"], args["crash_at"]
    ckpt = CheckpointManager(args["model_dir"])
    start, w = 0, np.zeros(())
    latest = ckpt.latest_step()
    if latest is not None:
        state = ckpt.restore()
        start, w = int(state["step"]), np.asarray(state["w"])
    with open(os.path.join(ctx.working_dir, f"resume.{ctx.executor_id}"), "a") as f:
        f.write(f"{start}\n")

    sentinel = os.path.join(ctx.working_dir, "crash-injected")
    for s in range(start, total):
        w = w + 1.0
        step = s + 1
        if ctx.is_chief and step == crash_at and not os.path.exists(sentinel):
            ckpt.save(step, {"step": np.asarray(step), "w": w}, force=True)
            ckpt.wait()
            with open(sentinel, "w"):
                pass
            raise RuntimeError("injected preemption")
    if ctx.is_chief:
        ckpt.save(total, {"step": np.asarray(total), "w": w}, force=True)
        ckpt.close()


def fn_distributed_pipeline_train(args, ctx):
    """Cross-process PIPELINE parallelism: a pp=2 mesh spanning two worker
    processes, so the GPipe schedule's stage-hop ``ppermute`` crosses a
    real process boundary (gloo) — the multihost path single-process tests
    can't reach.  Writes ``pipe.<id>`` with the loss trajectory."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    ctx.initialize_distributed()

    import numpy as np
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.parallel import make_mesh, pipeline_apply
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = jax.devices()
    assert len(devs) == 2 and jax.process_count() == 2
    mesh = make_mesh(MeshSpec(pp=2, dp=1), devices=devs)

    def stage_fn(p, x):
        return x + jnp.tanh(x @ p["w"])

    hid, num_mb, steps = 8, 2, int(args.get("steps", 2))
    rng = np.random.default_rng(0)
    w0 = (rng.standard_normal((2, hid, hid)) * 0.1).astype(np.float32)
    x_np = rng.standard_normal((4, hid)).astype(np.float32)
    tx = optax.sgd(0.1)

    stacked_sh = NamedSharding(mesh, P("pp", None, None))
    stacked = jax.make_array_from_callback(
        w0.shape, stacked_sh, lambda i: w0[i])
    params = {"w": stacked}
    opt_state = jax.jit(tx.init)(params)
    x = jax.device_put(jnp.asarray(x_np), NamedSharding(mesh, P()))

    @jax.jit
    def train_step(params, opt_state, x):
        def loss_fn(p):
            y = pipeline_apply(mesh, stage_fn, p, x, num_microbatches=num_mb)
            return jnp.mean(y ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state, x)
        losses.append(float(loss))

    path = os.path.join(ctx.working_dir, f"pipe.{ctx.executor_id}")
    with open(path, "w") as f:
        f.write(":".join(f"{v:.8f}" for v in losses))


def fn_write_cache_env(args, ctx):
    """Record the worker-side compile-cache env contract (node.run must
    export the JAX cache vars before the user fn: the directory the
    environment names, else the one fixed in-checkout path)."""
    path = os.path.join(ctx.working_dir, f"cacheenv.{ctx.executor_id}")
    with open(path, "w") as f:
        f.write(os.environ.get("JAX_COMPILATION_CACHE_DIR", "MISSING") + ":"
                + os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                                 "MISSING"))


def fn_publish_crash_once(args, ctx):
    """Continual-loop crash-atomicity workload: the first attempt
    publishes a multi-MB candidate and SIGKILLs itself immediately —
    the driver's collector is racing that enqueue, so it either never
    sees the message or dies mid-``get`` on a torn stream; a partial
    payload must never surface.  The second attempt (sentinel present)
    publishes a small clean candidate and exits 0.  Payloads are
    deterministic ``np.full`` so the driver asserts whole-or-nothing."""
    import signal

    import numpy as np

    from tensorflowonspark_tpu.continual import CheckpointPublisher

    pub = CheckpointPublisher(ctx, args["model"], timeout=30.0)
    sentinel = os.path.join(ctx.working_dir, "publish-crash-injected")
    if not os.path.exists(sentinel):
        with open(sentinel, "w"):
            pass
        n = int(args.get("big_elems", 1 << 20))
        pub.publish(1, {"w": np.full((n,), 1.0, np.float64)})
        os.kill(os.getpid(), signal.SIGKILL)
    pub.publish(2, {"w": np.full((8,), 2.0, np.float64)})


def batch_predict_scale(model, records, trial_params):
    """Batch-plane scorer over array shards: one bytes record per row,
    scaled by the grid trial's ``scale`` (default 2.0) — deterministic, so
    restarted and uninterrupted runs are byte-identical."""
    import numpy as np

    scale = float((trial_params or {}).get("scale", 2.0))
    arr = np.asarray(records, dtype=np.float64)
    return [(row * scale).tobytes() for row in arr]


def batch_predict_scale_paced(model, records, trial_params):
    """``batch_predict_scale`` with a small per-shard delay: paces the
    queue so a mid-job chaos kill reliably lands while work is still
    outstanding (a free-running scorer lets one worker drain everything
    before the victim's trigger step).  Output is byte-identical to the
    unpaced scorer."""
    import time

    time.sleep(0.1)
    return batch_predict_scale(model, records, trial_params)


def batch_predict_len(model, records, trial_params):
    """Batch-plane scorer over tfrecord shards: echo each raw record's
    length (records arrive as bytes)."""
    return [len(r).to_bytes(4, "little") for r in records]


def batch_model_builder_offset(args):
    """Model builder fixture: built once per worker process; the returned
    'model' is an offset the predict fn applies."""
    return {"offset": float(args.get("offset", 100.0))}


def batch_predict_with_model(model, records, trial_params):
    """Proves the builder's model reaches every predict call."""
    import numpy as np

    arr = np.asarray(records, dtype=np.float64)
    return [(row + model["offset"]).tobytes() for row in arr]


def serving_tiny_gpt_builder(args):
    """Model builder for serving-tier tests (``serving.ServingCluster``):
    a deterministic seeded tiny GPT, rebuilt identically in every replica
    process AND by the driver-side oracle, so cluster outputs can be
    asserted greedy-exact against solo ``greedy_generate`` runs."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=83, hidden_size=32, num_layers=2, num_heads=4,
                    intermediate_size=64, max_position_embeddings=64,
                    dtype=jnp.float32, pos_encoding="rope")
    params = GPT(cfg).init(jax.random.key(int(args.get("seed", 0))),
                           jnp.ones((1, 4), jnp.int32))["params"]
    return cfg, params


def serving_cache_probe_builder(args):
    """``serving_tiny_gpt_builder`` that also records where this replica
    process keeps its compile caches (``args["cache_report"]``)."""
    import jax

    from tensorflowonspark_tpu import util

    with open(args["cache_report"], "w") as f:
        f.write(f"{jax.config.jax_compilation_cache_dir}\n"
                f"{util.aot_cache_dir()}")
    return serving_tiny_gpt_builder(args)


def shm_crash_server(pipe):
    """test_shm consumer-crash fixture: serve a queue (shm negotiation on),
    acknowledge the feed, then die HARD — no finally blocks, no atexit —
    simulating a worker crash while it still holds zero-copy leases."""
    from tensorflowonspark_tpu.queues import QueueServer

    srv = QueueServer(authkey=b"k" * 16, qnames=("input",), mode="local")
    addr = srv.start()
    pipe.send(addr)
    # hold the fed item's views so the lease is live at crash time
    item = srv.queue_get("input", timeout=30)
    pipe.send(int(item[0, 0]))  # prove the shm payload arrived intact
    pipe.recv()              # wait for the driver's kill order
    os._exit(1)


def serving_sharded_gpt_builder(args):
    """Model builder for SHARDED serving-tier tests: like
    ``serving_tiny_gpt_builder`` but with every tp-sharded dimension
    (vocab, heads, intermediate) divisible by the test gangs' tp=2/4,
    so the Megatron layout actually shards."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                    intermediate_size=64, max_position_embeddings=64,
                    dtype=jnp.float32, pos_encoding="rope")
    params = GPT(cfg).init(jax.random.key(int(args.get("seed", 0))),
                           jnp.ones((1, 4), jnp.int32))["params"]
    return cfg, params


def rollout_parity_cfg():
    """The estimator→serve parity test's tiny GPT config — ONE
    definition shared by the trainer, the batch-eval workers, the
    serving replicas, and the driver-side oracle."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPTConfig

    return GPTConfig(vocab_size=31, hidden_size=16, num_layers=1,
                     num_heads=2, intermediate_size=32,
                     max_position_embeddings=32, dtype=jnp.float32,
                     pos_encoding="rope")


def rollout_parity_builder(args):
    """Model builder restoring the estimator-trained checkpoint from
    ``args["model_dir"]`` (top level so spawn pickles it by reference)
    — the registry entry behind the estimator → eval → promote → serve
    parity path.  A target-less orbax restore returns flax
    ``Partitioned`` kernels as ``{"value": array}`` boxes; serving
    applies raw arrays, so unbox them."""
    from tensorflowonspark_tpu.checkpoint import CheckpointManager

    def unbox(tree):
        if isinstance(tree, dict):
            if set(tree) == {"value"}:
                return unbox(tree["value"])
            return {k: unbox(v) for k, v in tree.items()}
        return tree

    with CheckpointManager(args["model_dir"]) as ckpt:
        state = ckpt.restore()
    params = state["params"] if isinstance(state, dict) else state.params
    return rollout_parity_cfg(), unbox(params)


def rollout_parity_predict(model, records, trial_params):
    """Batch-plane predict fn for the parity test's GridSearch eval:
    greedy-decode each prompt record under the restored params."""
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.models import greedy_generate

    cfg, params = model
    out = []
    for rec in records:
        p = np.asarray(rec, np.int32).reshape(-1)
        toks = np.asarray(greedy_generate(
            cfg, params, jnp.asarray(p)[None, :],
            int(trial_params.get("budget", 4))))[0, p.size:]
        out.append(toks.astype(np.int32).tobytes())
    return out
