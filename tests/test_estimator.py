"""Estimator surface: train_and_evaluate, max_steps semantics, resume.

Every test in this module runs its body in a SUBPROCESS (one fresh
``pytest <this_file>::<test>`` child per test, see ``_isolated``): the
estimator suite carries a known pre-existing flake — a hard segfault
inside jax's CPU runtime (``_batched_device_put_impl`` /pjit lowering,
reproducible under CPU contention, predates the health/chaos PR) — and
a native crash in-process takes down the WHOLE pytest run, losing every
not-yet-run test with it.  Isolation fixes the blast radius, not the
symptom: a segfaulting child becomes one attributable test failure
(named signal in the assertion message) instead of an rc=139 session.
"""

import functools
import os
import subprocess
import sys

import numpy as np
import optax
import pytest

from tensorflowonspark_tpu.estimator import (Estimator, EvalSpec, TrainSpec,
                                             train_and_evaluate)

_CHILD_ENV = "TFOS_ESTIMATOR_ISOLATED"


def _isolated(fn):
    """Run the decorated test in a fresh pytest child process.

    Parent side: re-invoke ``pytest <file>::<name>`` with ``_CHILD_ENV``
    set and assert on the child's exit status, naming the signal when
    the child died natively.  Child side (env var present): run the test
    body normally.  Fixtures resolve in the child — the parent's are
    unused."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if os.environ.get(_CHILD_ENV) == "1":
            return fn(*args, **kwargs)
        cmd = [sys.executable, "-m", "pytest", "-q", "-x",
               "-p", "no:cacheprovider", "-p", "no:randomly",
               f"{os.path.abspath(__file__)}::{fn.__name__}"]
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=600,
            env={**os.environ, _CHILD_ENV: "1"})
        if proc.returncode != 0:
            died = (f"crashed natively with signal {-proc.returncode}"
                    if proc.returncode < 0
                    else f"failed (exit {proc.returncode})")
            raise AssertionError(
                f"isolated estimator test {fn.__name__} {died}\n"
                f"--- child stdout (tail) ---\n{proc.stdout[-4000:]}\n"
                f"--- child stderr (tail) ---\n{proc.stderr[-2000:]}")
    return wrapper


def _linreg_problem(seed=0, n=64, d=4):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(d, 1)).astype(np.float32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = x @ w_true
    return x, y


def _make_estimator(model_dir, save_every=10, tx=None, **kwargs):
    import jax.numpy as jnp

    def init_fn():
        return {"w": jnp.zeros((4, 1))}

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    def metrics_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return {"mse": jnp.mean((pred - batch["y"]) ** 2),
                "mae": jnp.mean(jnp.abs(pred - batch["y"]))}

    return Estimator(init_fn, loss_fn, tx or optax.sgd(0.1), str(model_dir),
                     eval_metrics_fn=metrics_fn, save_every_steps=save_every,
                     **kwargs)


def _batches(x, y, bs=16):
    def input_fn():
        for i in range(0, len(x), bs):
            yield {"x": x[i:i + bs], "y": y[i:i + bs]}
    return input_fn


@_isolated
def test_train_and_evaluate_learns_and_reports(tmp_path):
    x, y = _linreg_problem()
    with _make_estimator(tmp_path / "m") as est:
        baseline = est.evaluate(_batches(x, y), steps=2)["mse"]
        final = train_and_evaluate(
            est,
            TrainSpec(input_fn=_batches(x, y), max_steps=40),
            EvalSpec(input_fn=_batches(x, y), steps=4, throttle_steps=15))
        assert final["global_step"] == 40
        assert final["mse"] < baseline * 0.1, (baseline, final)
        assert "mae" in final


@_isolated
def test_max_steps_is_total_budget_and_resume_works(tmp_path):
    x, y = _linreg_problem()
    with _make_estimator(tmp_path / "m") as est:
        est.train(_batches(x, y), max_steps=12)
        assert est.global_step == 12
        w_after = np.asarray(est.params["w"])

    # "restart": a fresh Estimator on the same model_dir resumes at step 12
    with _make_estimator(tmp_path / "m") as est2:
        assert est2.global_step == 12
        np.testing.assert_allclose(np.asarray(est2.params["w"]), w_after)
        est2.train(_batches(x, y), max_steps=20)  # only the remaining 8
        assert est2.global_step == 20


@_isolated
def test_resume_at_max_steps_still_runs_final_eval(tmp_path):
    x, y = _linreg_problem()
    with _make_estimator(tmp_path / "m") as est:
        est.train(_batches(x, y), max_steps=10)
    # relaunch with the SAME budget: no training remains, but
    # train_and_evaluate must still deliver the final eval metrics
    with _make_estimator(tmp_path / "m") as est2:
        final = train_and_evaluate(
            est2,
            TrainSpec(input_fn=_batches(x, y), max_steps=10),
            EvalSpec(input_fn=_batches(x, y), steps=2, throttle_steps=5))
        assert final["global_step"] == 10
        assert "mse" in final


@_isolated
def test_export_serves_trained_params(tmp_path):
    import jax.numpy as jnp

    from tensorflowonspark_tpu.checkpoint import ExportedModel

    x, y = _linreg_problem()
    with _make_estimator(tmp_path / "m") as est:
        est.train(_batches(x, y), max_steps=30)
        w = np.asarray(est.params["w"])
        out = est.export(str(tmp_path / "export"),
                         lambda p, x: x @ p["w"],
                         [jnp.zeros((4, 4))])
    assert out is not None
    served = ExportedModel.load(str(tmp_path / "export"))
    out_vals = served(x[:8])
    pred = np.asarray(next(iter(out_vals.values()))
                      if isinstance(out_vals, dict) else out_vals)
    np.testing.assert_allclose(pred, x[:8] @ w, rtol=1e-5)

    # non-chief writes nothing
    with _make_estimator(tmp_path / "m") as est2:
        assert est2.export(str(tmp_path / "e2"), lambda p, x: x @ p["w"],
                           [np.zeros((4, 4))], is_chief=False) is None


@_isolated
def test_goodput_accounting(tmp_path):
    x, y = _linreg_problem()
    with _make_estimator(tmp_path / "m") as est:
        est.train(_batches(x, y), max_steps=8)
        g = est.goodput()
    assert g["counts"]["step"] == 8
    assert 0.0 < g["goodput"] <= 1.0
    for cat in ("init", "data", "step", "checkpoint"):
        assert g["secs"].get(cat, 0) >= 0


@_isolated
def test_predict_streams_batches(tmp_path):
    x, y = _linreg_problem()
    with _make_estimator(tmp_path / "m") as est:
        est.train(_batches(x, y), max_steps=30)
        w = np.asarray(est.params["w"])
        preds = list(est.predict(_batches(x, y),
                                 lambda p, b: b["x"] @ p["w"]))
    assert len(preds) == 4  # 64 samples / bs 16
    np.testing.assert_allclose(np.concatenate(preds), x @ w, rtol=1e-5)

    with _make_estimator(tmp_path / "m") as est2:
        import pytest as _pytest

        with _pytest.raises(ValueError, match="predict_fn"):
            next(est2.predict(_batches(x, y)))


@_isolated
def test_predict_params_override_and_goodput(tmp_path):
    """Satellite: ``predict(params=...)`` scores a candidate tree (grid
    trial / EMA weights) without touching trained state, and predict's
    input waits land in goodput()'s ``data`` bucket like train's."""
    x, y = _linreg_problem()
    ones = {"w": np.ones((4, 1), np.float32)}
    with _make_estimator(tmp_path / "m") as est:
        est.train(_batches(x, y), max_steps=20)
        base = est.goodput()
        w_trained = np.asarray(est.params["w"])

        preds = list(est.predict(_batches(x, y),
                                 lambda p, b: b["x"] @ p["w"], params=ones))
        np.testing.assert_allclose(np.concatenate(preds), x @ ones["w"],
                                   rtol=1e-5)
        # the override was per-call: trained params still serve by default
        np.testing.assert_allclose(np.asarray(est.params["w"]), w_trained)
        preds2 = list(est.predict(_batches(x, y),
                                  lambda p, b: b["x"] @ p["w"]))
        np.testing.assert_allclose(np.concatenate(preds2), x @ w_trained,
                                   rtol=1e-5)

        g = est.goodput()
        assert g["counts"]["data"] > base["counts"]["data"]
        assert g["secs"]["data"] >= base["secs"]["data"]
        assert g["counts"]["step"] > base["counts"]["step"]


@_isolated
def test_profile_steps_writes_trace(tmp_path):
    import glob
    import os

    import jax.numpy as jnp
    import optax

    x, y = _linreg_problem()

    def init_fn():
        return {"w": jnp.zeros((4, 1))}

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    model_dir = str(tmp_path / "m")
    with Estimator(init_fn, loss_fn, optax.sgd(0.1), model_dir,
                   profile_steps=(2, 4)) as est:
        est.train(_batches(x, y), max_steps=6)
        assert not est._profiling
    traces = glob.glob(os.path.join(model_dir, "tensorboard", "plugins",
                                    "profile", "*"))
    assert traces, "no xprof trace directory written"


@_isolated
def test_throttle_steps_must_be_positive():
    with pytest.raises(ValueError, match="throttle_steps"):
        EvalSpec(input_fn=lambda: iter(()), throttle_steps=0)


@_isolated
def test_empty_input_fn_raises(tmp_path):
    with _make_estimator(tmp_path / "m") as est:
        with pytest.raises(ValueError, match="no batches"):
            est.train(lambda: iter(()), max_steps=5)
        with pytest.raises(ValueError, match="no batches"):
            est.evaluate(lambda: iter(()), steps=2)


@_isolated
def test_enable_compilation_cache(tmp_path, monkeypatch):
    """One rule for the cache's place: the environment's directory where
    it names one — then nothing in code sets another — else the fixed
    in-checkout path."""
    import jax

    from tensorflowonspark_tpu import util

    old = jax.config.jax_compilation_cache_dir
    old_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        placed = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        assert util.enable_compilation_cache() == placed
        assert os.path.isdir(placed)
        assert jax.config.jax_compilation_cache_dir == placed
        assert util.aot_cache_dir() == os.path.join(placed, "aot")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = util.compilation_cache_dir()
        assert fixed == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_secs)


@_isolated
def test_input_state_resumes_pipeline_after_restart(tmp_path):
    """A restarted estimator must continue the data stream where the saved
    checkpoint left it, not re-train the epoch's first batches (tf.data
    iterator-checkpointing parity)."""
    import jax.numpy as jnp

    seen_a, seen_b = [], []

    def make(record):
        def init_fn():
            return {"w": jnp.zeros(())}

        def loss_fn(params, batch):
            return params["w"] ** 2 + 0.0 * batch["i"].sum()

        def input_fn():
            for i in range(100):  # long epoch: never exhausted
                record.append(i)
                yield {"i": np.full((8,), i, np.float32)}

        return init_fn, loss_fn, input_fn

    init_fn, loss_fn, input_fn = make(seen_a)
    with Estimator(init_fn, loss_fn, optax.sgd(0.1), str(tmp_path / "m"),
                   save_every_steps=5, summary_dir="") as est:
        est.train(input_fn, max_steps=7)  # final save at step 7

    # "restart": a fresh estimator against the same model_dir
    init_fn, loss_fn, input_fn = make(seen_b)
    with Estimator(init_fn, loss_fn, optax.sgd(0.1), str(tmp_path / "m"),
                   save_every_steps=5, summary_dir="") as est:
        assert est.global_step == 7
        assert est._pending_input_resume == {"epoch": 0, "batches": 7}
        est.train(input_fn, max_steps=10)

    # the resumed run must TRAIN on batches 7, 8, 9 (the replayed prefix
    # 0..6 is only skipped through, never stepped on)
    trained_b = seen_b[7:10] if len(seen_b) >= 10 else None
    assert seen_b[:7] == list(range(7))  # deterministic replay of prefix
    assert trained_b == [7, 8, 9], (seen_b, trained_b)


@_isolated
def test_input_state_disabled_restarts_epoch(tmp_path):
    import jax.numpy as jnp

    def init_fn():
        return {"w": jnp.zeros(())}

    def loss_fn(params, batch):
        return params["w"] ** 2 + 0.0 * batch["i"].sum()

    def input_fn():
        for i in range(50):
            yield {"i": np.full((8,), i, np.float32)}

    kw = dict(save_every_steps=5, summary_dir="",
              checkpoint_input_state=False)
    with Estimator(init_fn, loss_fn, optax.sgd(0.1), str(tmp_path / "m"),
                   **kw) as est:
        est.train(input_fn, max_steps=6)
    with Estimator(init_fn, loss_fn, optax.sgd(0.1), str(tmp_path / "m"),
                   **kw) as est:
        assert est._pending_input_resume is None
        est.train(input_fn, max_steps=8)


@_isolated
def test_early_stopping_halts_on_plateau(tmp_path):
    import jax.numpy as jnp

    def init_fn():
        return {"w": jnp.zeros(())}

    def loss_fn(params, batch):
        # loss is constant in w: every eval round plateaus immediately
        return 1.0 + 0.0 * params["w"] + 0.0 * batch["i"].sum()

    def input_fn():
        for i in range(16):
            yield {"i": np.full((8,), i, np.float32)}

    with Estimator(init_fn, loss_fn, optax.sgd(0.1), str(tmp_path / "m"),
                   summary_dir="") as est:
        final = train_and_evaluate(
            est,
            TrainSpec(input_fn=input_fn, max_steps=1000),
            EvalSpec(input_fn=input_fn, steps=2, throttle_steps=4,
                     early_stopping_patience=2))
        # 1 improving round (first) + 2 stale rounds = stop at step 12
        assert est.global_step == 12, est.global_step
        assert final["loss"] == pytest.approx(1.0)


@_isolated
def test_early_stopping_patience_validation():
    with pytest.raises(ValueError, match="early_stopping_patience"):
        EvalSpec(input_fn=lambda: [], early_stopping_patience=0)


@_isolated
def test_early_stopping_state_survives_restart(tmp_path):
    import jax.numpy as jnp

    def make():
        def init_fn():
            return {"w": jnp.zeros(())}

        def loss_fn(params, batch):
            return 1.0 + 0.0 * params["w"] + 0.0 * batch["i"].sum()

        def input_fn():
            for i in range(16):
                yield {"i": np.full((8,), i, np.float32)}

        return init_fn, loss_fn, input_fn

    init_fn, loss_fn, input_fn = make()
    spec = dict(steps=2, throttle_steps=4, early_stopping_patience=3)
    with Estimator(init_fn, loss_fn, optax.sgd(0.1), str(tmp_path / "m"),
                   summary_dir="") as est:
        # run exactly 2 eval rounds (1 improving + 1 stale), then "crash"
        train_and_evaluate(est, TrainSpec(input_fn=input_fn, max_steps=8),
                           EvalSpec(input_fn=input_fn, **spec))
        assert est.global_step == 8

    init_fn, loss_fn, input_fn = make()
    with Estimator(init_fn, loss_fn, optax.sgd(0.1), str(tmp_path / "m"),
                   summary_dir="") as est:
        # resumed run: stale=1 carried over, so only 2 more stale rounds
        # (not 3) before the stop — step 16, not 20
        train_and_evaluate(est, TrainSpec(input_fn=input_fn, max_steps=1000),
                           EvalSpec(input_fn=input_fn, **spec))
        assert est.global_step == 16, est.global_step

    # a third launch of an already-stopped run must not train at all
    init_fn, loss_fn, input_fn = make()
    with Estimator(init_fn, loss_fn, optax.sgd(0.1), str(tmp_path / "m"),
                   summary_dir="") as est:
        train_and_evaluate(est, TrainSpec(input_fn=input_fn, max_steps=1000),
                           EvalSpec(input_fn=input_fn, **spec))
        assert est.global_step == 16, est.global_step


@_isolated
def test_early_stopping_unknown_metric_raises(tmp_path):
    import jax.numpy as jnp

    def init_fn():
        return {"w": jnp.zeros(())}

    def loss_fn(params, batch):
        return params["w"] ** 2 + 0.0 * batch["i"].sum()

    def input_fn():
        for i in range(8):
            yield {"i": np.full((8,), i, np.float32)}

    with Estimator(init_fn, loss_fn, optax.sgd(0.1), str(tmp_path / "m"),
                   summary_dir="") as est:
        with pytest.raises(ValueError, match="accuracy"):
            train_and_evaluate(
                est, TrainSpec(input_fn=input_fn, max_steps=8),
                EvalSpec(input_fn=input_fn, steps=2, throttle_steps=4,
                         early_stopping_patience=1, metric="accuracy"))


@_isolated
def test_negative_min_delta_rejected():
    with pytest.raises(ValueError, match="min_delta"):
        EvalSpec(input_fn=lambda: [], early_stopping_patience=1,
                 min_delta=-0.1)


@_isolated
def test_warm_start_loads_params_but_not_step(tmp_path):
    x, y = _linreg_problem()
    with _make_estimator(tmp_path / "donor") as est:
        est.train(_batches(x, y), max_steps=20)
        trained_w = np.asarray(est.params["w"])
    assert not np.allclose(trained_w, 0.0)

    with Estimator(*_triple(), str(tmp_path / "fresh"), summary_dir="",
                   warm_start_from=str(tmp_path / "donor")) as est:
        assert est.global_step == 0  # step starts fresh...
        np.testing.assert_allclose(np.asarray(est.params["w"]), trained_w)

    # a dir with a checkpoint ignores warm_start_from
    with Estimator(*_triple(), str(tmp_path / "donor"), summary_dir="",
                   warm_start_from=str(tmp_path / "fresh")) as est:
        assert est.global_step == 20

    with pytest.raises(ValueError, match="no\\s+checkpoint"):
        Estimator(*_triple(), str(tmp_path / "x"), summary_dir="",
                  warm_start_from=str(tmp_path / "empty"))


def _triple():
    import jax.numpy as jnp

    def init_fn():
        return {"w": jnp.zeros((4, 1))}

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    return init_fn, loss_fn, optax.sgd(0.1)


@_isolated
def test_estimator_to_serve_parity(tmp_path):
    """Estimator → serve parity, end to end on one stack (ROADMAP item
    5's last pipeline gap): train a tiny GPT through ``Estimator``/
    ``train_and_evaluate`` (checkpoint under ``model_dir``), run the
    batch plane's ``GridSearch`` as the OFFLINE EVAL whose verdict gates
    promotion (``ModelRegistry.evaluate_grid``), then serve the
    promoted version on a real ``ServingCluster`` — with the served
    output greedy-exact vs a solo ``greedy_generate`` oracle over the
    SAME restored checkpoint."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.batch.gridsearch import GridSearch
    from tensorflowonspark_tpu.batch.manifest import ShardManifest
    from tensorflowonspark_tpu.models import GPT, greedy_generate
    from tensorflowonspark_tpu.serving import ModelRegistry, ServingCluster
    from tests.cluster_funcs import (rollout_parity_builder,
                                     rollout_parity_cfg,
                                     rollout_parity_predict)

    cfg = rollout_parity_cfg()
    model_dir = str(tmp_path / "ckpt")
    rng = np.random.default_rng(0)
    # batch rows divisible by any local device count (the default
    # DataParallelStrategy shards the batch over all devices)
    data = rng.integers(1, cfg.vocab_size, (16, 9)).astype(np.int32)

    def init_fn():
        return GPT(cfg).init(jax.random.key(0),
                             jnp.ones((1, 4), jnp.int32))["params"]

    def loss_fn(params, batch):
        x = batch["x"]
        logits = GPT(cfg).apply({"params": params}, x[:, :-1])
        logp = jax.nn.log_softmax(logits)
        picked = jnp.take_along_axis(logp, x[:, 1:, None], axis=-1)
        return -jnp.mean(picked)

    def input_fn():
        for i in range(0, len(data), 8):
            yield {"x": data[i:i + 8]}

    with Estimator(init_fn, loss_fn, optax.adam(1e-2), model_dir,
                   save_every_steps=2, handle_preemption=False,
                   summary_dir="") as est:
        final = train_and_evaluate(
            est, TrainSpec(input_fn=input_fn, max_steps=4),
            EvalSpec(input_fn=input_fn, steps=1, throttle_steps=4))
        assert final["global_step"] == 4

    # the driver-side oracle decodes under the SAME restored checkpoint
    _cfg, params = rollout_parity_builder({"model_dir": model_dir})
    prompts = [data[i, :5] for i in range(4)]
    budget = 4
    oracle = [np.asarray(greedy_generate(
        cfg, params, jnp.asarray(p)[None, :], budget))[0, p.size:].tolist()
        for p in prompts]

    # offline eval: the batch plane's GridSearch over the checkpoint
    reg = ModelRegistry()
    reg.register("parity", "v1", rollout_parity_builder)
    assert not reg.promotable("parity", "v1")
    gs = GridSearch(
        ShardManifest.from_arrays([np.stack(prompts[:2]),
                                   np.stack(prompts[2:])]),
        str(tmp_path / "eval"), rollout_parity_predict,
        param_grid=[{"budget": budget}],
        model_builder=rollout_parity_builder,
        predict_args={"model_dir": model_dir}, batch_size=2)
    gs.run(num_workers=1, max_restarts=0,
           worker_env={"JAX_PLATFORMS": "cpu"},
           working_dir=str(tmp_path / "wd"),
           reservation_timeout=120, shutdown_timeout=120)

    def scorer(results):
        got = [np.frombuffer(b, np.int32).tolist() for b in results]
        exact = sum(g == o for g, o in zip(got, oracle))
        return ({"exact": exact, "n": len(got)},
                len(got) == len(oracle) and exact == len(oracle))

    assert reg.evaluate_grid("parity", "v1", gs, "t0", scorer)
    assert reg.promotable("parity", "v1")
    assert reg.version("parity", "v1").eval_metrics == {"exact": 4, "n": 4}

    # serve the promoted version on one cluster; the registry entry's
    # builder restores the estimator checkpoint in the replica process
    serving = ServingCluster.run(
        None, 1, registry=reg, model=("parity", "v1"),
        replica_args={"model_dir": model_dir},
        worker_env={"JAX_PLATFORMS": "cpu"}, reservation_timeout=120)
    try:
        with serving.client() as c:
            got = c.generate(prompts[0], budget, model="parity")
        assert got.tolist() == oracle[0], \
            "served output diverged from the trained checkpoint's oracle"
        m = serving.metrics()
        assert m["registry"]["parity"]["v1"]["state"] == "serving"
        assert m["replicas"][0]["model"] == "parity"
    finally:
        serving.shutdown(timeout=300)
