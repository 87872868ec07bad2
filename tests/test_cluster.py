"""Cluster orchestration integration tests.

Reference model: ``tests/test_TFCluster.py`` — run/train/inference/shutdown
round trips with trivial map_funs on a local multi-process cluster, both
input modes, error propagation (SURVEY.md §4).  Worker processes are real
OS processes via LocalProcessBackend, the rebuild's ``local-cluster`` analogue.
"""

import os

import pytest

from tensorflowonspark_tpu.cluster import (InputMode, Partitioned, TPUCluster,
                                           _build_cluster_template, _partition)
from tests import cluster_funcs as funcs

pytestmark = pytest.mark.integration


def _run(map_fun, num_workers=2, tmp=None, **kw):
    return TPUCluster.run(map_fun, kw.pop("tf_args", {}), num_workers,
                          reservation_timeout=60, working_dir=str(tmp), **kw)


def test_run_and_shutdown_noop(tmp_path):
    cluster = _run(funcs.fn_noop, 2, tmp_path)
    cluster.shutdown(timeout=60)


def test_role_assignment_template(tmp_path):
    cluster = _run(funcs.fn_write_role, 3, tmp_path, master_node="chief")
    cluster.shutdown(timeout=60)
    roles = {}
    for i in range(3):
        with open(os.path.join(str(tmp_path), f"role.{i}")) as f:
            roles[i] = f.read()
    assert roles[0].startswith("chief:0:1")     # chief is executor 0 and is_chief
    assert roles[1].startswith("worker:0:0")
    assert roles[2].startswith("worker:1:0")
    assert all(r.endswith(":3") for r in roles.values())


def test_train_feed_roundtrip(tmp_path):
    cluster = _run(funcs.fn_sum_feed, 2, tmp_path, tf_args={"batch_size": 8})
    cluster.train(list(range(100)), num_epochs=1)
    cluster.shutdown(timeout=60)
    total = count = 0
    for i in range(2):
        with open(os.path.join(str(tmp_path), f"sum.{i}")) as f:
            t, c = f.read().split(":")
            total += int(t)
            count += int(c)
    assert total == sum(range(100))
    assert count == 100


def test_train_multi_epoch(tmp_path):
    cluster = _run(funcs.fn_sum_feed, 2, tmp_path, tf_args={"batch_size": 16})
    cluster.train(list(range(10)), num_epochs=3)
    cluster.shutdown(timeout=60)
    total = count = 0
    for i in range(2):
        with open(os.path.join(str(tmp_path), f"sum.{i}")) as f:
            t, c = f.read().split(":")
            total += int(t)
            count += int(c)
    assert count == 30
    assert total == 3 * sum(range(10))


def test_inference_roundtrip(tmp_path):
    cluster = _run(funcs.fn_square_inference, 2, tmp_path)
    preds = cluster.inference(list(range(20)))
    cluster.shutdown(timeout=60)
    assert sorted(preds) == sorted(x * x for x in range(20))


def test_inference_more_partitions_than_nodes(tmp_path):
    # regression: multiple partitions routed to one node must be fed
    # sequentially, not interleaved by concurrent feeder threads
    cluster = _run(funcs.fn_square_inference, 2, tmp_path)
    preds = cluster.inference(Partitioned([[1, 2], [3, 4], [5, 6], [7]]))
    cluster.shutdown(timeout=60)
    assert sorted(preds) == sorted(x * x for x in range(1, 8))


def test_inference_ordering_multi_node_uneven_partitions(tmp_path):
    """Satellite: result ordering across MULTIPLE feedable nodes follows
    partition index, with uneven partitions and more partitions than
    nodes — previously asserted only order-insensitively / single-node.
    Exact list equality: partition p goes to node p % N, results are
    re-merged by partition index regardless of node finish order."""
    cluster = _run(funcs.fn_square_inference, 3, tmp_path)
    parts = [[1, 2, 3], [4], [5, 6], [7, 8, 9, 10], [], [11]]
    preds = cluster.inference(Partitioned(parts))
    cluster.shutdown(timeout=60)
    assert preds == [x * x for x in range(1, 12)]  # exact order, not sorted


def test_inference_ordering_uneven_flat_split(tmp_path):
    """Same contract for a flat list: _partition's uneven split (larger
    partitions first) must re-merge into the input order."""
    cluster = _run(funcs.fn_square_inference, 2, tmp_path)
    data = list(range(23))
    preds = cluster.inference(data)
    cluster.shutdown(timeout=60)
    assert preds == [x * x for x in data]


def test_inference_backpressure_tiny_output_batches(tmp_path):
    # regression: worker emits 1 result message per sample; with queue_depth=4
    # the output queue fills while the driver is still feeding — the feeder
    # must drain results while its puts block instead of deadlocking
    cluster = _run(funcs.fn_tiny_batch_inference, 1, tmp_path, queue_depth=4)
    preds = cluster.inference(list(range(64)), chunk_size=8, feed_timeout=60)
    cluster.shutdown(timeout=60)
    assert sorted(preds) == [x + 1000 for x in range(64)]


def test_error_propagation_on_shutdown(tmp_path):
    cluster = _run(funcs.fn_crash, 2, tmp_path, input_mode=InputMode.TENSORFLOW)
    with pytest.raises(RuntimeError, match="deliberate failure"):
        cluster.shutdown(timeout=60)


def test_early_terminate_stops_feed(tmp_path):
    cluster = _run(funcs.fn_terminating_consumer, 1, tmp_path)
    # feed far more data than the consumer will read; must not hang
    cluster.train(list(range(10000)), num_epochs=0, feed_timeout=30)
    cluster.shutdown(timeout=60)
    assert os.path.exists(os.path.join(str(tmp_path), "term.0"))


# -- pure-function unit tests ----------------------------------------------

def test_build_cluster_template_roles():
    t = _build_cluster_template(5, num_ps=2, master_node="master", eval_node=True)
    assert t == {"ps": [0, 1], "evaluator": [4], "master": [2], "worker": [3]}


def test_build_cluster_template_workers_only():
    assert _build_cluster_template(3, 0, None, False) == {"worker": [0, 1, 2]}


def test_partition_even_split():
    parts = _partition(list(range(10)), 3)
    assert [len(p) for p in parts] == [4, 4, 2]
    assert sum(parts, []) == list(range(10))


def test_partition_explicit():
    parts = _partition(Partitioned([[1, 2], [3]]), 99)
    assert parts == [[1, 2], [3]]


def test_driver_side_streaming_stop(tmp_path):
    """An unbounded feed (num_epochs=0) must be stoppable from the DRIVER
    via stop_feed(), without worker-side DataFeed.terminate() (reference:
    TFCluster.py::shutdown's Spark-Streaming background path)."""
    import threading
    import time as _time

    cluster = _run(funcs.fn_sum_feed, 2, tmp_path, tf_args={"batch_size": 8})
    feeder = threading.Thread(
        target=cluster.train,
        args=(list(range(40)),), kwargs={"num_epochs": 0, "chunk_size": 8},
        daemon=True)
    feeder.start()
    _time.sleep(1.5)             # let several epochs stream
    assert feeder.is_alive(), "unbounded feed should still be streaming"

    cluster.stop_feed()
    feeder.join(timeout=30)
    assert not feeder.is_alive(), "stop_feed() must unblock the feeder thread"

    cluster.shutdown(timeout=60)  # delivers EndOfFeed; workers drain + exit
    consumed = 0
    for i in range(2):
        with open(os.path.join(str(tmp_path), f"sum.{i}")) as f:
            consumed += int(f.read().split(":")[1])
    assert consumed > 0, "workers should have consumed streamed data"


def test_run_with_recovery_resumes_from_checkpoint(tmp_path):
    """One injected chief crash mid-training: run_with_recovery must
    relaunch the cluster and the job must complete with the step count
    preserved (resume from orbax, not restart from 0) — SURVEY.md §5
    'recovery = whole-job restart + resume'."""
    from tensorflowonspark_tpu.checkpoint import CheckpointManager
    from tensorflowonspark_tpu.cluster import run_with_recovery

    model_dir = str(tmp_path / "ckpt")
    run_with_recovery(
        funcs.fn_train_checkpoint_crash_once,
        {"total_steps": 7, "crash_at": 3, "model_dir": model_dir},
        num_workers=2, max_restarts=2,
        working_dir=str(tmp_path), worker_env={"JAX_PLATFORMS": "cpu"},
        reservation_timeout=60, shutdown_timeout=120)

    ckpt = CheckpointManager(model_dir)
    assert ckpt.latest_step() == 7
    state = ckpt.restore()
    assert float(state["w"]) == 7.0  # 3 pre-crash steps + 4 resumed, not 7+3
    ckpt.close()

    with open(tmp_path / "resume.0") as f:
        starts = f.read().split()
    assert starts[0] == "0", starts
    assert "3" in starts[1:], f"chief must resume from step 3, got {starts}"


def test_run_with_recovery_gives_up_after_max_restarts(tmp_path):
    from tensorflowonspark_tpu.cluster import run_with_recovery

    with pytest.raises(RuntimeError, match="deliberate failure"):
        run_with_recovery(
            funcs.fn_crash, {}, num_workers=1, max_restarts=1,
            working_dir=str(tmp_path), worker_env={"JAX_PLATFORMS": "cpu"},
            reservation_timeout=60, shutdown_timeout=60)


def test_worker_compile_cache_follows_the_environment(tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set, every spawned worker keeps
    its compile cache THERE (node.run exports it untouched, with the
    TFOS_CACHE_MIN_COMPILE_SECS threshold, before the user's map_fun) —
    the relaunch-reuses-compiles contract, placeable from outside."""
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    cluster = _run(funcs.fn_write_cache_env, 2, tmp_path,
                   worker_env={"TFOS_CACHE_MIN_COMPILE_SECS": "0.7"})
    cluster.shutdown(timeout=60)
    for i in range(2):
        with open(os.path.join(str(tmp_path), f"cacheenv.{i}")) as f:
            assert f.read() == f"{placed}:0.7"


def test_worker_compile_cache_default_never_moves(tmp_path, monkeypatch):
    """Unset, the cache is ONE fixed directory inside the checkout — the
    same for two runs with different working_dirs (the directory is part
    of XLA's cache key: a cache under mkdtemp can never hit)."""
    from tensorflowonspark_tpu import util

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert util.compilation_cache_dir() == os.path.join(repo, ".jax_cache")
    assert util.aot_cache_dir() == os.path.join(repo, ".jax_cache", "aot")
    seen = set()
    for name in ("run_a", "run_b"):
        wd = tmp_path / name
        wd.mkdir()
        cluster = _run(funcs.fn_write_cache_env, 1, wd)
        cluster.shutdown(timeout=60)
        with open(os.path.join(str(wd), "cacheenv.0")) as f:
            seen.add(f.read().rsplit(":", 1)[0])
    assert seen == {os.path.join(repo, ".jax_cache")}


def test_raise_worker_errors_aggregates_all_crashes(tmp_path):
    """A multi-worker failure must surface EVERY worker's traceback in one
    error, not one per restart (satellite: _raise_worker_errors)."""
    from tensorflowonspark_tpu.cluster import _raise_worker_errors

    (tmp_path / "error.0").write_text("Traceback...\nValueError: boom zero\n")
    (tmp_path / "error.2").write_text("Traceback...\nTypeError: boom two\n")
    with pytest.raises(RuntimeError) as ei:
        _raise_worker_errors(str(tmp_path), 3)
    msg = str(ei.value)
    assert "worker 0" in msg and "worker 2" in msg
    assert "boom zero" in msg and "boom two" in msg

    # single-crash format unchanged (the common case, matched by callers)
    (tmp_path / "error.2").unlink()
    with pytest.raises(RuntimeError, match="worker 0 failed"):
        _raise_worker_errors(str(tmp_path), 3)


def test_local_backend_reaps_a_worker_once_across_threads():
    """The health monitor's thread reads exit codes while ``shutdown``
    joins.  ``multiprocessing.Process`` is not thread-safe: of two
    ``waitpid`` calls on one child the loser gets ECHILD and reports the
    child alive, so a join on workers that had all exited came back False
    ("workers still alive after 60s", under load once in a few runs).
    The backend serialises the reap."""
    import multiprocessing as mp
    import threading

    from tensorflowonspark_tpu.cluster import LocalProcessBackend

    for _ in range(2):
        backend = LocalProcessBackend()
        ctx = mp.get_context("spawn")
        backend.procs = [ctx.Process(target=funcs.fn_noop, args=({}, None))
                         for _ in range(6)]
        for p in backend.procs:
            p.start()
        stop = threading.Event()

        def poll():
            while not stop.is_set():
                backend.exitcodes()

        poller = threading.Thread(target=poll)
        poller.start()
        try:
            assert backend.join(60), backend.exitcodes()
        finally:
            stop.set()
            poller.join()
        assert backend.failed() == [] and not any(backend.alive())


class FlakyBackend:
    """LocalProcessBackend whose first start() raises — the relaunch-during-
    re-provisioning shape (an agent fleet not yet back after preemption)."""

    def __init__(self, fail_times=1, worker_env=None):
        from tensorflowonspark_tpu.cluster import LocalProcessBackend

        self._inner = LocalProcessBackend(worker_env=worker_env)
        self.fail_times = fail_times
        self.start_calls = 0

    def start(self, *a, **kw):
        self.start_calls += 1
        if self.start_calls <= self.fail_times:
            raise ConnectionError("agents still re-provisioning")
        self._inner.start(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_run_with_recovery_retries_bootstrap_failure(tmp_path):
    """When TPUCluster.run ITSELF raises (backend cannot launch), the
    recovery loop must classify it infra and relaunch — previously only
    in-training failures were exercised."""
    from tensorflowonspark_tpu.cluster import run_with_recovery

    backend = FlakyBackend(fail_times=1, worker_env={"JAX_PLATFORMS": "cpu"})
    run_with_recovery(
        funcs.fn_noop, {}, num_workers=1, max_restarts=2, backoff_base=0.1,
        backend=backend, working_dir=str(tmp_path),
        reservation_timeout=60, shutdown_timeout=60)
    assert backend.start_calls == 2  # failed once, relaunched, completed


def test_run_with_recovery_user_error_not_retried(tmp_path):
    """A deterministic map_fun ValueError classifies 'user': no relaunch,
    no burned restart budget — the error surfaces on the first attempt."""
    from tensorflowonspark_tpu.cluster import run_with_recovery

    restarts = []
    with pytest.raises(RuntimeError, match="deliberate failure"):
        run_with_recovery(
            funcs.fn_crash, {}, num_workers=1, max_restarts=3,
            on_restart=lambda *a: restarts.append(a),
            working_dir=str(tmp_path), worker_env={"JAX_PLATFORMS": "cpu"},
            reservation_timeout=60, shutdown_timeout=60)
    assert restarts == [], "user error must not be retried"


def test_run_with_recovery_restart_budget_window(tmp_path):
    """restart_budget=(R, T) bounds the restart RATE below max_restarts:
    an infra crash loop stops after R windowed restarts."""
    from tensorflowonspark_tpu.cluster import run_with_recovery

    kinds = []
    with pytest.raises(RuntimeError, match="injected infra failure"):
        run_with_recovery(
            funcs.fn_crash_infra, {}, num_workers=1, max_restarts=5,
            restart_budget=(1, 3600.0), backoff_base=0.1,
            on_restart=lambda attempt, exc, kind: kinds.append(kind),
            working_dir=str(tmp_path), worker_env={"JAX_PLATFORMS": "cpu"},
            reservation_timeout=60, shutdown_timeout=60)
    assert kinds == ["infra"], kinds  # one restart allowed, then budget cut


def test_shutdown_warns_on_stuck_feeder(tmp_path, caplog, monkeypatch):
    """A feeder thread that outlives the join window must be named in a
    warning before its QueueClient is closed out from under it."""
    import logging as _logging
    import threading

    class StubBackend:
        def join(self, timeout=None):
            return True

        def failed(self):
            return []

        def terminate(self):
            pass

    class StubServer:
        def stop(self):
            pass

    monkeypatch.setattr(TPUCluster, "FEEDER_JOIN_SECS", 0.2)
    cluster = TPUCluster(StubBackend(), StubServer(), [], {"num_workers": 0},
                         InputMode.TENSORFLOW, working_dir=str(tmp_path))
    release = threading.Event()
    t = threading.Thread(target=release.wait, name="stuck-feeder", daemon=True)
    t.start()
    cluster._active_feeders.add(t)
    try:
        with caplog.at_level(_logging.WARNING,
                             logger="tensorflowonspark_tpu.cluster"):
            cluster.shutdown(timeout=5)
        assert any("stuck-feeder" in r.getMessage() for r in caplog.records)
    finally:
        release.set()


def test_monitor_disabled_and_enabled(tmp_path):
    """monitor=False must actually disable the watchdog (regression: the
    run() parameter was once shadowed by a local), and the default must
    expose a running monitor on the handle."""
    cluster = _run(funcs.fn_noop, 1, tmp_path / "off", monitor=False)
    try:
        assert cluster.monitor is None
    finally:
        cluster.shutdown(timeout=60)
    (tmp_path / "on").mkdir()
    cluster = _run(funcs.fn_noop, 1, tmp_path / "on")
    try:
        assert cluster.monitor is not None
        assert cluster.monitor.failure is None
    finally:
        cluster.shutdown(timeout=60)
    assert (tmp_path / "on" / "health_events.jsonl").exists()
