"""Observability layer: tensorboard spawn/URL, profiler trace, goodput.

Reference posture (SURVEY.md §5): tensorboard is the only facility —
spawned on worker:0/chief, (tb_pid, tb_port) registered, URL surfaced by
``TFCluster.tensorboard_url()``.  The spawn tests boot a *real* TensorBoard
(skipped when the package isn't installed) because the failure mode being
guarded — TB dying at import time — only reproduces with the real thing.
"""

import json
import os
import time

import pytest

from tensorflowonspark_tpu import observability
from tensorflowonspark_tpu.observability import GoodputRecorder


# -- goodput ---------------------------------------------------------------

def test_goodput_accounting():
    rec = GoodputRecorder()
    with rec.time("init"):
        time.sleep(0.05)
    for _ in range(3):
        with rec.time("step"):
            time.sleep(0.02)
    s = rec.summary()
    assert s["counts"] == {"init": 1, "step": 3}
    assert s["secs"]["step"] == pytest.approx(0.06, abs=0.04)
    assert 0.0 < s["goodput"] < 1.0
    assert s["secs"]["idle"] >= 0.0


def test_goodput_write(tmp_path):
    rec = GoodputRecorder()
    rec.record("step", 1.0)
    out = str(tmp_path / "goodput.json")
    s = rec.write(out)
    loaded = json.load(open(out))
    assert loaded["counts"] == s["counts"]
    assert loaded["secs"]["step"] == pytest.approx(1.0)
    assert loaded["goodput"] == pytest.approx(s["goodput"])


# -- profiler --------------------------------------------------------------

def test_profile_trace_writes_events(tmp_path):
    import jax
    import jax.numpy as jnp

    logdir = str(tmp_path / "prof")
    with observability.profile_trace(logdir):
        jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    # jax.profiler.trace writes plugins/profile/<run>/... under logdir
    found = [os.path.join(r, f) for r, _, fs in os.walk(logdir) for f in fs]
    assert found, "no profiler output written"


def test_span_smoke():
    """The one span primitive (the rest is tests/test_phase_spans.py):
    usable with no profiler session and no clock."""
    with observability.span("tfos/test/mystep") as sp:
        assert sp.name == "tfos/test/mystep"
    assert getattr(observability._open_span, "span", None) is None


# -- tensorboard spawn -----------------------------------------------------

def test_start_tensorboard_real_module(tmp_path):
    """Spawns the real tensorboard and requires it to actually serve HTTP
    (regression: setuptools>=81 removed pkg_resources → TB died instantly;
    the _shims/pkg_resources.py injection keeps it bootable)."""
    import urllib.request

    pytest.importorskip("tensorboard")
    res = observability.start_tensorboard(str(tmp_path / "tb"), wait_secs=1.0)
    assert res is not None
    proc, port = res
    assert port > 0
    try:
        status = None
        # 90s budget: TB's bootstrap on a saturated 1-core box can exceed
        # 30s (observed flake when the suite shares the core with other
        # jobs); serving normally starts within ~5s
        for _ in range(90):
            try:
                status = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}", timeout=3).status
                break
            except OSError:
                time.sleep(1)
        assert status == 200, "tensorboard never served HTTP"
    finally:
        observability.stop_tensorboard(proc)
    assert proc.poll() is not None


def test_cluster_tensorboard_url(tmp_path):
    """End to end: tensorboard=True → tb_port registered → URL surfaced."""
    from tensorflowonspark_tpu import TPUCluster
    from tests import cluster_funcs as funcs

    cluster = TPUCluster.run(
        funcs.fn_noop, {}, 2, tensorboard=True,
        tensorboard_logdir=str(tmp_path / "tblog"),
        worker_env={"JAX_PLATFORMS": "cpu"}, reservation_timeout=60,
        working_dir=str(tmp_path / "wd"))
    url = cluster.tensorboard_url()
    try:
        assert url is not None and url.startswith("http://")
        ports = [n.get("tb_port", 0) for n in cluster.cluster_info]
        assert sum(1 for p in ports if p) == 1  # exactly one chief spawn
    finally:
        cluster.shutdown(timeout=120)


@pytest.mark.integration
def test_goodput_and_worker_metrics_visible_from_driver(tmp_path):
    """The heartbeat-carried telemetry transport end to end: a map_fun
    using ``ctx.goodput()`` + a registry counter becomes visible in the
    driver's aggregated ``cluster.metrics()`` view (and the Prometheus
    page) while the job runs — not only as an end-of-job file."""
    from tensorflowonspark_tpu import TPUCluster
    from tests import cluster_funcs as funcs

    cluster = TPUCluster.run(
        funcs.fn_goodput_metrics_steps, {"max_secs": 60}, 1,
        worker_env={"JAX_PLATFORMS": "cpu"}, reservation_timeout=60,
        working_dir=str(tmp_path / "wd"))
    try:
        deadline = time.monotonic() + 30
        node0 = None
        while time.monotonic() < deadline:
            node0 = cluster.metrics()["nodes"].get(0)
            if node0 and node0.get("goodput") \
                    and node0["goodput"]["counts"].get("step", 0) > 0 \
                    and "tfos_test_worker_steps_total" in node0["metrics"]:
                break
            time.sleep(0.25)
        assert node0 is not None and node0.get("goodput"), \
            "goodput never arrived in the driver's aggregated view"
        assert node0["goodput"]["counts"]["step"] > 0
        assert 0.0 < node0["goodput"]["goodput"] <= 1.0
        samples = node0["metrics"]["tfos_test_worker_steps_total"]["samples"]
        assert samples and samples[0][1] > 0
        # the merged exposition page carries the worker series, labeled
        text = cluster.metrics_text()
        assert 'tfos_test_worker_steps_total{node="0"}' in text
        # standalone /metrics endpoint for training-only jobs
        import urllib.request

        host, port = cluster.serve_metrics()
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5).read().decode()
        assert "tfos_test_worker_steps_total" in body
    finally:
        import contextlib

        with contextlib.suppress(Exception):
            cluster._client_for(0).kv_set("stop_goodput", "1")
        cluster.shutdown(timeout=120)


def test_event_log_jsonl_roundtrip(tmp_path):
    """EventLog appends one timestamped JSON object per event (creating
    parent dirs) and reads them back — the health monitor's audit trail."""
    path = str(tmp_path / "events" / "health_events.jsonl")
    log = observability.EventLog(path)
    t0 = time.time()
    log.emit("monitor_started", workers=2)
    log.emit("crash", workers=[1], message="worker 1 exit=-9")
    log.close()

    log2 = observability.EventLog(path)  # append mode: reopen must not clobber
    log2.emit("abort", reason="crash")
    log2.close()

    recs = observability.EventLog.read(path)
    assert [r["kind"] for r in recs] == ["monitor_started", "crash", "abort"]
    assert recs[1]["workers"] == [1]
    assert all(r["t"] >= t0 - 1 for r in recs)


def test_event_log_read_skips_truncated_final_line(tmp_path, caplog):
    """A driver killed mid-emit leaves a partial JSON line; a post-mortem
    read must keep every good record and skip the fragment with a
    warning, not raise and lose the whole file."""
    path = str(tmp_path / "events.jsonl")
    log = observability.EventLog(path)
    log.emit("monitor_started", workers=2)
    log.emit("crash", workers=[0])
    log.close()
    with open(path, "a") as f:
        f.write('{"t": 123.4, "kind": "abo')   # killed mid-write

    import logging

    with caplog.at_level(logging.WARNING,
                         logger="tensorflowonspark_tpu.observability"):
        recs = observability.EventLog.read(path)
    assert [r["kind"] for r in recs] == ["monitor_started", "crash"]
    assert any("malformed" in r.message for r in caplog.records)

    # mid-file corruption (torn page) must not hide the records after it
    with open(path, "a") as f:
        f.write('\n{"t": 125.0, "kind": "late"}\n')
    recs = observability.EventLog.read(path)
    assert [r["kind"] for r in recs] == ["monitor_started", "crash", "late"]


def test_event_log_read_survives_line_cut_mid_utf8_sequence(tmp_path,
                                                            caplog):
    """The torn byte can fall INSIDE a multi-byte UTF-8 sequence — a
    text-mode read would raise ``UnicodeDecodeError`` before any line
    splitting happens and lose the whole file; the binary-read per-line
    decode skips exactly the cut line."""
    import json
    import logging

    path = str(tmp_path / "events.jsonl")
    log = observability.EventLog(path)
    log.emit("monitor_started", workers=2)
    log.close()
    whole = json.dumps({"t": 9.0, "kind": "crash", "detail": "nœud"},
                       ensure_ascii=False).encode("utf-8")
    cut = whole[:whole.index(b"\xc5") + 1]     # half of the œ
    with open(path, "ab") as f:
        f.write(cut)
    with caplog.at_level(logging.WARNING,
                         logger="tensorflowonspark_tpu.observability"):
        recs = observability.EventLog.read(path)
    assert [r["kind"] for r in recs] == ["monitor_started"]
    assert any("malformed" in r.message for r in caplog.records)


# -- latency histogram -----------------------------------------------------

def test_latency_histogram_percentiles():
    h = observability.LatencyHistogram()
    assert len(h) == 0 and h.percentile(99) is None
    assert h.summary()["count"] == 0 and h.summary()["p50_secs"] is None
    for ms in range(1, 101):           # 1..100 ms
        h.record(ms / 1000.0)
    s = h.summary()
    assert s["count"] == 100
    # nearest-rank: every reported value is an actual sample
    assert s["p50_secs"] == pytest.approx(0.050)
    assert s["p95_secs"] == pytest.approx(0.095)
    assert s["p99_secs"] == pytest.approx(0.099)
    assert s["max_secs"] == pytest.approx(0.100)
    assert s["mean_secs"] == pytest.approx(0.0505)
    assert h.percentile(100) == pytest.approx(0.100)


def test_latency_histogram_single_sample_and_concurrent_records():
    h = observability.LatencyHistogram()
    h.record(0.25)
    s = h.summary()
    assert s["p50_secs"] == s["p99_secs"] == s["max_secs"] == 0.25

    # hot-path contract: record from many threads without a lock
    import threading

    h2 = observability.LatencyHistogram()

    def worker():
        for _ in range(500):
            h2.record(0.001)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(h2) == 8 * 500          # list.append is GIL-atomic


def test_latency_histogram_reservoir_is_bounded():
    """A long-lived frontend must not grow the sample list forever: the
    reservoir keeps a ring of the most recent ``cap`` samples, percentile
    semantics stay nearest-rank on that window, and ``count`` reports the
    total ever recorded."""
    h = observability.LatencyHistogram(cap=100)
    for ms in range(1, 1001):          # 10x the cap
        h.record(ms / 1000.0)
    assert len(h._samples) == 100      # memory bounded at cap
    assert len(h) == 1000              # total recorded preserved
    s = h.summary()
    assert s["count"] == 1000
    # retained window is the most recent 100 samples: 0.901..1.000
    assert s["p50_secs"] == pytest.approx(0.950)
    assert s["p99_secs"] == pytest.approx(0.999)
    assert s["max_secs"] == pytest.approx(1.000)
    assert 0.901 <= s["mean_secs"] <= 1.0
    # every reported value is a sample that actually occurred
    assert s["p95_secs"] in h._samples

    # concurrent records against a small cap: bounded and crash-free
    import threading

    h2 = observability.LatencyHistogram(cap=64)

    def worker():
        for i in range(500):
            h2.record(i / 1000.0)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # bounded: cap + at most one fill-phase straggler append per thread
    assert len(h2._samples) <= 64 + 8
    assert h2.summary()["p99_secs"] is not None


def test_event_log_emit_after_close_degrades_to_warning(tmp_path, caplog):
    """A late monitor-thread emit into a closed log must warn, not raise
    ValueError out of the writer thread."""
    import logging

    path = str(tmp_path / "events.jsonl")
    log = observability.EventLog(path)
    log.emit("monitor_started", workers=1)
    log.close()
    with caplog.at_level(logging.WARNING,
                         logger="tensorflowonspark_tpu.observability"):
        rec = log.emit("late_event", detail="after close")   # must not raise
        log.emit("later_still")                              # warns only once
    assert rec["kind"] == "late_event"
    warnings = [r for r in caplog.records if "unwritable" in r.message]
    assert len(warnings) == 1
    # the file keeps only the pre-close events
    recs = observability.EventLog.read(path)
    assert [r["kind"] for r in recs] == ["monitor_started"]
