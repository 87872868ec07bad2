"""Observability: TensorBoard, the JAX profiler, and goodput accounting.

The reference's entire observability story (SURVEY.md §5) is: spawn a
``tensorboard`` subprocess on one node when ``tensorboard=True``
(``TFSparkNode.py::run``), register ``(tb_pid, tb_port)`` in the
reservation, surface it via ``TFCluster.tensorboard_url()``, and leave
profiling to whatever the user's TF callbacks emit.  This module keeps that
surface and adds the TPU-era equivalents:

- :func:`start_tensorboard` — the subprocess spawn (module-invoked, so no
  PATH dependency), returning ``(proc, port)``; the reservation carries
  ``(tb_pid, tb_port)``;
- :func:`start_profiler_server` / :func:`profile_trace` — ``jax.profiler``
  wiring (xprof traces viewable in TensorBoard's profile plugin, the
  TPU-native replacement for tf.profiler callbacks);
- :class:`GoodputRecorder` — badput accounting in the spirit of
  ``ml-goodput-measurement``: wall time split into productive step time vs
  init/compile/checkpoint/idle, because on large TPU fleets *goodput* (not
  step speed) is the capacity metric.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import logging
import os
import subprocess
import sys
import threading
import time
from collections import defaultdict

from tensorflowonspark_tpu import metrics as _metrics
from tensorflowonspark_tpu import util

logger = logging.getLogger(__name__)


# ------------------------------------------------------------- tensorboard

def start_tensorboard(logdir: str, port: int | None = None,
                      wait_secs: float = 0.0):
    """Spawn TensorBoard on ``logdir``; returns ``(proc, port)`` or ``None``.

    Reference: the ``tensorboard`` subprocess spawned for worker:0/chief in
    ``TFSparkNode.py::run``.  Spawned as ``python -m tensorboard.main`` so it
    works without a console-script on PATH; returns None (never raises) when
    tensorboard isn't importable — observability must not kill training.
    """
    try:
        import tensorboard  # noqa: F401 — availability probe
    except ImportError:
        logger.warning("tensorboard=True but tensorboard is not installed")
        return None
    port = port or util.get_free_port()
    os.makedirs(logdir, exist_ok=True)
    env = os.environ.copy()
    try:
        import pkg_resources  # noqa: F401 — removed in setuptools>=81
    except ImportError:
        shim = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_shims")
        env["PYTHONPATH"] = shim + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "tensorboard.main", "--logdir", logdir,
             "--port", str(port), "--host", "0.0.0.0", "--load_fast", "false"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env=env, start_new_session=True)
    except OSError as e:
        logger.warning("could not spawn tensorboard: %s", e)
        return None
    if wait_secs:
        time.sleep(wait_secs)
        if proc.poll() is not None:
            logger.warning("tensorboard exited immediately (code %s)",
                           proc.returncode)
            return None
    logger.info("tensorboard pid %d serving %s on port %d",
                proc.pid, logdir, port)
    return proc, port


def stop_tensorboard(proc) -> None:
    if proc is None:
        return
    with contextlib.suppress(OSError):
        proc.terminate()
        try:
            proc.wait(5)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(5)  # reap — a kill without wait leaves a zombie


def tensorboard_url(cluster_info) -> str | None:
    """URL of the cluster's TensorBoard from the reservation records
    (``tb_port`` registered by the chief-designate node)."""
    for n in cluster_info:
        if n.get("tb_port"):
            return f"http://{n['host']}:{n['tb_port']}"
    return None


# ---------------------------------------------------------------- profiler

def start_profiler_server(port: int | None = None) -> int:
    """Start the in-process profiler RPC server (``jax.profiler``); a
    TensorBoard profile plugin (or ``xprof``) can then capture live traces
    from ``host:port``."""
    import jax

    port = port or util.get_free_port()
    jax.profiler.start_server(port)
    logger.info("jax profiler server on port %d", port)
    return port


@contextlib.contextmanager
def profile_trace(logdir: str):
    """Trace the enclosed block into ``logdir`` (viewable in TensorBoard →
    Profile).  The reference had no in-framework tracer; this is the
    one-liner the TPU stack makes possible."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    with jax.profiler.trace(logdir):
        yield


# ------------------------------------------------------------------- spans
#
# Host spans of the program's own.  The names are module constants (nothing
# formats a string on the hot path) and start ``tfos/``: the benchmark's
# trace reduction (``benchmark/trace.py``) lays every idle gap of the device
# against host spans named ``bench/...`` or ``tfos/...`` and gives the gap
# WHOLE to the one span that covers most of it.  Two rules follow:
#
# (i)  ``tfos/`` spans are leaves: they never nest (an enclosing
#      ``tfos/serve/step`` would win every gap).  :class:`span` holds a
#      thread to that itself: entering one suspends the span the thread has
#      open and resumes it on exit, so no two spans of a thread overlap.
# (ii) a whole turn of the serving loop is marked with
#      ``jax.profiler.StepTraceAnnotation(SERVE_STEP, step_num=...)``: its
#      name does not start ``tfos/``, the reduction ignores it, and
#      TensorBoard's step views use it.
#
# The ten serve-side names partition the time of the replica's loop thread,
# and ten are what the reduction's ``idle_gaps`` keeps.

SERVE_INTAKE = "tfos/serve/intake"
SERVE_IDLE = "tfos/serve/idle"
BATCHER_ADMIT = "tfos/batcher/admit"
BATCHER_PREFILL_DISPATCH = "tfos/batcher/prefill_dispatch"
BATCHER_PREFILL_FETCH = "tfos/batcher/prefill_fetch"
BATCHER_DECODE_DISPATCH = "tfos/batcher/decode_dispatch"
BATCHER_DECODE_FETCH = "tfos/batcher/decode_fetch"
BATCHER_EMIT = "tfos/batcher/emit"
SERVE_PUBLISH = "tfos/serve/publish"
SERVE_FLUSH = "tfos/serve/flush"
#: the spans of the replica's loop thread, in the order of a loop turn
REPLICA_PHASES = (SERVE_INTAKE, SERVE_IDLE, BATCHER_ADMIT,
                  BATCHER_PREFILL_DISPATCH, BATCHER_PREFILL_FETCH,
                  BATCHER_DECODE_DISPATCH, BATCHER_DECODE_FETCH,
                  BATCHER_EMIT, SERVE_PUBLISH, SERVE_FLUSH)
#: train side: the two blocking ``queue_get`` sites of ``datafeed.py`` and
#: ``MeshStrategy.shard_batch``
FEED_WAIT = "tfos/feed/wait"
TRAIN_SHARD_BATCH = "tfos/train/shard_batch"
#: ``StepTraceAnnotation`` name of one turn of the serving loop (rule ii)
SERVE_STEP = "tfos_serve_step"

_open_span = threading.local()   # .span: the span this thread has open


def _jax_profiler():
    """``jax.profiler`` where this process has imported jax, else None: a
    process that never did has no profiler to annotate for, and is not
    made to import jax for it."""
    return getattr(sys.modules.get("jax"), "profiler", None)


class span:
    """``with span(name, seconds):`` — one host span, two sinks.

    (a) A ``jax.profiler.TraceAnnotation(name)``: inert (about 0.1 us)
    while no profiler session runs, and on the profiler's own clock, beside
    the device planes, when one does.
    (b) The elapsed ``time.perf_counter()`` seconds are added to
    ``seconds``, a BOUND counter child (``Counter.labels(...)``, made once
    outside the loop; :func:`phase_seconds`), when given.

    Spans are leaves (rule i above): entering a span while this thread has
    another open closes that one (annotation and clock) and reopens it on
    exit, so the spans of one thread partition its time and never overlap.
    ``TFOS_NO_TELEMETRY=1`` makes it a no-op, like the metrics plane.
    """

    __slots__ = ("name", "seconds", "_outer", "_ann", "_t0")

    def __init__(self, name: str, seconds=None):
        self.name = name
        self.seconds = seconds
        self._ann = None
        self._t0 = None             # None: not entered, or inert

    def _start(self) -> None:
        profiler = _jax_profiler()
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()

    def _stop(self) -> None:
        elapsed = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self.seconds is not None:
            self.seconds.inc(elapsed)

    def __enter__(self) -> "span":
        if not _metrics.get_registry().enabled:
            return self
        self._outer = getattr(_open_span, "span", None)
        if self._outer is not None:
            self._outer._stop()
        _open_span.span = self
        self._start()
        return self

    def __exit__(self, *exc) -> bool:
        if self._t0 is None:
            return False
        self._stop()
        self._t0 = None
        _open_span.span = self._outer
        if self._outer is not None:
            self._outer._start()
        return False


class step_marks:
    """Back-to-back ``jax.profiler.StepTraceAnnotation`` marks of a loop's
    turns (rule ii above): ``marks.next(n)`` closes the open mark and opens
    turn ``n``'s, ``marks.close()`` ends the last.  Inert where jax is not
    imported or under ``TFOS_NO_TELEMETRY=1``."""

    __slots__ = ("name", "_open")

    def __init__(self, name: str):
        self.name = name
        self._open = None

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def __enter__(self) -> "step_marks":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def next(self, step_num: int) -> None:
        self.close()
        profiler = _jax_profiler()
        if profiler is not None and _metrics.get_registry().enabled:
            self._open = profiler.StepTraceAnnotation(self.name,
                                                      step_num=step_num)
            self._open.__enter__()


class PhaseSpans:
    """The spans of the replica's loop thread, each bound once (here, and
    not in the loop) to its clock: ``spans = PhaseSpans()``, then ``with
    spans(BATCHER_EMIT): ...``.  ``seconds[name]`` is the span's bound
    child of ``tfos_replica_phase_seconds_total``; the serving loop and the
    batcher it drives each make one and share the family."""

    __slots__ = ("seconds",)

    def __init__(self):
        self.seconds = {name: phase_seconds(name) for name in REPLICA_PHASES}

    def __call__(self, name: str) -> span:
        return span(name, self.seconds[name])


def phase_seconds(name: str):
    """The bound child of ``tfos_replica_phase_seconds_total`` that the
    span ``name`` (one of :data:`REPLICA_PHASES`) adds its seconds to; the
    ``phase`` label is the name's last path part."""
    return _metrics.get_registry().counter(
        "tfos_replica_phase_seconds_total",
        "Seconds the replica's loop thread spent in each phase of a loop "
        "turn; the phases partition the thread's time, so their deltas "
        "over a window sum to the window.",
        labelnames=("phase",)).labels(phase=name.rsplit("/", 1)[1])


# -------------------------------------------------------------- hop clocks
#
# A request's way through the DRIVER process, clocked as counters and never
# as spans: the driver holds no device, so its threads share no clock with a
# device trace, and a ``tfos/`` annotation off the replica's loop thread
# would take device gaps from the loop's ten spans in the benchmark's
# reduction (rule i above).  All stamps are ``time.time()``, the clock
# ``tracing.py``'s events use and the one that means anything in two
# processes: ``dispatch`` and ``fetch`` lay the driver's clock against the
# replica's, exact on one host and within clock sync across hosts.

#: a request's hops in the order it takes them (docs/observability.md
#: "Hop clocks"): the way in up to the first token's put, per request, then
#: the way back, per token message
SERVING_HOPS = ("accept", "pending", "dispatch", "seat",
                "fetch", "pump", "send")
#: the message that carries a request's first token (and every per-request
#: hop of the way in), and every later token message
HOP_TOKENS = ("first", "next")


class HopClock:
    """One hop of one token kind: the bound children of the two hop
    families, made once outside the paths that ``add`` to them."""

    __slots__ = ("seconds", "messages")

    def __init__(self, seconds, messages):
        self.seconds, self.messages = seconds, messages

    def add(self, secs: float) -> None:
        """One message (or request) took ``secs`` over this hop; a negative
        difference of two processes' clocks counts 0."""
        self.seconds.inc(secs if secs > 0.0 else 0.0)
        self.messages.inc()


def hop_clocks() -> dict | None:
    """``{token: {hop: HopClock}}`` over :data:`HOP_TOKENS` and
    :data:`SERVING_HOPS`, bound to ``tfos_serving_hop_seconds_total`` and
    ``tfos_serving_hop_messages_total`` of this process's registry; None
    under ``TFOS_NO_TELEMETRY=1``, so that a path tests one attribute and
    is otherwise what it was."""
    reg = _metrics.get_registry()
    if not reg.enabled:
        return None
    seconds = reg.counter(
        "tfos_serving_hop_seconds_total",
        "Seconds requests and token messages took over each hop through "
        "the driver process, summed; token=first is the message with a "
        "request's first token and the request's way in, token=next every "
        "later token message.",
        labelnames=("hop", "token"))
    messages = reg.counter(
        "tfos_serving_hop_messages_total",
        "Messages (or requests) tfos_serving_hop_seconds_total sums over, "
        "so that seconds over messages is a hop's mean.",
        labelnames=("hop", "token"))
    return {token: {hop: HopClock(seconds.labels(hop=hop, token=token),
                                  messages.labels(hop=hop, token=token))
                    for hop in SERVING_HOPS} for token in HOP_TOKENS}


def hop_totals(clocks: dict | None) -> dict:
    """``{(hop, token): (seconds, messages)}`` as the clocks stand now: two
    of these bracket a stretch of time for :func:`hop_means`."""
    return {(hop, token): (clock.seconds.value(), clock.messages.value())
            for token, hops in (clocks or {}).items()
            for hop, clock in hops.items()}


def hop_means(clocks: dict | None, since: dict | None = None) -> dict:
    """``{hop: {token: {"mean_ms", "count"}}}`` of the hops that counted
    anything: the operator's view of :func:`hop_clocks`, over the
    process's life, or over the time since an earlier :func:`hop_totals`
    (the first requests of a replica wait for its programs for seconds,
    and a mean over the process's life never forgets them)."""
    out: dict = {}
    for (hop, token), (secs, n) in hop_totals(clocks).items():
        secs0, n0 = (since or {}).get((hop, token), (0.0, 0.0))
        if n - n0:
            out.setdefault(hop, {})[token] = {
                "mean_ms": 1e3 * (secs - secs0) / (n - n0),
                "count": int(n - n0)}
    return out


# ------------------------------------------------------------ health events

class EventLog:
    """Append-only JSONL stream of cluster lifecycle/health events.

    The reference surfaced executor failures through the Spark UI/event
    log; this is the rebuild's equivalent record.  One JSON object per
    line, each stamped with the writer's ``time.time()`` — the
    :class:`~tensorflowonspark_tpu.health.ClusterMonitor` writes
    ``monitor_started`` / ``crash`` / ``hang`` / ``preemption`` / ``abort``
    events here (default path: ``<working_dir>/health_events.jsonl``), and
    ``scripts/bench_recovery.py`` reads the timestamps back for
    detection-latency accounting.  Line-buffered append, so a post-mortem
    sees every event the driver managed to classify before dying.
    """

    def __init__(self, path: str, echo: bool = True):
        """``echo=False`` silences the per-event INFO log line — required
        for per-request/per-span streams (serving audit, tracing) whose
        emit rate would flood the process log."""
        self.path = path
        self._echo = echo
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._f = open(path, "a", buffering=1)
        self._lock = threading.Lock()
        self._write_failed = False

    def emit(self, kind: str, **fields) -> dict:
        """Append one event.  Safe after :meth:`close` (and after the fd
        is otherwise gone): a late monitor-thread emit into a closed
        line-buffered file degrades to a one-time logged warning instead
        of a ``ValueError`` out of the writer thread.  Later emits still
        attempt the write (a transient failure — brief ENOSPC — may
        clear), but only the first failure warns."""
        rec = {"t": time.time(), "kind": kind, **fields}
        line = json.dumps(rec) + "\n"
        with self._lock:
            try:
                self._f.write(line)
            except (ValueError, OSError, AttributeError) as e:
                # ValueError: write-after-close; OSError: fd gone
                if not self._write_failed:
                    self._write_failed = True
                    logger.warning(
                        "event log %s is unwritable (%s); dropped %r — "
                        "later writes are retried silently", self.path, e,
                        kind)
                return rec
        if self._echo:
            logger.info("health event: %s %s", kind, fields or "")
        return rec

    def close(self) -> None:
        with self._lock, contextlib.suppress(OSError, ValueError):
            self._f.close()

    @staticmethod
    def read(path: str) -> list[dict]:
        """Parse an event file back into records (bench/test helper).

        Tolerates malformed lines: a driver killed mid-``emit`` leaves a
        truncated final line — cut mid-payload, mid-UTF-8 sequence, or
        before its newline — and a post-mortem read that raised on it
        would lose every GOOD record in the file.  The file is read as
        bytes and decoded per line (a text-mode iterator raises
        ``UnicodeDecodeError`` on a torn multibyte tail and drops every
        line after it); bad lines are skipped with a warning, intact
        lines before AND after still come back."""
        out: list[dict] = []
        with open(path, "rb") as f:
            data = f.read()
        for lineno, raw in enumerate(data.split(b"\n"), 1):
            raw = raw.strip()
            if not raw:
                continue
            try:
                out.append(json.loads(raw.decode("utf-8")))
            except (UnicodeDecodeError, json.JSONDecodeError):
                logger.warning(
                    "skipping malformed event at %s:%d (truncated by a "
                    "mid-write death?): %.80r", path, lineno, raw)
        return out


# ------------------------------------------------------ latency histogram

class LatencyHistogram:
    """Latency percentile accumulator (p50/p95/p99) with a lock-free
    hot path and a **bounded** sample reservoir.

    ``record`` costs one ``itertools.count`` tick plus one list
    append/assign — all GIL-atomic, no lock — so request threads never
    contend to record a sample (the serving frontend records TTFT/e2e
    from many connection threads at once).  The reservoir is a ring of
    the most recent ``cap`` samples (default 4096): a long-lived serving
    frontend at millions-of-users scale must not grow a sample list
    forever, and recency is the window an operator actually wants
    percentiles over.  Readers take a snapshot copy (GIL-atomic slice)
    and sort it; percentile reads are O(cap log cap) off the hot path.
    Percentiles use the nearest-rank method on the retained window, so
    every reported value is a latency that actually occurred;
    ``summary()['count']`` stays the TOTAL recorded count.
    """

    DEFAULT_CAP = 4096

    def __init__(self, cap: int = DEFAULT_CAP):
        self._cap = max(1, int(cap))
        self._samples: list[float] = []
        self._ids = itertools.count()   # thread-safe total-count source
        self._count = 0

    def record(self, secs: float) -> None:
        i = next(self._ids)
        if i >= self._count:            # monotonic, benign-race update
            self._count = i + 1
        v = float(secs)
        s = self._samples
        n = len(s)
        if n >= self._cap:
            # the list never shrinks, so i % n is always in range even
            # if a fill-phase straggler appends concurrently; indexing
            # by the ACTUAL length keeps every slot reachable
            s[i % n] = v
        else:
            # fill phase: racing threads may overshoot cap by at most
            # one slot each (bounded, and still part of the ring above)
            s.append(v)

    def __len__(self) -> int:
        """Total samples recorded (retained window is ``min(len, cap)``)."""
        return max(len(self._samples), self._count)

    @staticmethod
    def _rank(snap: list, q: float):
        """Nearest-rank pick from a sorted snapshot (``ceil(q/100*n)``-th
        sample, 1-based, clamped)."""
        n = len(snap)
        return snap[min(n, int(max(1, -(-n * q // 100)))) - 1]

    def percentile(self, q: float) -> float | None:
        """Nearest-rank percentile ``q`` in [0, 100]; None when empty."""
        snap = sorted(self._samples)
        return self._rank(snap, q) if snap else None

    def window_summary(self, since_count: int) -> dict:
        """:meth:`summary` restricted to the samples recorded AFTER the
        first ``since_count`` — the canary-gate window: a baseline
        snapshot's total ``count`` feeds back in, so the gate compares
        bake-window latencies and is never biased by history the other
        side doesn't share (warm-up compiles in the incumbent's
        cumulative percentiles were exactly that bias).  Exact while
        the reservoir has not wrapped (total <= cap — the gate-scale
        case); after a wrap the retained recent ring is the best
        available approximation of the window."""
        total = len(self)
        n = max(0, total - max(0, int(since_count)))
        snap = list(self._samples)
        if n and total <= len(snap):
            # no wrap yet: the list is still in append order
            snap = snap[total - n:]
        if n == 0 or not snap:
            return {"count": 0, "mean_secs": None, "p50_secs": None,
                    "p95_secs": None, "p99_secs": None, "max_secs": None}
        snap.sort()
        return {"count": n,
                "mean_secs": sum(snap) / len(snap),
                "p50_secs": self._rank(snap, 50),
                "p95_secs": self._rank(snap, 95),
                "p99_secs": self._rank(snap, 99),
                "max_secs": snap[-1]}

    def summary(self) -> dict:
        """``{count, mean_secs, p50_secs, p95_secs, p99_secs, max_secs}``
        (None-valued stats when no sample was recorded).  ``count`` is
        the total ever recorded; the other stats cover the retained
        window (the most recent ``cap`` samples)."""
        snap = sorted(self._samples)
        n = len(snap)
        if not n:
            return {"count": 0, "mean_secs": None, "p50_secs": None,
                    "p95_secs": None, "p99_secs": None, "max_secs": None}
        return {"count": len(self), "mean_secs": sum(snap) / n,
                "p50_secs": self._rank(snap, 50),
                "p95_secs": self._rank(snap, 95),
                "p99_secs": self._rank(snap, 99), "max_secs": snap[-1]}


# ----------------------------------------------------------------- goodput

# ------------------------------------------------------- summary writing

class SummaryWriter:
    """TensorBoard scalar writer with zero TF dependency.

    TensorBoard event files are TFRecord streams of ``Event`` protos; this
    writer hand-encodes the ``Event``/``Summary`` wire format (the same
    approach as :mod:`.example_proto`) and frames records with the
    package's own :class:`~.tfrecord.TFRecordWriter` (CRC32C via the C++
    codec).  Byte-compatibility with TensorBoard's reader is pinned by
    test against the TF event parser.

    The reference delegated training curves to Keras/TF summary callbacks
    (SURVEY.md §5); here the estimator writes them natively::

        with SummaryWriter(logdir) as w:
            w.scalar("loss", 0.5, step=10)
            w.scalars({"loss": 0.4, "acc": 0.9}, step=20)
    """

    _FILE_VERSION = "brain.Event:2"

    def __init__(self, logdir: str, filename_suffix: str = ""):
        import socket

        from tensorflowonspark_tpu import filesystem as fsutil
        from tensorflowonspark_tpu.tfrecord import TFRecordWriter

        # scheme-aware: logdir may be gs:// etc., like the checkpoint dir
        fsutil.makedirs(logdir)
        name = (f"events.out.tfevents.{time.time():.6f}."
                f"{socket.gethostname()}{filename_suffix}")
        self.path = fsutil.join(logdir, name)
        self._w = TFRecordWriter(self.path)
        self._w.write(self._encode_event(file_version=self._FILE_VERSION))

    @staticmethod
    def _encode_event(step: int | None = None, summary: bytes | None = None,
                      file_version: str | None = None) -> bytes:
        import struct

        from tensorflowonspark_tpu.example_proto import (_tag, _write_len_field,
                                                         _write_varint)

        out = bytearray()
        _write_varint(out, _tag(1, 1))                 # wall_time: double
        out.extend(struct.pack("<d", time.time()))
        if step is not None:
            _write_varint(out, _tag(2, 0))             # step: int64
            _write_varint(out, int(step))
        if file_version is not None:
            _write_len_field(out, 3, file_version.encode())
        if summary is not None:
            _write_len_field(out, 5, summary)
        return bytes(out)

    @staticmethod
    def _encode_summary(metrics: dict) -> bytes:
        import struct

        from tensorflowonspark_tpu.example_proto import (_tag, _write_len_field,
                                                         _write_varint)

        out = bytearray()
        for tag_name, value in metrics.items():
            val = bytearray()
            _write_len_field(val, 1, str(tag_name).encode())  # Value.tag
            _write_varint(val, _tag(2, 5))                    # simple_value
            val.extend(struct.pack("<f", float(value)))
            _write_len_field(out, 1, bytes(val))              # Summary.value
        return bytes(out)

    def scalar(self, tag: str, value: float, step: int) -> None:
        self.scalars({tag: value}, step)

    def scalars(self, metrics: dict, step: int) -> None:
        """Write a dict of scalars as one event at ``step`` and flush —
        a live TensorBoard should see the point now, and a preempted
        process must not lose its buffered curves."""
        self._w.write(self._encode_event(
            step=step, summary=self._encode_summary(metrics)))
        self._w.flush()

    def flush(self) -> None:
        self._w.flush()

    def close(self) -> None:
        self._w.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class GoodputRecorder:
    """Wall-clock accounting: productive step time vs everything else.

    Categories follow the badput taxonomy: ``init`` (bootstrap + compile),
    ``checkpoint`` (save/restore stalls), ``data`` (feed waits), ``step``
    (productive compute).  Unattributed wall time counts as ``idle``.

        rec = GoodputRecorder()
        with rec.time("init"): state = make_state()
        while ...:
            with rec.time("data"): batch = feed.next_batch(...)
            with rec.time("step"): state, _ = train_step(state, batch)
        rec.summary()  # {'goodput': 0.87, 'wall_secs': ..., 'secs': {...}}
    """

    PRODUCTIVE = ("step",)

    def __init__(self):
        self._t0 = time.monotonic()
        self._secs: dict[str, float] = defaultdict(float)
        self._counts: dict[str, int] = defaultdict(int)

    @contextlib.contextmanager
    def time(self, category: str):
        start = time.monotonic()
        try:
            yield
        finally:
            self._secs[category] += time.monotonic() - start
            self._counts[category] += 1

    def record(self, category: str, secs: float, count: bool = True) -> None:
        self._secs[category] += secs
        if count:
            self._counts[category] += 1

    def summary(self) -> dict:
        wall = time.monotonic() - self._t0
        attributed = sum(self._secs.values())
        secs = dict(self._secs)
        secs["idle"] = max(0.0, wall - attributed)
        productive = sum(self._secs[c] for c in self.PRODUCTIVE)
        return {
            "wall_secs": wall,
            "goodput": productive / wall if wall > 0 else 0.0,
            "secs": secs,
            "counts": dict(self._counts),
        }

    def write(self, path: str) -> dict:
        """Write the summary as one JSON file (per-host goodput roll-up)."""
        s = self.summary()
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(s, f, indent=2)
        return s
