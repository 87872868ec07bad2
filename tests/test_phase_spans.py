"""Phase spans and phase clocks of the serving loop and the batcher
(``observability.span`` / ``PhaseSpans``; docs/observability.md "Profiler
spans"), and the stable names of the compiled programs.

One real ``ContinuousBatcher`` (a two-layer GPT, paged KV) is driven through
``run_serve_loop`` over an in-process queue plane, once, under a real
``jax.profiler`` session on the CPU; the tests read what that one run left:
the profiler's host plane, the phase clocks, ``trace_events.jsonl``.
"""

import glob
import os
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import metrics, observability as obs, tracing
from tensorflowonspark_tpu.marker import EndOfFeed
from tensorflowonspark_tpu.models import GPT, GPTConfig, ContinuousBatcher
from tensorflowonspark_tpu.models import serving as serving_mod
from tensorflowonspark_tpu.models.serving import STANDDOWNS, DraftModel
from tensorflowonspark_tpu.serving import replica
from tensorflowonspark_tpu.serving.scheduler import (REQUEST_QUEUE,
                                                     RESPONSE_QUEUE)

SLOW_DELAY = 0.7      # the stretched turn's serve_step_delay, seconds
FAST_REQUESTS = 6


def _make(**kw):
    cfg = GPTConfig(vocab_size=61, hidden_size=32, num_layers=2, num_heads=4,
                    intermediate_size=64, max_position_embeddings=48,
                    dtype=jnp.float32, **kw)
    params = GPT(cfg).init(jax.random.key(0),
                           jnp.ones((1, 4), jnp.int32))["params"]
    return cfg, params


def _toy_builder(args):
    return _make()


def _prompt(i: int, n: int) -> np.ndarray:
    return np.random.default_rng(i).integers(0, 61, (n,)).astype(np.int32)


# -------------------------------------------------------------- primitive

def test_span_adds_its_seconds_to_the_bound_counter():
    reg = metrics.get_registry()
    child = reg.counter("tfos_test_span_seconds_total", "test",
                        labelnames=("phase",)).labels(phase="a")
    before = child.value()
    with obs.span("tfos/test/a", child):
        time.sleep(0.02)
    with obs.span("tfos/test/plain"):     # no clock given: annotation only
        pass
    assert 0.02 <= child.value() - before < 0.5


def test_span_suspends_the_enclosing_span():
    """The leaf rule by construction: entering a span closes the one the
    thread has open, so the two clocks partition the time."""
    fam = metrics.get_registry().counter(
        "tfos_test_span_seconds_total", "test", labelnames=("phase",))
    outer, inner = fam.labels(phase="outer"), fam.labels(phase="inner")
    o0, i0 = outer.value(), inner.value()
    t0 = time.perf_counter()
    with obs.span("tfos/test/outer", outer):
        time.sleep(0.01)
        with obs.span("tfos/test/inner", inner):
            time.sleep(0.03)
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    assert inner.value() - i0 >= 0.03
    assert 0.02 <= outer.value() - o0 < wall - 0.03 + 1e-3
    assert (outer.value() - o0) + (inner.value() - i0) \
        == pytest.approx(wall, abs=2e-3)


def test_span_is_inert_without_telemetry(monkeypatch):
    """``TFOS_NO_TELEMETRY=1``: no clock moves, no annotation is made,
    nothing is left open on the thread."""
    child = metrics.get_registry().counter(
        "tfos_test_span_seconds_total", "test",
        labelnames=("phase",)).labels(phase="off")
    before = child.value()
    monkeypatch.setenv(metrics.DISABLE_ENV, "1")
    monkeypatch.setattr(metrics, "_default_registry", None)
    assert not metrics.get_registry().enabled
    with obs.span("tfos/test/off", child) as sp:
        time.sleep(0.005)
        assert sp._ann is None
    marks = obs.step_marks(obs.SERVE_STEP)
    marks.next(1)
    assert marks._open is None
    phases = obs.PhaseSpans()
    with phases(obs.SERVE_FLUSH):
        pass
    assert phases.seconds[obs.SERVE_FLUSH].value() is None
    assert child.value() == before
    assert getattr(obs._open_span, "span", None) is None


def test_phase_names_are_ten_leaves_with_distinct_labels():
    assert len(obs.REPLICA_PHASES) == 10     # what idle_gaps keeps
    assert all(n.startswith("tfos/") for n in obs.REPLICA_PHASES)
    assert len({n.rsplit("/", 1)[1] for n in obs.REPLICA_PHASES}) == 10
    assert not obs.SERVE_STEP.startswith("tfos/")   # the reduction skips it


# ------------------------------------------------------ the one served run

class _Mgr:
    """The node queue plane, in process."""

    def __init__(self):
        self.requests: queue.Queue = queue.Queue()
        self.responses: list = []
        #: (timeout, what ``probe()`` said) of every read of the queue
        self.sweeps: list = []
        self.probe = lambda: None

    def queue_get(self, name, timeout=None):
        assert name == REQUEST_QUEUE
        self.sweeps.append((timeout, self.probe()))
        return self.requests.get(timeout=timeout)

    def queue_put(self, name, item, timeout=None):
        assert name == RESPONSE_QUEUE
        self.responses.append(item)

    def done(self) -> int:
        return sum(1 for r in list(self.responses)
                   if r.get("event") == "done")


class _Ctx:
    executor_id = 7

    def __init__(self, working_dir):
        self.mgr, self.working_dir = _Mgr(), working_dir
        self.steps: list = []

    def report_step(self, step, phase=None):
        self.steps.append((step, phase))


def _gen(rid, prompt, budget):
    return {"op": "gen", "rid": rid, "prompt": prompt,
            "max_new_tokens": budget, "trace": f"trace-{rid}"}


def _feeder(mgr: _Mgr, errors: list):
    """The traffic: FAST_REQUESTS streams, then (the loop idle) a hot swap
    that only sets serve_step_delay, one 1-token request (ONE stretched
    turn), and the end of the feed."""
    def wait_done(n):
        deadline = time.monotonic() + 120
        while mgr.done() < n:
            if time.monotonic() > deadline:
                raise TimeoutError(f"{mgr.done()} of {n} requests done")
            time.sleep(0.005)

    try:
        for i in range(FAST_REQUESTS):
            # unequal budgets: a row ends while the one beside it goes on,
            # and the next request is admitted behind a queued step
            mgr.requests.put(_gen(i, _prompt(i, 5 + i), 12 + 3 * i))
        wait_done(FAST_REQUESTS)
        mgr.requests.put({"op": "model", "event": "swap", "model": "toy",
                          "version": "slow", "swap_token": 1,
                          "serve_args": {"serve_step_delay": SLOW_DELAY}})
        deadline = time.monotonic() + 60
        while not any(r.get("event") == "model_swapped"
                      for r in list(mgr.responses)):
            if time.monotonic() > deadline:
                raise TimeoutError("the swap was never applied")
            time.sleep(0.005)    # the swap waits for an idle batcher
        mgr.requests.put(_gen(100, _prompt(100, 6), 1))
        wait_done(FAST_REQUESTS + 1)
    except Exception as e:   # surfaced by the fixture
        errors.append(e)
    finally:
        mgr.requests.put(EndOfFeed())


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp("serve"))
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    cfg, params = _make()
    batcher = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    # every program the loop will use compiles here, not in a loop turn
    for n in (1, 2):
        for i in range(n):
            batcher.submit(_prompt(50 + i, 6), 3)
        batcher.run()
    ctx = _Ctx(workdir)
    ctx.mgr.probe = lambda: {"free": batcher.has_free_slot(),
                             "queued": batcher.step_queued,
                             "seated": any(batcher.slots)}
    args = {"serve_model_builder": _toy_builder, "serve_idle_poll": 0.05}
    errors: list = []
    feeder = threading.Thread(target=_feeder, args=(ctx.mgr, errors),
                              daemon=True)
    reg = metrics.get_registry()
    phases = obs.PhaseSpans()
    slow = reg.counter("tfos_replica_slow_steps_total", "",
                       labelnames=("phase",))

    def clocks():
        return {n: c.value() for n, c in phases.seconds.items()}

    standdowns = reg.counter("tfos_replica_decode_ahead_standdowns_total",
                             "", labelnames=("why",))

    def dispatches():
        return {"ahead": reg.counter(
                    "tfos_replica_decode_ahead_dispatches_total").value(),
                "decode": reg.counter(
                    "tfos_replica_decode_dispatches_total").value(),
                **{why: standdowns.value(why=why) for why in STANDDOWNS}}

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # as benchmark/child.start_trace
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        before, slow_before = clocks(), slow.value(phase="decode_fetch")
        counted = dispatches()
        t0 = time.perf_counter()
        feeder.start()
        replica.run_serve_loop(args, ctx, batcher)
        wall = time.perf_counter() - t0
        after = clocks()
    finally:
        jax.profiler.stop_trace()
    feeder.join(30)
    assert not feeder.is_alive() and not errors, errors
    tracing.tracer_for(workdir).close()
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    host = {}       # (plane, line) -> [(name, start_ns, end_ns)]
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events
                      if e.name.startswith(("tfos/", obs.SERVE_STEP))]
            if events:
                host[(plane.name, line.name)] = events
    return {"ctx": ctx, "workdir": workdir, "wall": wall, "host": host,
            # the loop publishes the batcher's lifetime counts, the
            # warm-up's included, as this run's deltas
            "dispatches": {k: v - counted[k]
                           for k, v in dispatches().items()},
            "batcher": batcher,
            # read after the loop alone: the loop registers it
            "kernel_calls": reg.snapshot().get(
                "tfos_replica_grouped_matmul_calls_total"),
            "carried_prefills": reg.snapshot().get(
                "tfos_replica_carried_prefills_total"),
            "split": {n: after[n] - before[n] for n in after},
            "slow_trips": slow.value(phase="decode_fetch") - slow_before}


def test_loop_served_every_request(served):
    ctx = served["ctx"]
    assert ctx.mgr.done() == FAST_REQUESTS + 1
    assert len(ctx.steps) >= 50          # toy turns the clocks cover
    assert any(r.get("event") == "model_swapped" for r in ctx.mgr.responses)


def test_flush_stamps_its_messages_and_a_requests_first_with_its_intake(
        served):
    """The two stamps the driver's hop clocks read (``hop_clocks``):
    ``t_put`` on every ``tok`` and ``done`` message, ``t_in`` (the wall
    clock of ``replica_intake``) on a request's first ``tok`` alone; both
    ``time.time()`` of this process, in order."""
    t_end = time.time()
    by_rid: dict = {}
    for r in served["ctx"].mgr.responses:
        if r.get("event") in ("tok", "done"):
            by_rid.setdefault(r["rid"], []).append(r)
    assert len(by_rid) == FAST_REQUESTS + 1
    for rid, msgs in by_rid.items():
        assert [m["event"] for m in msgs[:-1]] == ["tok"] * (len(msgs) - 1)
        assert msgs[-1]["event"] == "done"
        assert ["t_in" in m for m in msgs] == [True] + [False] * (
            len(msgs) - 1), rid
        puts = [m["t_put"] for m in msgs]
        assert msgs[0]["t_in"] < puts[0] and puts == sorted(puts)
        assert t_end - 600 < msgs[0]["t_in"] and puts[-1] <= t_end
    other = [r for r in served["ctx"].mgr.responses
             if r.get("event") not in ("tok", "done")]
    assert other and not any("t_put" in r or "t_in" in r for r in other)


def test_no_stamp_is_added_without_telemetry(monkeypatch, tmp_path):
    """``TFOS_NO_TELEMETRY=1``: the messages are the ones they were."""
    monkeypatch.setenv(metrics.DISABLE_ENV, "1")
    monkeypatch.setattr(metrics, "_default_registry", None)
    cfg, params = _make()
    batcher = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    ctx = _Ctx(str(tmp_path))
    ctx.mgr.requests.put(_gen(0, _prompt(0, 5), 4))
    ctx.mgr.requests.put(EndOfFeed())
    replica.run_serve_loop({"serve_model_builder": _toy_builder,
                            "serve_idle_poll": 0.05}, ctx, batcher)
    msgs = ctx.mgr.responses
    assert {m["event"] for m in msgs[:-1]} == {"tok"}
    assert msgs[-1]["event"] == "done"
    assert sum(len(m["tokens"]) for m in msgs[:-1]) == 4
    assert not any("t_put" in m or "t_in" in m for m in msgs)


@pytest.mark.parametrize("name", obs.REPLICA_PHASES)
def test_every_phase_is_a_span_on_a_host_plane(served, name):
    found = {n for events in served["host"].values() for n, _, _ in events}
    assert name in found
    assert all(plane.startswith("/host:") for plane, _ in served["host"])


def test_tfos_spans_of_one_thread_never_overlap(served):
    """The leaf rule ``benchmark/trace.py::reduce`` imposes: it gives a
    gap whole to the span covering most of it, so an enclosing ``tfos/``
    span would win every gap."""
    checked = 0
    for events in served["host"].values():
        leaves = sorted((s, e, n) for n, s, e in events
                        if n.startswith("tfos/"))
        for (_, e0, n0), (s1, _, n1) in zip(leaves, leaves[1:]):
            assert e0 <= s1, f"{n0} overlaps {n1}"
            checked += 1
    assert checked > 200


def test_each_loop_turn_is_one_step_mark(served):
    marks = [n for events in served["host"].values() for n, _, _ in events
             if n.startswith(obs.SERVE_STEP)]
    assert len(marks) >= len(served["ctx"].steps)


def test_phase_seconds_sum_to_the_loops_wall_time(served):
    """The phases partition the loop thread's time: over the whole run
    their deltas sum to the run's seconds within 2 %."""
    total = sum(served["split"].values())
    assert total == pytest.approx(served["wall"], rel=0.02)
    assert all(v > 0 for v in served["split"].values()), served["split"]
    # the stretched turn's sleep stands in for the device and reads so
    assert served["split"][obs.BATCHER_DECODE_FETCH] >= SLOW_DELAY


def test_ahead_dispatches_are_published_beside_decode_dispatches(served):
    """``tfos_replica_decode_ahead_dispatches_total`` rises with the
    batcher's attribute (two slots, six streams of 12 to 27 tokens: most
    turns find the next step decided) and is a share of the decode
    dispatches."""
    d, b = served["dispatches"], served["batcher"]
    assert d["ahead"] == b.decode_ahead_dispatches > 0
    assert d["decode"] == b.decode_dispatches
    assert len(served["ctx"].steps) / 2 < d["ahead"] <= d["decode"]


def test_standdowns_are_published_and_sum_to_the_steps_not_run_ahead(served):
    """``tfos_replica_decode_ahead_standdowns_total{why}``: every decode
    dispatch either had the next step dispatched behind it or says what
    stood in the way.  Here: the turn that admits a request behind a
    queued step, and the last step of a stream with no row beside it."""
    d, b = served["dispatches"], served["batcher"]
    assert {why: d[why] for why in STANDDOWNS} == b.decode_ahead_standdowns
    assert sum(d[why] for why in STANDDOWNS) == d["decode"] - d["ahead"]
    assert d["admission"] >= FAST_REQUESTS - 2 and d["idle"] >= 2
    assert d["eos"] == d["sampled"] == d["chunked"] == d["alternative"] == 0


def test_a_sweep_does_not_wait_for_a_request_past_a_queued_step(served):
    """While the batcher holds a step queued ahead the device has its
    work: the sweep with a slot free then waits no longer than the sweep
    with none free, whatever ``serve_busy_poll`` is; with nothing queued
    ``serve_busy_poll`` keeps its meaning."""
    sweeps = [(t, p) for t, p in served["ctx"].mgr.sweeps if p["seated"]]
    full = {t for t, p in sweeps if not p["free"]}
    queued = {t for t, p in sweeps if p["free"] and p["queued"]}
    plain = {t for t, p in sweeps if p["free"] and not p["queued"]}
    assert full == queued == {0.001}
    assert plain <= {0.005}      # the default ``serve_busy_poll``


def test_kernel_calls_are_published_and_a_dense_model_moves_none(served):
    """``tfos_replica_grouped_matmul_calls_total`` is the loop's own
    registration (its help names the kernel), published like the other
    engine counters, and a model without experts adds nothing to it."""
    entry = served["kernel_calls"]
    assert entry is not None and entry["type"] == "counter"
    assert "tfos_grouped_matmul" in entry["help"]
    assert sum(row[-1] for row in entry["samples"]) == 0
    assert served["batcher"].grouped_matmul_calls == 0


def test_carried_prefills_are_published_beside_the_prefill_dispatches(
        served):
    """``tfos_replica_carried_prefills_total`` is published like the other
    engine counters; this loop's prefills brought no recurrent state (a
    dense model, no chunked admission), so it stays 0 beside prefill
    dispatches that moved."""
    entry = served["carried_prefills"]
    assert entry is not None and entry["type"] == "counter"
    assert "recurrent state" in entry["help"]
    assert sum(row[-1] for row in entry["samples"]) == 0
    assert served["batcher"].carried_prefills == 0
    assert served["batcher"].prefill_dispatches > 0


def test_stretched_turn_trips_the_slow_step_rule_once(served):
    assert served["slow_trips"] == 1
    events = [r for r in observability_events(served["workdir"])
              if r["kind"] == "replica_slow_step"
              and r["phase"] == "decode_fetch"]
    assert len(events) == 1
    ev = events[0]
    assert "trace" not in ev and ev["replica"] == 7
    assert ev["seconds"] >= SLOW_DELAY
    assert ev["split"]["decode_fetch"] >= SLOW_DELAY
    assert "idle" not in ev["split"]     # waiting for requests is no stall


def observability_events(workdir):
    return obs.EventLog.read(os.path.join(workdir, tracing.TRACE_FILENAME))


def test_traced_request_still_stitches(served):
    rows = tracing.stitch_trace(served["workdir"], "trace-3")
    assert [r["kind"] for r in rows] == [
        "replica_intake", "replica_first_token", "replica_done"]
    assert all(r["trace"] == "trace-3" and r["replica"] == 7 for r in rows)


# ------------------------------------------------------------------ tracer

def test_traceless_event_is_written_and_owned_by_no_timeline(tmp_path):
    tracer = tracing.Tracer(str(tmp_path / tracing.TRACE_FILENAME))
    tracer.event("replica_intake", "abc", rid=1, replica=0)
    tracer.event("replica_preempted", None, replica=0, inflight=2)
    tracer.event("replica_done", "abc", rid=1, replica=0)
    tracer.close()
    records = obs.EventLog.read(str(tmp_path / tracing.TRACE_FILENAME))
    assert [r["kind"] for r in records] == [
        "replica_intake", "replica_preempted", "replica_done"]
    assert "trace" not in records[1] and records[1]["inflight"] == 2
    rows = tracing.stitch_trace(str(tmp_path), "abc")
    assert [r["kind"] for r in rows] == ["replica_intake", "replica_done"]
    assert list(tracing.list_traces(str(tmp_path))) == ["abc"]


# ---------------------------------------------------------- program names

def _module_name(lowered) -> str:
    text = lowered.as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


@pytest.fixture(scope="module")
def lowered_names():
    """Every compile site of the batcher (and the draft model), lowered
    with the arguments of its first real call: site kind -> module name."""
    names: dict = {}
    real_jit = ContinuousBatcher._jit

    def recording_jit(self, site, fn, donate_argnums=()):
        jitted = real_jit(self, site, fn, donate_argnums)
        kind = site if isinstance(site, str) else site[0]

        def call(*args):
            names.setdefault(kind, set()).add(
                _module_name(jitted.lower(*args)))
            return jitted(*args)
        return call

    mp = pytest.MonkeyPatch()
    mp.setattr(ContinuousBatcher, "_jit", recording_jit)
    try:
        cfg, params = _make()
        long, short = _prompt(1, 11), _prompt(2, 5)
        # scanned blocks and the plain step that ends them (9 = 4 + 4 + 1),
        # greedy then sampled: block, step, step_sample
        b = ContinuousBatcher(cfg, params, max_batch=2, decode_block_steps=4)
        b.submit(short, 10)
        b.run()
        b.submit(short, 10, temperature=0.8, seed=3)
        b.run()
        # final, chunk, park; speculative verify and draft
        b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                              prefill_chunk=4, speculative_k=2)
        draft = DraftModel(cfg, params, window=8)
        b.set_draft(draft)
        b.submit(long, 6)
        b.submit(np.tile(short, 2), 6)
        b.run()
        names["draft_propose"] = {
            _module_name(fn.lower(params, jnp.zeros((B, L), jnp.int32),
                                  jnp.ones((B,), jnp.int32)))
            for (B, L, _), fn in draft._jits.items()}
        # the handoff pair: pexport on a prefill pool, padopt on a decoder
        pre = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                                prefill_only=True)
        pre.submit(long, 4)
        pre.step()
        (_, session), = pre.take_sessions()
        dec = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
        dec.adopt_session(session)
        dec.run()
    finally:
        mp.undo()
    return names


@pytest.mark.parametrize("site,program", sorted(
    serving_mod.PROGRAM_NAMES.items()))
def test_compile_site_lowers_to_its_role_name(lowered_names, site, program):
    """Named by role, never by shape: one name per site whatever the
    bucket, group or block size (the profiler then reads ``jit_<name>``)."""
    assert lowered_names.get(site) == {f"jit_{program}"}


def test_train_step_lowers_to_its_name():
    import optax

    from tensorflowonspark_tpu.parallel.strategy import DataParallelStrategy

    strategy = DataParallelStrategy(devices=jax.devices()[:1])
    state = strategy.init_state(lambda: {"w": jnp.ones((4, 2))},
                                optax.sgd(0.1))

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"]) ** 2)

    step = strategy.build_train_step(loss_fn, donate=False)
    lowered = step.lower(state, {"x": jnp.ones((8, 4))})
    assert _module_name(lowered) == "jit_tfos_train_step"
    text = lowered.as_text(debug_info=True)
    assert "loss_and_grad" in text and "optimizer_update" in text


def test_decode_program_carries_the_named_scopes():
    """flax scopes each module; these name what is INSIDE attention, the
    embedding lookup, the tied head and token selection."""
    cfg, params = _make(pos_encoding="learned")
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    text = b._step_sample.lower(
        params, b.cache, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.ones((2,), jnp.float32), jnp.ones((2,), jnp.float32)
    ).as_text(debug_info=True)
    for scope in ("qkv", "kv_store", "kv_gather", "scores", "context",
                  "embed", "lm_head", "sample"):
        assert f"/{scope}" in text, scope
