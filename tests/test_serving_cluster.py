"""Distributed online serving tier (``tensorflowonspark_tpu/serving``).

Two layers, mirroring the health tests' split:

- **unit** — ``ReplicaScheduler`` + ``ServeFrontend``/``ServeClient``
  against deterministic in-process fake replicas, so every policy branch
  (shed, deadline, least-outstanding routing, requeue-once failover,
  typed errors, stream dedup across failover) is exercised fast.
- **integration** — real 2-replica clusters (``LocalProcessBackend``,
  spawned worker processes hosting ``ContinuousBatcher``), locked
  greedy-exact against solo ``greedy_generate`` oracles, including a
  chaos SIGKILL of a replica mid-stream (fast variant tier-1; the soak
  is ``-m slow``).
"""

import os
import queue as _queue
import threading
import time

import numpy as np
import pytest

from tensorflowonspark_tpu.serving import (DeadlineExceeded, ReplicaFailed,
                                           ReplicaScheduler, RequestRejected,
                                           ServeClient, ServeFrontend)

# --------------------------------------------------------------- fakes


class _FakeBackend:
    def __init__(self, n):
        self.codes = {i: None for i in range(n)}

    def exitcodes(self):
        return dict(self.codes)

    def failed(self):
        return [i for i, c in self.codes.items() if c not in (0, None)]


def _fake_tokens(prompt, n):
    """The fake replica's deterministic 'decode': a pure function of the
    request, like the real batcher's contract — so a failover replay
    regenerates the identical sequence."""
    base = int(np.sum(np.asarray(prompt, np.int64)))
    return [(base + 7 * k) % 101 for k in range(n)]


class _FakeWorld:
    """N serial fake replicas speaking the serve queue protocol over
    in-process queues; ``kill(i)`` emulates a SIGKILL (exit code -9,
    connections start raising)."""

    def __init__(self, n, token_delay=0.0):
        self.backend = _FakeBackend(n)
        self.cluster_info = [
            {"executor_id": i, "job_name": "worker",
             "addr": ("127.0.0.1", 0), "authkey": b"x"} for i in range(n)]
        self.cluster_meta = {"queue_shm": False}
        self.working_dir = None
        self.token_delay = token_delay
        self.inq = {i: _queue.Queue() for i in range(n)}
        self.outq = {i: _queue.Queue() for i in range(n)}
        self._dead: set[int] = set()
        self.threads = [threading.Thread(target=self._run, args=(i,),
                                         daemon=True) for i in range(n)]
        for t in self.threads:
            t.start()

    def _run(self, i):
        while i not in self._dead:
            try:
                item = self.inq[i].get(timeout=0.02)
            except _queue.Empty:
                continue
            rid, p = item["rid"], item["prompt"]
            for k, tok in enumerate(_fake_tokens(p, item["max_new_tokens"])):
                if i in self._dead:
                    return               # died mid-stream
                if self.token_delay:
                    time.sleep(self.token_delay)
                self.outq[i].put({"rid": rid, "event": "tok",
                                  "tokens": [tok], "load": 1})
            self.outq[i].put({"rid": rid, "event": "done", "load": 0})

    def kill(self, i):
        self._dead.add(i)
        self.backend.codes[i] = -9

    def add_replica(self):
        """Bring up one more fake replica (live scale-up); returns its
        info dict, shaped like a reservation."""
        i = len(self.cluster_info)
        info = {"executor_id": i, "job_name": "worker",
                "addr": ("127.0.0.1", 0), "authkey": b"x"}
        self.cluster_info.append(info)
        self.backend.codes[i] = None
        self.inq[i] = _queue.Queue()
        self.outq[i] = _queue.Queue()
        t = threading.Thread(target=self._run, args=(i,), daemon=True)
        self.threads.append(t)
        t.start()
        return info

    def exit_clean(self, i):
        """Emulate a clean worker exit (drained retire / preemption)."""
        self._dead.add(i)
        self.backend.codes[i] = 0

    def client(self, info):
        eid, world = info["executor_id"], self

        class _C:
            def put(self, qname, item, timeout=None):
                if eid in world._dead:
                    raise ConnectionError("replica dead")
                world.inq[eid].put(item)

            def get(self, qname, timeout=0.5):
                if eid in world._dead:
                    raise ConnectionError("replica dead")
                try:
                    return world.outq[eid].get(timeout=timeout)
                except _queue.Empty:
                    raise TimeoutError

            def close(self):
                pass

        return _C()


def _scheduler(world, **kw):
    kw.setdefault("slots_per_replica", 2)
    kw.setdefault("poll_interval", 0.05)
    return ReplicaScheduler(world, client_factory=world.client, **kw)


def _collect(req, timeout=10.0):
    """Drain one request's event stream; returns (tokens, error_or_None)."""
    toks, deadline = [], time.monotonic() + timeout
    while True:
        ev = req.events.get(timeout=max(0.01, deadline - time.monotonic()))
        if ev[0] == "tok":
            toks.extend(ev[1])
        elif ev[0] == "done":
            return toks, None
        else:
            return toks, ev


# ------------------------------------------------------- scheduler units

def test_scheduler_routes_and_completes():
    world = _FakeWorld(2)
    s = _scheduler(world).start()
    try:
        prompts = [np.arange(1, 4 + i, dtype=np.int32) for i in range(6)]
        reqs = [s.submit(p, 5) for p in prompts]
        for req, p in zip(reqs, prompts):
            toks, err = _collect(req)
            assert err is None and toks == _fake_tokens(p, 5)
        m = s.metrics()
        assert m["accepted"] == m["completed"] == 6
        assert m["shed"] == m["failed"] == m["requeued"] == 0
        assert m["ttft"]["count"] == 6 and m["e2e"]["p99_secs"] is not None
        # least-outstanding routing spread work over both replicas
        assert all(r["served"] > 0 for r in m["replicas"].values())
    finally:
        s.stop()


def test_routing_tie_breaks_on_kv_page_pressure():
    """Equal outstanding + equal reported load: the replica reporting
    MORE free KV pages wins the route (memory pressure tie-break); both
    primary keys still outrank it."""
    from types import SimpleNamespace

    world = _FakeWorld(2)
    s = _scheduler(world)            # policy unit: never started
    try:
        a, b = s.replicas[0], s.replicas[1]
        # replicas report page capacity on the response wire
        s._handle_response(a, {"rid": None, "event": "",
                               "load": 0, "free_pages": 2})
        s._handle_response(b, {"rid": None, "event": "",
                               "load": 0, "free_pages": 9})
        assert s.metrics()["replicas"][1]["free_pages"] == 9
        with s._lock:
            assert s._pick_replica() is b
        # fewer outstanding outranks page pressure...
        b.outstanding[99] = SimpleNamespace(finished=True)
        with s._lock:
            assert s._pick_replica() is a
        b.outstanding.clear()
        # ...and so does lower self-reported load
        a.reported_load, b.reported_load = 0, 3
        with s._lock:
            assert s._pick_replica() is a
    finally:
        s.stop()


def test_scheduler_sheds_at_bounded_depth():
    world = _FakeWorld(1, token_delay=0.2)   # slow: backlog builds
    s = _scheduler(world, slots_per_replica=1, overcommit=1,
                   max_queue_depth=2).start()
    try:
        a = s.submit(np.asarray([1], np.int32), 3)
        b = s.submit(np.asarray([2], np.int32), 3)
        with pytest.raises(RequestRejected) as ei:
            s.submit(np.asarray([3], np.int32), 3)
        assert ei.value.reason == "queue_full"
        assert s.metrics()["shed"] == 1
        for req in (a, b):                   # accepted work still completes
            _, err = _collect(req)
            assert err is None
    finally:
        s.stop()


def test_scheduler_expires_queued_request_past_deadline():
    world = _FakeWorld(1, token_delay=0.2)
    s = _scheduler(world, slots_per_replica=1, overcommit=1).start()
    try:
        blocker = s.submit(np.asarray([1], np.int32), 4)  # owns the slot
        late = s.submit(np.asarray([2], np.int32), 4, timeout=0.05)
        toks, err = _collect(late)
        assert err is not None and err[1] == "deadline" and toks == []
        assert s.metrics()["expired"] == 1
        _, err = _collect(blocker)
        assert err is None
    finally:
        s.stop()


def test_replica_death_requeues_once_with_exact_stream():
    """Kill the replica serving a request mid-stream: the request replays
    on the survivor and the client-visible stream is the exact oracle
    sequence with no duplicates or gaps (skip-dedup across failover)."""
    world = _FakeWorld(2, token_delay=0.05)
    s = _scheduler(world, slots_per_replica=1, overcommit=1).start()
    try:
        p = np.asarray([3, 5], np.int32)
        req = s.submit(p, 8)
        # wait until some tokens flowed, then kill the serving replica
        while not req.tokens:
            time.sleep(0.01)
        victim = req.replica
        world.kill(victim)
        toks, err = _collect(req, timeout=15)
        assert err is None
        assert toks == _fake_tokens(p, 8), "failover stream not exact"
        m = s.metrics()
        assert m["requeued"] == 1 and m["completed"] == 1
        assert not m["replicas"][victim]["alive"]
        assert s.dead_replicas() == {victim}
    finally:
        s.stop()


def test_trace_id_survives_requeue_failover(tmp_path):
    """End-to-end tracing across the failover path: the trace id stamped
    at admission survives the requeue-once hop to the surviving replica,
    and ``tracing.stitch_trace`` reconstructs the full
    admission → route → first-token → requeue → re-route → done timeline
    (with the untraced ``replica_dead`` folded in as context)."""
    from tensorflowonspark_tpu import tracing
    from tensorflowonspark_tpu.observability import EventLog

    world = _FakeWorld(2, token_delay=0.05)
    log = EventLog(str(tmp_path / "serving_events.jsonl"))
    s = _scheduler(world, slots_per_replica=1, overcommit=1,
                   event_log=log).start()
    try:
        p = np.asarray([3, 5], np.int32)
        trace = tracing.new_trace_id()
        req = s.submit(p, 8, trace=trace)
        assert req.trace == trace
        assert req.message()["trace"] == trace   # rides the wire message
        while not req.tokens:
            time.sleep(0.01)
        victim = req.replica
        world.kill(victim)
        toks, err = _collect(req, timeout=15)
        assert err is None and toks == _fake_tokens(p, 8)
    finally:
        s.stop()
        log.close()

    timeline = tracing.stitch_trace(str(tmp_path), trace)
    kinds = [r["kind"] for r in timeline if not r.get("_context")]
    assert kinds[0] == "request_admitted" and kinds[-1] == "request_done"
    routed = [r for r in timeline if r["kind"] == "request_routed"]
    assert len(routed) == 2, "expected a route before and after failover"
    assert routed[0]["replica"] == victim != routed[1]["replica"]
    assert [r["attempt"] for r in routed] == [1, 2]
    (requeued,) = [r for r in timeline if r["kind"] == "request_requeued"]
    assert requeued["from_replica"] == victim and requeued["trace"] == trace
    assert all(r["trace"] == trace for r in timeline
               if not r.get("_context"))
    # the replica kill that explains the hop appears as a context row
    assert any(r["kind"] == "replica_dead" and r.get("_context")
               for r in timeline)
    # and the CLI-facing formatter renders it
    text = tracing.format_timeline(timeline)
    assert "request_requeued" in text and "[context]" in text


def test_scheduler_registry_series_update(tmp_path):
    """The scheduler's registry instruments: outcome counters tick and
    the collect hook mirrors queue depth / per-replica gauges into a
    snapshot."""
    from tensorflowonspark_tpu import metrics as tpu_metrics

    world = _FakeWorld(2)
    s = _scheduler(world).start()
    reg = tpu_metrics.get_registry()
    c = reg.counter("tfos_serving_requests_total",
                    labelnames=("outcome", "model"))
    accepted0 = c.value(outcome="accepted", model="default")
    completed0 = c.value(outcome="completed", model="default")
    try:
        req = s.submit(np.asarray([1, 2], np.int32), 4)
        _, err = _collect(req)
        assert err is None
        # single-model tiers collapse to the model="default" series
        assert c.value(outcome="accepted", model="default") == accepted0 + 1
        assert c.value(outcome="completed",
                       model="default") == completed0 + 1
        snap = reg.snapshot()    # runs the collect hook
        outst = {tuple(sorted(lbl.items())): v for lbl, v in
                 snap["tfos_serving_replica_outstanding_count"]["samples"]}
        assert (("replica", "0"),) in outst and (("replica", "1"),) in outst
        assert snap["tfos_serving_replicas_alive_count"]["samples"] \
            == [[{}, 2.0]]
        ((_, ttft),) = snap["tfos_serving_ttft_seconds"]["samples"]
        assert ttft["count"] >= 1
        # a dead replica's series are removed, not frozen at last value
        world.kill(1)
        deadline = time.monotonic() + 5
        while 1 not in s.dead_replicas() and time.monotonic() < deadline:
            time.sleep(0.02)
        snap = reg.snapshot()
        labels = [lbl for lbl, _ in
                  snap["tfos_serving_replica_outstanding_count"]["samples"]]
        assert {"replica": "0"} in labels and {"replica": "1"} not in labels
        assert snap["tfos_serving_replicas_alive_count"]["samples"] \
            == [[{}, 1.0]]
    finally:
        s.stop()


def test_replica_death_beyond_requeue_limit_fails_typed():
    world = _FakeWorld(2, token_delay=0.05)
    s = _scheduler(world, slots_per_replica=1, overcommit=1,
                   requeue_limit=0).start()
    try:
        req = s.submit(np.asarray([4], np.int32), 8)
        while not req.tokens:
            time.sleep(0.01)
        world.kill(req.replica)
        _, err = _collect(req, timeout=15)
        assert err is not None and err[1] == "replica_failed"
        assert s.metrics()["failed"] == 1
    finally:
        s.stop()


def test_last_replica_death_fails_no_replica_and_rejects_submits():
    world = _FakeWorld(1, token_delay=0.05)
    s = _scheduler(world, slots_per_replica=1, overcommit=1).start()
    try:
        req = s.submit(np.asarray([5], np.int32), 8)
        while not req.tokens:
            time.sleep(0.01)
        world.kill(0)
        _, err = _collect(req, timeout=15)
        assert err is not None and err[1] == "no_replica"
        with pytest.raises(RequestRejected) as ei:
            s.submit(np.asarray([6], np.int32), 2)
        assert ei.value.reason == "no_replica"
    finally:
        s.stop()


def test_monitor_failure_subscription_marks_dead():
    """on_cluster_failure (the ClusterMonitor hook) retires the implicated
    replica even when its process looks alive (the hang shape)."""
    from tensorflowonspark_tpu.health import HANG, ClusterFailure

    world = _FakeWorld(2)
    s = _scheduler(world).start()
    try:
        s.on_cluster_failure(ClusterFailure(HANG, "wedged", (1,)))
        assert s.dead_replicas() == {1}
        # traffic keeps flowing on the survivor
        req = s.submit(np.asarray([9], np.int32), 3)
        toks, err = _collect(req)
        assert err is None and toks == _fake_tokens([9], 3)
    finally:
        s.stop()


def test_scheduler_stop_rejects_and_errors_leftovers():
    world = _FakeWorld(1, token_delay=0.3)
    s = _scheduler(world).start()
    req = s.submit(np.asarray([1, 2], np.int32), 5)
    s.stop()
    _, err = _collect(req)
    assert err is not None and err[1] == "shutdown"
    with pytest.raises(RequestRejected) as ei:
        s.submit(np.asarray([1], np.int32), 1)
    assert ei.value.reason == "shutdown"


# --------------------------------------------- tenant admission units

def test_token_bucket_rate_and_burst():
    from tensorflowonspark_tpu.serving import TokenBucket

    b = TokenBucket(rate=2.0, burst=3)
    t = 100.0
    assert [b.try_take(t) for _ in range(4)] == [True, True, True, False]
    assert b.try_take(t + 0.5)            # 0.5s x 2/s = 1 token back
    assert not b.try_take(t + 0.5)
    # refill caps at burst, no matter how long idle
    assert [b.try_take(t + 100.0) for _ in range(4)] \
        == [True, True, True, False]


def test_tenant_throttle_sheds_only_the_noisy_tenant():
    """Acceptance: per-tenant shed hits ONLY the over-budget tenant —
    the noisy tenant's burst exhausts its bucket and gets typed
    ``tenant_throttled`` rejections while the quiet tenant's requests,
    submitted between the noisy ones, all sail through."""
    world = _FakeWorld(2)
    s = _scheduler(world, max_queue_depth=256,
                   tenants={"noisy": {"rate": 0.001, "burst": 3},
                            "quiet": {"rate": None}}).start()
    try:
        accepted, shed = [], []
        for k in range(8):
            try:
                accepted.append(
                    s.submit(np.asarray([k + 1], np.int32), 2,
                             tenant="noisy"))
            except RequestRejected as e:
                assert e.reason == "tenant_throttled"
                assert "noisy" in str(e)
                shed.append(k)
            # interleaved quiet traffic is never shed
            accepted.append(s.submit(np.asarray([50 + k], np.int32), 2,
                                     tenant="quiet"))
        assert len(shed) == 5            # burst of 3 admitted, rest shed
        for req in accepted:
            _, err = _collect(req)
            assert err is None
        m = s.metrics()
        assert m["tenants"]["noisy"]["shed"] == 5
        assert m["tenants"]["noisy"]["accepted"] == 3
        assert m["tenants"]["quiet"]["shed"] == 0
        assert m["tenants"]["quiet"]["accepted"] == 8
        assert m["shed"] == 5 and m["failed"] == 0
    finally:
        s.stop()


def test_priority_classes_order_the_pending_queue():
    """With one busy slot, later-admitted high-priority work dispatches
    ahead of earlier low-priority work (FIFO within a class)."""
    world = _FakeWorld(1, token_delay=0.1)
    s = _scheduler(world, slots_per_replica=1, overcommit=1,
                   max_queue_depth=16,
                   tenants={"batch": {"priority": "low"},
                            "inter": {"priority": "high"}}).start()
    try:
        blocker = s.submit(np.asarray([1], np.int32), 3)   # owns the slot
        low = [s.submit(np.asarray([10 + k], np.int32), 2, tenant="batch")
               for k in range(2)]
        high = s.submit(np.asarray([30], np.int32), 2, tenant="inter")
        for req in (blocker, high, *low):
            _, err = _collect(req)
            assert err is None
        assert high.priority == "high" and low[0].priority == "low"
        # the replica is strictly serial, so first-token times reflect
        # dispatch order: high (admitted LAST) ran before both lows
        assert high.first_token_at < low[0].first_token_at \
            < low[1].first_token_at
        assert s.metrics()["completed"] == 4
    finally:
        s.stop()


def test_priority_override_can_only_demote():
    world = _FakeWorld(1)
    s = _scheduler(world, tenants={"t": {"priority": "normal"}}).start()
    try:
        up = s.submit(np.asarray([1], np.int32), 1, tenant="t",
                      priority="high")
        down = s.submit(np.asarray([2], np.int32), 1, tenant="t",
                        priority="low")
        assert up.priority == "normal"      # promotion denied
        assert down.priority == "low"       # demotion honored
        with pytest.raises(ValueError):
            s.submit(np.asarray([3], np.int32), 1, priority="urgent")
        for req in (up, down):
            _, err = _collect(req)
            assert err is None
    finally:
        s.stop()


# --------------------------------------------- elastic membership units

def test_live_add_replica_takes_traffic():
    world = _FakeWorld(1)
    s = _scheduler(world).start()
    try:
        _, err = _collect(s.submit(np.asarray([1], np.int32), 3))
        assert err is None
        s.add_replica(world.add_replica())
        assert s.alive_replicas() == {0, 1}
        # saturate: enough parallel work that least-outstanding routing
        # must spill onto the newcomer
        reqs = [s.submit(np.asarray([k + 2], np.int32), 3)
                for k in range(8)]
        for req in reqs:
            _, err = _collect(req)
            assert err is None
        m = s.metrics()
        assert m["replicas"][1]["served"] > 0, "newcomer got no traffic"
        with pytest.raises(ValueError):
            s.add_replica(world.cluster_info[1])   # double registration
    finally:
        s.stop()


def test_drain_based_retire_is_clean_and_loses_nothing():
    """Mark-drain → drain → retire mid-stream: the in-flight request
    finishes on the draining replica (exact), no new work routes to it,
    and the departure never counts as a death."""
    world = _FakeWorld(2, token_delay=0.05)
    s = _scheduler(world, slots_per_replica=1, overcommit=1).start()
    try:
        p = np.asarray([3, 5], np.int32)
        req = s.submit(p, 6)
        while not req.tokens:
            time.sleep(0.01)
        victim = req.replica
        assert s.mark_draining(victim)
        assert not s.mark_draining(victim)     # idempotent
        assert s.draining_replicas() == {victim}
        # new work only lands on the survivor
        other = [s.submit(np.asarray([9 + k], np.int32), 2)
                 for k in range(3)]
        toks, err = _collect(req)
        assert err is None and toks == _fake_tokens(p, 6)
        assert s.drain_replica(victim, timeout=10)
        s.retire_replica(victim)
        world.exit_clean(victim)
        for r in other:
            assert r.replica != victim
            _, err = _collect(r)
            assert err is None
        m = s.metrics()
        assert s.dead_replicas() == set()       # retired, NOT dead
        assert m["replicas"][victim]["retired"]
        assert m["requeued"] == 0 and m["failed"] == 0
        # traffic continues on the survivor
        _, err = _collect(s.submit(np.asarray([40], np.int32), 2))
        assert err is None
    finally:
        s.stop()


def test_forced_retire_requeues_in_flight_exactly():
    """Retiring WITHOUT waiting for the drain re-queues the in-flight
    request to the survivor — stream stays exact and the planned move
    does not burn the request's failover attempt."""
    world = _FakeWorld(2, token_delay=0.05)
    s = _scheduler(world, slots_per_replica=1, overcommit=1).start()
    try:
        p = np.asarray([4, 7], np.int32)
        req = s.submit(p, 8)
        while not req.tokens:
            time.sleep(0.01)
        victim = req.replica
        s.retire_replica(victim, reason="forced")   # no drain first
        world.exit_clean(victim)
        toks, err = _collect(req, timeout=15)
        assert err is None and toks == _fake_tokens(p, 8)
        m = s.metrics()
        assert m["requeued"] == 1 and m["completed"] == 1
        assert s.dead_replicas() == set()
        # the replay kept its one real-failure requeue budget: retire the
        # serving replica mid-flight AGAIN (replacement registered
        # first) and the request must still complete via a second
        # planned re-queue — only real deaths charge the failover limit
        s.add_replica(world.add_replica())
        req2 = s.submit(p, 8)
        while not req2.tokens:
            time.sleep(0.01)
        s.retire_replica(req2.replica, reason="forced")
        toks, err = _collect(req2, timeout=15)
        assert err is None and toks == _fake_tokens(p, 8)
    finally:
        s.stop()


# ------------------------------------------------- sharded gang units

def test_gang_registration_and_capacity_accounting():
    """gang_size=2 over 4 workers registers TWO routable endpoints
    (leaders 0 and 2, members 1 and 3) with device-weighted capacity —
    a tp gang is one endpoint with a weight, not N replicas."""
    world = _FakeWorld(4)
    s = _scheduler(world, gang_size=2, capacity_weight=2).start()
    try:
        assert set(s.replicas) == {0, 2}
        assert s.gang_members(0) == (0, 1) and s.gang_members(2) == (2, 3)
        assert s.resolve_gang(1) == 0 and s.resolve_gang(3) == 2
        assert s.resolve_gang(2) == 2        # leaders resolve to selves
        m = s.metrics()
        assert m["gang_size"] == 2 and m["capacity_devices"] == 4
        assert m["replicas"][0]["weight"] == 2
        assert m["replicas"][0]["members"] == [1]
        # traffic routes over LEADERS only
        reqs = [s.submit(np.arange(1, 3 + k, dtype=np.int32), 4)
                for k in range(6)]
        for req in reqs:
            _, err = _collect(req)
            assert err is None
        m = s.metrics()
        assert all(m["replicas"][eid]["served"] > 0 for eid in (0, 2))
        # live gang add registers leader + member as one endpoint
        info4 = world.add_replica()
        world.add_replica()                  # member slot (eid 5)
        s.add_replica(info4, members=(5,))
        assert s.alive_replicas() == {0, 2, 4}
        assert s.resolve_gang(5) == 4
        assert s.metrics()["capacity_devices"] == 6
        # a gang endpoint needs exactly gang_size-1 members
        with pytest.raises(ValueError, match="gang"):
            s.add_replica({"executor_id": 6, "addr": ("x", 0),
                           "authkey": b"x"}, members=())
    finally:
        s.stop()


def test_gang_misaligned_blocks_rejected():
    world = _FakeWorld(3)
    with pytest.raises(ValueError, match="not a multiple of gang_size"):
        _scheduler(world, gang_size=2)


def test_gang_member_death_fails_whole_gang_over_once():
    """SIGKILL one NON-LEADER shard mid-stream: the whole gang
    classifies dead, its in-flight request re-queues ONCE to the
    surviving gang, and the client stream is the exact oracle sequence
    (skip-dedup across the gang failover)."""
    world = _FakeWorld(4, token_delay=0.05)
    s = _scheduler(world, gang_size=2, capacity_weight=2,
                   slots_per_replica=1, overcommit=1).start()
    try:
        p = np.asarray([3, 5], np.int32)
        req = s.submit(p, 8)
        while not req.tokens:
            time.sleep(0.01)
        victim_leader = req.replica
        member = victim_leader + 1
        world.kill(member)                  # the member, NOT the leader
        from tensorflowonspark_tpu.health import ClusterFailure

        s.on_cluster_failure(ClusterFailure(
            "crash", f"crash: worker {member} exit=-9",
            failed_workers=(member,)))
        toks, err = _collect(req, timeout=15)
        assert err is None
        assert toks == _fake_tokens(p, 8), "gang failover stream not exact"
        m = s.metrics()
        assert m["requeued"] == 1 and m["completed"] == 1
        assert not m["replicas"][victim_leader]["alive"]
        # dead set covers the WHOLE gang (shutdown tolerance needs every
        # corpse), and capacity dropped by the gang's weight
        assert s.dead_replicas() == {victim_leader, member}
        assert m["capacity_devices"] == 2
    finally:
        s.stop()


def test_gang_member_exit_detected_by_supervisor():
    """The backend-exitcode supervision path alone (no monitor event)
    must also resolve a member's death to the whole gang."""
    world = _FakeWorld(4)
    s = _scheduler(world, gang_size=2, poll_interval=0.05).start()
    try:
        world.kill(3)                       # member of gang 2
        deadline = time.monotonic() + 5
        while s.alive_replicas() != {0} and time.monotonic() < deadline:
            time.sleep(0.02)
        assert s.alive_replicas() == {0}
        assert s.dead_replicas() == {2, 3}
    finally:
        s.stop()


def test_autoscaler_weights_capacity_by_gang_devices():
    """A tp=4 gang counts 4 capacity units in the up-pressure signal:
    the same queue depth that would scale a 4-replica tier up must NOT
    scale a single 4-device gang tier up at 4x the per-unit threshold,
    and vice versa must once the weighted threshold is crossed."""
    from tensorflowonspark_tpu.serving import Autoscaler

    fake = _FakeServing(replicas=1)
    # graft gang weight onto the fake's metrics
    base_metrics = fake.scheduler.metrics

    def metrics():
        m = base_metrics()
        for r in m["replicas"].values():
            r["weight"] = 4
        return m

    fake.scheduler.metrics = metrics
    a = Autoscaler(fake, min_replicas=1, max_replicas=3,
                   up_queue_per_replica=4.0, up_consecutive=1,
                   up_cooldown=0.0)
    fake.queued = 9        # 9 > 4*1 endpoint, but NOT > 4*4 devices
    s = a.sample()
    assert s["capacity"] == 4
    assert a.decide(s, now=1.0)[0] == "hold"
    fake.queued = 17       # 17 > 4 units x 4/unit: genuine overload
    d, reason = a.decide(a.sample(), now=2.0)
    assert d == "up" and "capacity" in reason


# ------------------------------------------------------ autoscaler units

class _FakeServing:
    """Scheduler-facade the Autoscaler drives in units: canned metrics,
    recorded actions."""

    def __init__(self, replicas=1):
        self.n = replicas
        self.queued = 0
        self.outstanding = 0
        self.added = 0
        self.retired = []
        self.events = []
        fake = self

        class _Sched:
            def metrics(self):
                return {
                    "queued": fake.queued,
                    "ttft": {"p95_secs": None},
                    "replicas": {
                        i: {"alive": True, "draining": False,
                            "outstanding": fake.outstanding // max(1, fake.n)}
                        for i in range(fake.n)},
                }

            def emit_event(self, kind, **fields):
                fake.events.append((kind, fields))

        self.scheduler = _Sched()

    def add_replicas(self, n):
        self.n += n
        self.added += n
        return list(range(self.n - n, self.n))

    def retire_replica(self, eid, drain_timeout=None):
        self.n -= 1
        self.retired.append(eid)
        return True


def test_autoscaler_decisions_hysteresis_and_cooldown():
    from tensorflowonspark_tpu.serving import Autoscaler

    fake = _FakeServing(replicas=1)
    a = Autoscaler(fake, min_replicas=1, max_replicas=3,
                   up_queue_per_replica=4.0, up_consecutive=2,
                   up_cooldown=10.0, down_consecutive=2,
                   down_cooldown=30.0,
                   down_outstanding_per_replica=1.0)
    t = 1000.0
    fake.queued = 9                       # 9 > 4*1: overload
    assert a.decide(a.sample(), now=t)[0] == "hold"      # 1 sample: wait
    d, reason = a.decide(a.sample(), now=t + 1)
    assert d == "up" and "queued 9" in reason            # hysteresis met
    a.acted("up", now=t + 1)
    fake.n = 2
    # still overloaded but inside the up-cooldown: hold
    assert a.decide(a.sample(), now=t + 2)[0] == "hold"
    assert a.decide(a.sample(), now=t + 3)[0] == "hold"
    # past the cooldown (and streak rebuilt): up again, capped at max
    d, _ = a.decide(a.sample(), now=t + 12)
    assert d == "up"
    a.acted("up", now=t + 12)
    fake.n = 3
    fake.queued = 20
    # at max_replicas: no more ups no matter the load
    for k in range(5):
        assert a.decide(a.sample(), now=t + 30 + k)[0] == "hold"
    # load vanishes: scale down only after ITS hysteresis + cooldown
    fake.queued = 0
    fake.outstanding = 0
    assert a.decide(a.sample(), now=t + 40)[0] == "hold"
    d, reason = a.decide(a.sample(), now=t + 41)
    assert d == "down" and "idle" in reason
    a.acted("down", now=t + 41)
    fake.n = 2
    # down-cooldown holds the next shrink
    assert a.decide(a.sample(), now=t + 42)[0] == "hold"
    assert a.decide(a.sample(), now=t + 43)[0] == "hold"
    d, _ = a.decide(a.sample(), now=t + 72)
    assert d == "down"


def test_autoscaler_ttft_signal_and_min_bound():
    from tensorflowonspark_tpu.serving import Autoscaler

    fake = _FakeServing(replicas=2)
    a = Autoscaler(fake, min_replicas=2, max_replicas=3,
                   up_ttft_p95=0.5, up_consecutive=1, up_cooldown=0.0,
                   down_consecutive=1, down_cooldown=0.0)
    s = a.sample()
    s["ttft_p95"] = 0.8                   # latency breach, queue empty
    d, reason = a.decide(s, now=1.0)
    assert d == "up" and "ttft" in reason
    a.acted("up", now=1.0)
    # idle at min_replicas: never below the floor
    fake.queued = 0
    fake.outstanding = 0
    assert a.decide(a.sample(), now=100.0)[0] == "hold"


def test_autoscaler_loop_acts_and_emits_events():
    """The threaded loop end-to-end over the facade: overload → add;
    idle → drain-based retire; both actions land in the event stream."""
    from tensorflowonspark_tpu.serving import Autoscaler

    fake = _FakeServing(replicas=1)
    fake.queued = 50
    a = Autoscaler(fake, min_replicas=1, max_replicas=2, interval=0.05,
                   up_queue_per_replica=4.0, up_consecutive=2,
                   up_cooldown=0.0, down_consecutive=2, down_cooldown=0.0)
    a.start()
    try:
        deadline = time.monotonic() + 5
        while fake.added == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert fake.added >= 1, "no scale-up happened"
        fake.queued = 0
        fake.outstanding = 0
        deadline = time.monotonic() + 5
        while not fake.retired and time.monotonic() < deadline:
            time.sleep(0.02)
        assert fake.retired, "no scale-down happened"
    finally:
        a.stop()
    kinds = [k for k, _ in fake.events]
    assert "scale_up" in kinds and "scale_down" in kinds
    up = dict(fake.events)[("scale_up")]
    assert "reason" in up and "queued" in up


# ---------------------------------------------- warm-standby pool units

class _PoolWorld(_FakeWorld):
    """_FakeWorld + the cluster surface StandbyPool/ServingCluster need:
    ``add_workers`` spawns fake replicas in gang-sized blocks,
    ``_client_for`` swallows driver control messages (promote etc.)."""

    def add_workers(self, n, map_fun=None, tf_args=None, timeout=None):
        return [self.add_replica() for _ in range(n)]

    def _client_for(self, eid):
        class _Null:
            def put(self, qname, item, timeout=None):
                pass
        return _Null()

    def retire_worker(self, eid):
        pass


def _standby_tier(world, scheduler, pool_size):
    """A driver-side ServingCluster over fakes (no frontend/monitor),
    with a filled warm-standby pool — the unit harness for promotion
    race-safety."""
    from tensorflowonspark_tpu.serving import ServingCluster, StandbyPool

    tier = ServingCluster(world, scheduler, monitor=None, frontend=None,
                          address=("127.0.0.1", 0))
    scheduler.on_replica_ready = tier._on_standby_ready
    tier.standbys = StandbyPool(tier, pool_size)
    tier.standbys.fill()
    return tier


def test_standby_promotion_race_promotes_two_different_standbys():
    """Acceptance (race-safety): a concurrent replica failure and an
    autoscaler scale-up each acquire a standby — with two pooled, they
    promote two DIFFERENT ones (acquire pops atomically; a double
    promotion would blow up scheduler.add_replica's double-registration
    guard)."""
    world = _PoolWorld(2)
    s = _scheduler(world).start()
    tier = _standby_tier(world, s, pool_size=2)
    try:
        assert tier.standbys.stats()["standbys"] == 2    # eids 2 and 3
        got = []
        threads = [threading.Thread(
            target=lambda src=src: got.append(tier.promote_standby(src)))
            for src in ("failure", "scale_up")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert sorted(got) == [2, 3], got
        assert {2, 3} <= s.alive_replicas()
        # both promoted gangs serve traffic
        for k in range(4):
            _, err = _collect(s.submit(np.asarray([k + 1], np.int32), 2))
            assert err is None
        # the standby_ready acks close the heal measurements AND release
        # the deferred backfills (restock waits for restored capacity)
        for eid in got:
            s._handle_response(s.replicas[eid],
                               {"rid": None, "event": "standby_ready"})
        deadline = time.monotonic() + 5
        while tier.standbys.stats()["standbys"] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert tier.standbys.stats()["standbys"] == 2
        m = tier.metrics()
        assert m["standby"]["promotions"] == {"failure": 1, "scale_up": 1}
        assert m["standby"]["heal"]["count"] == 2
    finally:
        tier.standbys.stop()
        s.stop()


def test_standby_promotion_race_with_one_standby_falls_back_cold():
    """With ONE pooled standby, a concurrent failure-heal + scale-up
    yield one promotion + one COLD spawn — never the same standby twice,
    and the tier still grows by two distinct replicas."""
    world = _PoolWorld(2)
    s = _scheduler(world).start()
    tier = _standby_tier(world, s, pool_size=1)
    try:
        standby_eid = tier.standbys.stats()["ready"][0]
        world.kill(1)
        s.on_cluster_failure(__import__(
            "tensorflowonspark_tpu.health", fromlist=["ClusterFailure"]
        ).ClusterFailure("crash", "crash: worker 1", (1,)))
        threads = [
            threading.Thread(target=tier._spawn_replacement,
                             kwargs=dict(eid=1, source="failure",
                                         promote_source="failure")),
            threading.Thread(target=lambda: tier.scale_up(1)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        deadline = time.monotonic() + 10
        while len(s.alive_replicas()) < 3 and time.monotonic() < deadline:
            time.sleep(0.05)
        alive = s.alive_replicas()
        assert standby_eid in alive, "the standby was never promoted"
        assert len(alive) == 3, alive    # 0 + promoted + one cold spawn
        for k in range(4):
            _, err = _collect(s.submit(np.asarray([k + 1], np.int32), 2))
            assert err is None
    finally:
        tier.standbys.stop()
        s.stop()


def test_standby_death_shrinks_pool_backfills_never_registers():
    """Acceptance (standby churn): a DEAD standby leaves the pool and is
    backfilled by a fresh one — and at no point does an unpromoted
    standby register with the scheduler."""
    from tensorflowonspark_tpu.health import ClusterFailure

    world = _PoolWorld(1)
    s = _scheduler(world).start()
    tier = _standby_tier(world, s, pool_size=1)
    try:
        standby_eid = tier.standbys.stats()["ready"][0]
        assert standby_eid == 1 and s.alive_replicas() == {0}
        world.kill(standby_eid)
        tier._on_cluster_failure(ClusterFailure(
            "crash", f"crash: worker {standby_eid}",
            (standby_eid,)))
        assert tier.standbys.leader_of(standby_eid) is None
        assert standby_eid in tier.standbys.dead
        deadline = time.monotonic() + 5
        while tier.standbys.stats()["standbys"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        fresh = tier.standbys.stats()["ready"]
        assert fresh and fresh[0] != standby_eid, fresh
        # the scheduler never saw either standby: no registration, no
        # death, no capacity change
        assert s.alive_replicas() == {0}
        assert s.dead_replicas() == set()
        assert standby_eid not in s.replicas
        _, err = _collect(s.submit(np.asarray([5], np.int32), 3))
        assert err is None
    finally:
        tier.standbys.stop()
        s.stop()


# ------------------------------------------------- frontend/client units

def test_frontend_client_roundtrip_and_typed_shed():
    """The TCP edge over fake replicas: generate, generate_stream (delta
    concat == generate), stats, and a typed queue_full rejection."""
    world = _FakeWorld(2)
    s = _scheduler(world, max_queue_depth=64).start()
    fe = ServeFrontend(s, authkey=b"s" * 16)
    addr = fe.start()
    try:
        with ServeClient(addr, b"s" * 16) as c:
            assert c.ping()
            p = np.asarray([2, 3, 4], np.int32)
            got = c.generate(p, 6)
            assert got.tolist() == _fake_tokens(p, 6)
            deltas = list(c.generate_stream(p, 6))
            assert [t for d in deltas for t in d] == _fake_tokens(p, 6)
            stats = c.stats()
            assert stats["completed"] == 2
            assert stats["ttft"]["count"] == 2
        with pytest.raises(ConnectionError):
            ServeClient(addr, b"wrong-key-------")
        # shed: shrink the bound under the scheduler lock-free counters
        s.max_queue_depth = 0
        with ServeClient(addr, b"s" * 16) as c, \
                pytest.raises(RequestRejected) as ei:
            c.generate(p, 2)
        assert ei.value.reason == "queue_full"
    finally:
        fe.stop()
        s.stop()


def test_frontend_deadline_mid_request_is_typed():
    world = _FakeWorld(1, token_delay=0.15)
    s = _scheduler(world, slots_per_replica=1, overcommit=1).start()
    fe = ServeFrontend(s, authkey=b"s" * 16)
    addr = fe.start()
    try:
        with ServeClient(addr, b"s" * 16) as c, \
                pytest.raises(DeadlineExceeded):
            c.generate(np.asarray([1], np.int32), 50, timeout=0.3)
    finally:
        fe.stop()
        s.stop()


def test_frontend_carries_tenant_and_priority():
    """Tenant/priority ride the wire: a client bound to the noisy tenant
    sees typed tenant_throttled shed; the quiet client's traffic (and
    the default tenant) sails through."""
    world = _FakeWorld(1)
    s = _scheduler(world, max_queue_depth=64,
                   tenants={"noisy": {"rate": 0.001, "burst": 1},
                            "quiet": {"rate": None,
                                      "priority": "high"}}).start()
    fe = ServeFrontend(s, authkey=b"s" * 16)
    addr = fe.start()
    try:
        p = np.asarray([5], np.int32)
        with ServeClient(addr, b"s" * 16, tenant="noisy") as c:
            c.generate(p, 2)                       # burst of 1
            with pytest.raises(RequestRejected) as ei:
                c.generate(p, 2)
            assert ei.value.reason == "tenant_throttled"
            # per-call override outruns the client default
            c.generate(p, 2, tenant="quiet")
        with ServeClient(addr, b"s" * 16) as c:    # default tenant
            c.generate(p, 2)
            stats = c.stats()
        assert stats["tenants"]["noisy"]["shed"] == 1
        assert stats["tenants"]["noisy"]["accepted"] == 1
        assert stats["tenants"]["quiet"]["accepted"] == 1
        assert stats["tenants"]["default"]["accepted"] == 1
    finally:
        fe.stop()
        s.stop()


def test_client_reconnects_once_on_idle_socket_error():
    """Satellite: a transient socket failure on an IDLE connection (the
    frontend closed the keep-alive between requests) is healed by one
    reconnect-and-retry; a genuinely dead frontend still raises after
    the single retry — typed, not swallowed."""
    world = _FakeWorld(1)
    s = _scheduler(world).start()
    fe = ServeFrontend(s, authkey=b"s" * 16)
    addr = fe.start()
    c = ServeClient(addr, b"s" * 16, timeout=5.0)
    try:
        p = np.asarray([2, 3], np.int32)
        got = c.generate(p, 3)
        # sever the established connection out from under the client —
        # the next send/receive fails like a reset idle keep-alive
        c._sock.shutdown(__import__("socket").SHUT_RDWR)
        c._sock.close()
        assert c.ping(), "reconnect-and-retry did not heal the connection"
        assert c.generate(p, 3).tolist() == got.tolist()
        # stream path heals the same way
        c._sock.close()
        deltas = list(c.generate_stream(p, 3))
        assert [t for d in deltas for t in d] == got.tolist()
    finally:
        c.close()
        fe.stop()
    # frontend really gone: the single retry must fail loudly
    c2_error = None
    try:
        c2 = ServeClient(addr, b"s" * 16, timeout=1.0)
    except (ConnectionError, OSError):
        c2 = None      # listener already down: constructor refuses
    if c2 is not None:
        try:
            c2.ping()
        except (ConnectionError, OSError, EOFError) as e:
            c2_error = e
        finally:
            c2.close()
        assert c2_error is not None, "dead frontend went unnoticed"
    s.stop()


# ------------------------------------------------------ integration

def _oracle(prompt, n, seed=0):
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import greedy_generate
    from tests.cluster_funcs import serving_tiny_gpt_builder

    cfg, params = serving_tiny_gpt_builder({"seed": seed})
    out = greedy_generate(cfg, params,
                          jnp.asarray(prompt, jnp.int32)[None, :], n)
    return np.asarray(out)[0, len(prompt):].tolist()


def _requests(rng, n, vocab=83, tmin=3, tmax=9, bmin=4, bmax=12):
    return [(rng.integers(0, vocab, (int(rng.integers(tmin, tmax)),))
             .astype(np.int32), int(rng.integers(bmin, bmax)))
            for _ in range(n)]


def _run_serving(tmp_path, worker_env, num_replicas=2, **kw):
    from tests.cluster_funcs import serving_tiny_gpt_builder

    from tensorflowonspark_tpu.serving import ServingCluster

    kw.setdefault("max_batch", 2)
    kw.setdefault("reservation_timeout", 120)
    return ServingCluster.run(
        serving_tiny_gpt_builder, num_replicas,
        worker_env=worker_env, working_dir=str(tmp_path), **kw)


@pytest.mark.integration
def test_serving_cluster_end_to_end(tmp_path, worker_env):
    """Acceptance: N concurrent clients against 2 replicas under
    staggered admission — every request greedy-exact vs its solo oracle,
    both replicas served traffic, streaming deltas concat exactly."""
    serving = _run_serving(tmp_path, worker_env)
    try:
        rng = np.random.default_rng(0)
        reqs = _requests(rng, 12)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 4):   # 4-way stagger
                        p, n = reqs[i]
                        results[i] = c.generate(p, n).tolist()
                        time.sleep(0.01 * cid)
            except Exception as e:                        # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors, errors
        assert len(results) == len(reqs)
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"

        # streaming: delta concat equals the oracle too
        with serving.client() as c:
            p, n = reqs[0]
            deltas = list(c.generate_stream(p, n))
            assert [t for d in deltas for t in d] == _oracle(p, n)
            assert len(deltas) > 1, "no incremental streaming happened"
            stats = c.stats()
        assert stats["completed"] == len(reqs) + 1
        assert stats["shed"] == stats["failed"] == 0
        assert all(r["served"] > 0 for r in stats["replicas"].values()), \
            f"routing starved a replica: {stats['replicas']}"
        assert stats["e2e"]["p99_secs"] is not None
    finally:
        serving.shutdown(timeout=120)


@pytest.mark.integration
@pytest.mark.parametrize("placed", [True, False], ids=["env", "default"])
def test_replica_compile_caches_have_one_place(tmp_path, worker_env,
                                               monkeypatch, placed):
    """A serve_replica boot keeps XLA's persistent cache AND the AOT
    cache where JAX_COMPILATION_CACHE_DIR says; unset, at the one fixed
    in-checkout path — never under the run's (moving) working_dir."""
    from tests.cluster_funcs import serving_cache_probe_builder

    from tensorflowonspark_tpu import util
    from tensorflowonspark_tpu.serving import ServingCluster

    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "placed"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = util.compilation_cache_dir()
    reports = set()
    for name in ("run_a", "run_b"):
        wd = tmp_path / name
        wd.mkdir()
        report = wd / "cache_report"
        serving = ServingCluster.run(
            serving_cache_probe_builder, 1, max_batch=2,
            replica_args={"cache_report": str(report)},
            worker_env=worker_env, working_dir=str(wd),
            reservation_timeout=120)
        serving.shutdown(timeout=120)
        reports.add(report.read_text())
        assert not (wd / "jax_cache").exists()
        assert not (wd / "jax_cache_aot").exists()
    assert reports == {f"{want}\n{os.path.join(want, 'aot')}"}
    assert str(tmp_path / "run_a") not in want


@pytest.mark.integration
def test_serving_replica_kill_requeues_and_stays_exact(tmp_path, worker_env):
    """Chaos: SIGKILL replica 1 mid-decode (TFOS_CHAOS at_step trigger on
    the serving loop's report_step).  Every accepted request must still
    complete with oracle-exact tokens — in-flight work on the dead
    replica is re-queued to the survivor — and the death must be
    recorded (requeued>0 or the dead replica visible in metrics) with
    zero failed requests."""
    env = dict(worker_env, TFOS_CHAOS="kill node=1 at_step=4")
    serving = _run_serving(tmp_path, env)
    try:
        rng = np.random.default_rng(1)
        reqs = _requests(rng, 8, bmin=10, bmax=16)   # long enough to span
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 2):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=120).tolist()
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"
        m = serving.metrics()
        assert m["completed"] == len(reqs) and m["failed"] == 0, m
        assert serving.scheduler.dead_replicas() == {1}, \
            "chaos kill was not detected"
    finally:
        serving.shutdown(timeout=120)


@pytest.mark.integration
@pytest.mark.slow
def test_serving_kill_soak_under_sustained_load(tmp_path, worker_env):
    """Soak: sustained staggered traffic while a replica dies mid-run;
    every accepted request completes exactly, none lost."""
    env = dict(worker_env, TFOS_CHAOS="kill node=0 at_step=12")
    serving = _run_serving(tmp_path, env, max_batch=2)
    try:
        rng = np.random.default_rng(2)
        reqs = _requests(rng, 24, bmin=6, bmax=14)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 3):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=180).tolist()
                        time.sleep(0.05)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"
        m = serving.metrics()
        assert m["completed"] == len(reqs) and m["failed"] == 0, m
        assert serving.scheduler.dead_replicas() == {0}
        events = [e["kind"] for e in _serving_events(tmp_path)]
        assert "replica_dead" in events
    finally:
        serving.shutdown(timeout=180)


@pytest.mark.integration
def test_live_add_and_drain_retire_replica(tmp_path, worker_env):
    """Elastic membership end-to-end on real worker processes: grow a
    1-replica tier to 2 (reservation path re-opens, newcomer serves
    oracle-exact traffic), then drain-retire the founding replica — the
    departure is clean (no dead replicas, no worker error) and the tier
    keeps serving on the survivor through shutdown."""
    serving = _run_serving(tmp_path, worker_env, num_replicas=1)
    try:
        rng = np.random.default_rng(3)
        reqs = _requests(rng, 10, bmin=5, bmax=9)
        with serving.client() as c:
            p, n = reqs[0]
            assert c.generate(p, n).tolist() == _oracle(p, n)
        added = serving.add_replicas(1)
        assert added == [1]
        assert serving.scheduler.alive_replicas() == {0, 1}
        # concurrent traffic so least-outstanding routing uses both
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 3):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=120).tolist()
            except Exception as e:       # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"
        m = serving.metrics()
        assert m["replicas"][1]["served"] > 0, "newcomer got no traffic"
        # drain-based scale-down of the founder
        assert serving.retire_replica(0, drain_timeout=60)
        assert serving.scheduler.dead_replicas() == set()
        assert serving.scheduler.alive_replicas() == {1}
        with serving.client() as c:
            p, n = reqs[1]
            assert c.generate(p, n, timeout=120).tolist() == _oracle(p, n)
        m = serving.metrics()
        assert m["failed"] == 0
        kinds = [e["kind"] for e in _serving_events(tmp_path)]
        for kind in ("replica_added", "replica_draining", "replica_retired"):
            assert kind in kinds, (kind, kinds)
    finally:
        serving.shutdown(timeout=120)   # must not raise over the retiree


@pytest.mark.integration
def test_preempted_replica_drains_and_is_replaced(tmp_path, worker_env):
    """Acceptance: chaos ``replace node=1`` SIGTERMs replica 1 mid-
    decode.  Its PreemptionGuard latches, the tier sees the grace-window
    phase flip, drains it, and spawns a replacement — zero accepted
    requests lost, every stream oracle-exact, and shutdown classifies
    NO failure (the reclaim was membership flex, not a crash)."""
    env = dict(worker_env, TFOS_CHAOS="replace node=1 at_step=4")
    serving = _run_serving(tmp_path, env)
    try:
        rng = np.random.default_rng(4)
        reqs = _requests(rng, 8, bmin=8, bmax=14)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 2):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=180).tolist()
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(240)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"
        # the replacement replica registers live (executor id 2)
        deadline = time.monotonic() + 90
        while 2 not in serving.scheduler.alive_replicas() \
                and time.monotonic() < deadline:
            time.sleep(0.25)
        assert 2 in serving.scheduler.alive_replicas(), \
            "preempted replica was not replaced"
        m = serving.metrics()
        assert m["failed"] == 0 and m["completed"] == m["accepted"], m
        assert m["replicas"][1]["alive"] is False
        kinds = [e["kind"] for e in _serving_events(tmp_path)]
        assert "replica_added" in kinds
        assert "replica_draining" in kinds or "replica_dead" in kinds
        # the replacement serves traffic
        with serving.client() as c:
            p, n = reqs[0]
            assert c.generate(p, n, timeout=120).tolist() == _oracle(p, n)
    finally:
        serving.shutdown(timeout=180)   # a reclaim must not fail shutdown


@pytest.mark.integration
def test_warm_standby_promotes_on_replica_kill(tmp_path, worker_env):
    """Acceptance (the heal window, closed): a tier with a warm standby
    loses replica 1 to a chaos SIGKILL mid-decode.  The heal PROMOTES
    the standby — control message + peer weight clone from replica 0 —
    instead of cold-spawning: zero accepted requests lost, every stream
    oracle-exact across the failover, the promoted standby serves, the
    pool backfills, and the event log tells the warm story
    (heal_started → standby_promoted → standby_ready with heal_secs)."""
    env = dict(worker_env, TFOS_CHAOS="kill node=1 at_step=4")
    serving = _run_serving(tmp_path, env, num_replicas=2, warm_standbys=1)
    try:
        assert serving.wait_standbys(timeout=120), "standby never warmed"
        assert serving.standbys.stats() == {"standbys": 1, "ready": [2]}
        rng = np.random.default_rng(6)
        reqs = _requests(rng, 8, bmin=10, bmax=16)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 2):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=180).tolist()
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(240)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"
        # the standby (executor 2) was promoted into the scheduler
        deadline = time.monotonic() + 90
        while 2 not in serving.scheduler.alive_replicas() \
                and time.monotonic() < deadline:
            time.sleep(0.25)
        assert 2 in serving.scheduler.alive_replicas(), \
            "standby was never promoted"
        assert serving.scheduler.dead_replicas() == {1}
        m = serving.metrics()
        assert m["failed"] == 0 and m["completed"] == m["accepted"], m
        assert m["standby"]["promotions"] == {"failure": 1}
        # the promoted replica serves traffic (probe until routed there)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if serving.metrics()["replicas"][2]["served"] > 0:
                break
            ts = [threading.Thread(target=lambda: _probe(serving, reqs[0]))
                  for _ in range(3)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(60)
        assert serving.metrics()["replicas"][2]["served"] > 0, \
            "promoted standby never served"
        # the pool backfilled a fresh standby (executor 3)
        deadline = time.monotonic() + 90
        while serving.standbys.stats()["standbys"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.25)
        assert serving.standbys.stats()["ready"] == [3]
        kinds = [e["kind"] for e in _serving_events(tmp_path)]
        for kind in ("heal_started", "standby_promoted", "standby_ready",
                     "standby_booted", "replica_replaced"):
            assert kind in kinds, (kind, kinds)
        ready = [e for e in _serving_events(tmp_path)
                 if e["kind"] == "standby_ready"]
        assert ready and ready[0]["heal_secs"] > 0
        assert m["standby"]["heal"]["count"] >= 1
    finally:
        serving.shutdown(timeout=180)


def _probe(serving, req):
    with serving.client() as c:
        p, n = req
        assert c.generate(p, n, timeout=60).tolist() == _oracle(p, n)


@pytest.mark.integration
def test_standby_death_backfills_and_never_registers_live(tmp_path,
                                                          worker_env):
    """Chaos kills the STANDBY itself (node 1, time-triggered — a
    standby reports no steps): the pool shrinks, backfills a fresh
    standby, the scheduler never registered either, and the tier keeps
    serving oracle-exact through shutdown (the corpse is tolerated)."""
    env = dict(worker_env, TFOS_CHAOS="kill node=1 after_secs=2")
    serving = _run_serving(tmp_path, env, num_replicas=1, warm_standbys=1)
    try:
        # wait for the kill to land and the backfill to replace it
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            stats = serving.standbys.stats()
            if stats["ready"] and stats["ready"][0] != 1:
                break
            time.sleep(0.25)
        assert serving.standbys.stats()["ready"] == [2], \
            serving.standbys.stats()
        assert 1 in serving.standbys.dead
        assert serving.scheduler.alive_replicas() == {0}
        assert serving.scheduler.dead_replicas() == set()
        assert 1 not in serving.scheduler.replicas
        rng = np.random.default_rng(7)
        p, n = _requests(rng, 1)[0]
        with serving.client() as c:
            assert c.generate(p, n, timeout=120).tolist() == _oracle(p, n)
        kinds = [e["kind"] for e in _serving_events(tmp_path)]
        assert "standby_dead" in kinds and kinds.count("standby_booted") >= 2
    finally:
        serving.shutdown(timeout=120)   # must tolerate the standby corpse


@pytest.mark.integration
@pytest.mark.slow
def test_autoscaler_ramp_soak_with_replace_chaos(tmp_path, worker_env):
    """Soak (the satellite's ramp scenario as a test): a 1-replica tier
    under a burst 16-deep queue scales itself up; chaos ``replace``
    reclaims the scaled-up replica mid-run (drain + replacement); after
    the burst the autoscaler drains back down.  Zero accepted requests
    lost, every stream oracle-exact."""
    env = dict(worker_env, TFOS_CHAOS="replace node=1 at_step=6")
    serving = _run_serving(
        tmp_path, env, num_replicas=1, max_queue_depth=64,
        autoscale=dict(min_replicas=1, max_replicas=3, interval=0.5,
                       up_queue_per_replica=2.0, up_consecutive=2,
                       up_cooldown=4.0, down_outstanding_per_replica=1.0,
                       down_consecutive=6, down_cooldown=6.0))
    try:
        rng = np.random.default_rng(5)
        reqs = _requests(rng, 16, bmin=6, bmax=12)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(i):
            try:
                with serving.client() as c:
                    p, n = reqs[i]
                    results[i] = c.generate(p, n, timeout=300).tolist()
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:     # burst: the queue piles onto one replica
            t.start()
        for t in threads:
            t.join(360)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"
        # idle tail: let the autoscaler shrink back toward min_replicas
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if (serving.autoscaler.scale_downs >= 1
                    and serving.autoscaler.scale_ups >= 1):
                break
            time.sleep(0.5)
        m = serving.metrics()
        assert m["failed"] == 0 and m["completed"] == m["accepted"], m
        assert serving.autoscaler.scale_ups >= 1, "no scale-up under burst"
        assert serving.autoscaler.scale_downs >= 1, "no drain scale-down"
        kinds = [e["kind"] for e in _serving_events(tmp_path)]
        assert "scale_up" in kinds and "scale_down" in kinds
        assert "replica_retired" in kinds
    finally:
        serving.shutdown(timeout=300)


# --------------------------------------------- sharded gang integration

def _sharded_oracle(prompt, n, seed=0):
    import jax.numpy as jnp

    from tests.cluster_funcs import serving_sharded_gpt_builder

    from tensorflowonspark_tpu.models import greedy_generate

    cfg, params = serving_sharded_gpt_builder({"seed": seed})
    out = greedy_generate(cfg, params,
                          jnp.asarray(prompt, jnp.int32)[None, :], n)
    return np.asarray(out)[0, len(prompt):].tolist()


def _run_sharded_serving(tmp_path, num_replicas=1, chaos=None, **kw):
    from tests.cluster_funcs import serving_sharded_gpt_builder

    from tensorflowonspark_tpu.serving import ServingCluster

    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    if chaos:
        env["TFOS_CHAOS"] = chaos
    kw.setdefault("max_batch", 2)
    kw.setdefault("reservation_timeout", 120)
    return ServingCluster.run(
        serving_sharded_gpt_builder, num_replicas, mesh={"tp": 2},
        worker_env=env, working_dir=str(tmp_path), **kw)


@pytest.mark.integration
def test_sharded_gang_serves_oracle_exact(tmp_path):
    """Acceptance: one tp=2 gang (leader + barrier member over real
    worker processes) serves concurrent streams greedy-exact vs the solo
    oracle, registers as ONE weighted endpoint, and shuts down clean."""
    serving = _run_sharded_serving(tmp_path)
    try:
        m = serving.scheduler.metrics()
        assert m["gang_size"] == 2 and m["capacity_devices"] == 2
        assert m["replicas"][0]["members"] == [1]
        rng = np.random.default_rng(3)
        reqs = _requests(rng, 6, vocab=64)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 2):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=180).tolist()
            except Exception as e:                      # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(240)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _sharded_oracle(p, n), \
                f"request {i} diverged from the solo oracle"
        m = serving.metrics()
        assert m["failed"] == 0 and m["completed"] == len(reqs)
        assert serving.scheduler.dead_replicas() == set()
    finally:
        serving.shutdown(timeout=180)


@pytest.mark.integration
def test_sharded_gang_member_kill_fails_over_exact(tmp_path):
    """Chaos: SIGKILL the NON-LEADER shard of gang 0 mid-stream (member
    executor 1, at_step on ITS barrier-mirrored step counter).  The
    whole gang must classify dead, its in-flight requests re-queue ONCE
    to the surviving gang, every accepted request completes oracle-exact
    (single-requeue skip-dedup), and shutdown tolerates the corpses."""
    serving = _run_sharded_serving(tmp_path, num_replicas=2,
                                   chaos="kill node=1 at_step=4")
    try:
        rng = np.random.default_rng(5)
        reqs = _requests(rng, 8, vocab=64, bmin=10, bmax=16)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 2):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=240).tolist()
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _sharded_oracle(p, n), \
                f"request {i} diverged across the gang failover"
        m = serving.metrics()
        assert m["failed"] == 0 and m["completed"] == len(reqs), m
        assert m["requeued"] >= 1, "the chaos kill landed nowhere"
        # ONE shard died; the WHOLE gang is the failure domain
        assert serving.scheduler.dead_replicas() == {0, 1}, \
            serving.scheduler.dead_replicas()
        assert m["replicas"][2]["alive"]
        events = _serving_events(tmp_path)
        dead = [e for e in events if e["kind"] == "replica_dead"]
        assert len(dead) == 1 and sorted(dead[0]["shards"]) == [0, 1], \
            "gang death must be reported exactly once, naming its shards"
    finally:
        serving.shutdown(timeout=180)


def _serving_events(tmp_path):
    import os

    from tensorflowonspark_tpu.observability import EventLog

    path = os.path.join(str(tmp_path), "serving_events.jsonl")
    return EventLog.read(path) if os.path.exists(path) else []


# --------------------------------------- disaggregated prefill/decode

class _DisaggWorld(_FakeWorld):
    """Fake specialized pools speaking the handoff protocol: prefill
    fakes answer a gen with the FIRST token + a ``handoff`` session;
    decode fakes answer an ``adopt`` by streaming the remainder.  The
    deterministic ``_fake_tokens`` stream spans the boundary, so replay
    exactness is assertable exactly like the unified fakes."""

    def __init__(self, n_prefill, n_decode, token_delay=0.0,
                 prefill_delay=0.0):
        self.roles = {i: ("prefill" if i < n_prefill else "decode")
                      for i in range(n_prefill + n_decode)}
        self.prefill_delay = prefill_delay
        super().__init__(n_prefill + n_decode, token_delay=token_delay)

    def _run(self, i):
        role = self.roles.get(i, "decode")   # late adds join decode
        while i not in self._dead:
            try:
                item = self.inq[i].get(timeout=0.02)
            except _queue.Empty:
                continue
            rid = item["rid"]
            if role == "prefill":
                p, n = item["prompt"], item["max_new_tokens"]
                toks = _fake_tokens(p, n)
                if self.prefill_delay:
                    time.sleep(self.prefill_delay)
                if i in self._dead:
                    return                   # died mid-prefill
                self.outq[i].put({"rid": rid, "event": "tok",
                                  "tokens": [toks[0]], "load": 0,
                                  "role": "prefill"})
                if n == 1:
                    self.outq[i].put({"rid": rid, "event": "done",
                                      "load": 0, "role": "prefill"})
                    continue
                self.outq[i].put(
                    {"rid": rid, "event": "handoff", "role": "prefill",
                     "load": 0, "free_pages": 7,
                     "session": {"prompt": p, "tokens": [toks[0]],
                                 "remaining": n - 1, "pages": 2,
                                 "kv": []}})
            else:
                sess = item["session"]
                p, g = sess["prompt"], len(sess["tokens"])
                toks = _fake_tokens(p, g + sess["remaining"])[g:]
                for tok in toks:
                    if i in self._dead:
                        return               # died post-handoff
                    if self.token_delay:
                        time.sleep(self.token_delay)
                    self.outq[i].put({"rid": rid, "event": "tok",
                                      "tokens": [tok], "load": 1,
                                      "role": "decode"})
                self.outq[i].put({"rid": rid, "event": "done", "load": 0,
                                  "role": "decode"})


def _disagg_scheduler(world, **kw):
    kw.setdefault("roles", dict(world.roles))
    return _scheduler(world, **kw)


def test_disagg_routes_prompt_to_prefill_then_session_to_decode():
    world = _DisaggWorld(1, 1)
    s = _disagg_scheduler(world).start()
    try:
        prompts = [np.arange(1, 4 + i, dtype=np.int32) for i in range(5)]
        reqs = [s.submit(p, 6) for p in prompts]
        for req, p in zip(reqs, prompts):
            toks, err = _collect(req)
            assert err is None and toks == _fake_tokens(p, 6)
        m = s.metrics()
        assert m["handoffs"] == 5 and m["completed"] == 5
        assert m["queued_handoffs"] == 0
        assert m["replicas"][0]["role"] == "prefill"
        assert m["replicas"][1]["role"] == "decode"
        # every DONE came from the decode gang; the prefill gang only
        # ever prefilled (its served count tracks done events)
        assert m["replicas"][1]["served"] == 5
        assert m["replicas"][0]["served"] == 0
        # the handoff message's free_pages piggyback reached the router
        assert m["replicas"][0]["free_pages"] == 7
    finally:
        s.stop()


def test_submit_rejects_bare_prompt_on_decode_only_tier():
    """The routing safety fix: a tier whose prefill pool is gone (or was
    never configured) rejects prompts TYPED at admission instead of
    queueing them on a decode-only gang forever."""
    world = _DisaggWorld(1, 1)
    s = _disagg_scheduler(world, roles={0: "decode", 1: "decode"}).start()
    try:
        with pytest.raises(RequestRejected) as ei:
            s.submit(np.asarray([1, 2], np.int32), 4)
        assert ei.value.reason == "role_mismatch"
        assert "refusing to queue a bare prompt on a decode-only gang" \
            in str(ei.value)
    finally:
        s.stop()
    # the same rejection when the prefill pool DIES out from under a
    # live tier
    world = _DisaggWorld(1, 1, prefill_delay=0.05)
    s = _disagg_scheduler(world).start()
    try:
        world.kill(0)
        deadline = time.monotonic() + 5
        while 0 not in s.dead_replicas() and time.monotonic() < deadline:
            time.sleep(0.02)
        with pytest.raises(RequestRejected) as ei:
            s.submit(np.asarray([1], np.int32), 3)
        assert ei.value.reason == "role_mismatch"
    finally:
        s.stop()


def test_disagg_prefill_death_mid_prefill_requeues_once_exact():
    world = _DisaggWorld(2, 1, prefill_delay=0.4)
    s = _disagg_scheduler(world, slots_per_replica=1, overcommit=1).start()
    try:
        p = np.asarray([2, 7], np.int32)
        req = s.submit(p, 6)
        deadline = time.monotonic() + 5
        while req.replica is None and time.monotonic() < deadline:
            time.sleep(0.005)
        victim = req.replica
        assert victim in (0, 1), "prompt routed off the prefill pool"
        world.kill(victim)
        toks, err = _collect(req, timeout=15)
        assert err is None and toks == _fake_tokens(p, 6)
        m = s.metrics()
        assert m["requeued"] == 1 and m["completed"] == 1
        assert s.dead_replicas() == {victim}
    finally:
        s.stop()


def test_disagg_decode_death_post_handoff_replays_full_pipeline():
    """A decode gang dying POST-handoff replays the request through the
    whole prefill→handoff→adopt pipeline once: the client stream stays
    exact (skip-dedup spans the boundary) and the request hands off
    TWICE."""
    world = _DisaggWorld(1, 2, token_delay=0.05)
    s = _disagg_scheduler(world, slots_per_replica=1, overcommit=1).start()
    try:
        p = np.asarray([3, 5, 8], np.int32)
        req = s.submit(p, 10)
        # wait until the DECODE side is streaming (>= 2 tokens: first
        # came from prefill, the rest from the adopted session)
        deadline = time.monotonic() + 10
        while len(req.tokens) < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        victim = req.replica
        assert world.roles[victim] == "decode", "request not in decode"
        world.kill(victim)
        toks, err = _collect(req, timeout=15)
        assert err is None and toks == _fake_tokens(p, 10), \
            "post-handoff failover stream not exact"
        m = s.metrics()
        assert m["requeued"] == 1 and m["completed"] == 1
        assert m["handoffs"] == 2, "the replay must re-handoff"
    finally:
        s.stop()


def test_disagg_requeue_once_budget_spans_the_boundary():
    """One failover attempt TOTAL across the pipeline: the adopt hop
    never charges the budget (a normal request = 1 attempt), and the
    second decode-side death fails typed."""
    world = _DisaggWorld(1, 2, token_delay=0.08)
    s = _disagg_scheduler(world, slots_per_replica=1, overcommit=1).start()
    try:
        p = np.asarray([9, 1], np.int32)
        req = s.submit(p, 12)
        deadline = time.monotonic() + 10
        while len(req.tokens) < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert req.attempts == 1, \
            "the adopt dispatch must not charge the failover budget"
        world.kill(req.replica)          # first decode death: replays
        deadline = time.monotonic() + 10
        while s.metrics()["requeued"] < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        # wait for the replay to reach the surviving decode gang
        deadline = time.monotonic() + 10
        while (req.replica is None
               or world.roles.get(req.replica) != "decode"
               or req.replica in world._dead) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        world.kill(req.replica)          # second death: budget exhausted
        toks, err = _collect(req, timeout=15)
        assert err is not None and err[1] == "replica_failed"
        assert s.metrics()["failed"] == 1
    finally:
        s.stop()


def test_trace_id_survives_handoff_and_post_handoff_requeue(tmp_path):
    """Satellite: the stitched timeline gains the handoff span — one
    trace id covers admission → prefill route → handoff (pages/bytes) →
    adopt route → requeue → re-prefill → re-handoff → done."""
    from tensorflowonspark_tpu import tracing
    from tensorflowonspark_tpu.observability import EventLog

    world = _DisaggWorld(1, 2, token_delay=0.05)
    log = EventLog(str(tmp_path / "serving_events.jsonl"))
    s = _disagg_scheduler(world, slots_per_replica=1, overcommit=1,
                          event_log=log).start()
    try:
        p = np.asarray([4, 4], np.int32)
        trace = tracing.new_trace_id()
        req = s.submit(p, 10, trace=trace)
        deadline = time.monotonic() + 10
        while len(req.tokens) < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        world.kill(req.replica)          # decode side, post-handoff
        toks, err = _collect(req, timeout=15)
        assert err is None and toks == _fake_tokens(p, 10)
    finally:
        s.stop()
        log.close()

    timeline = tracing.stitch_trace(str(tmp_path), trace)
    kinds = [r["kind"] for r in timeline if not r.get("_context")]
    assert kinds[0] == "request_admitted" and kinds[-1] == "request_done"
    handoffs = [r for r in timeline if r["kind"] == "request_handoff"]
    assert len(handoffs) == 2, "replay must re-handoff under ONE trace"
    assert all(h["trace"] == trace for h in handoffs)
    assert handoffs[0]["from_replica"] == 0
    assert handoffs[0]["pages"] == 2 and "bytes" in handoffs[0]
    adopt_routes = [r for r in timeline
                    if r["kind"] == "request_handoff_routed"]
    assert len(adopt_routes) == 2
    assert all(world.roles[r["replica"]] == "decode"
               for r in adopt_routes)
    (requeued,) = [r for r in timeline if r["kind"] == "request_requeued"]
    assert requeued["trace"] == trace
    assert all(r["trace"] == trace for r in timeline
               if not r.get("_context"))
    # the CLI-facing formatter renders the handoff span
    assert "request_handoff" in tracing.format_timeline(timeline)


class _DisaggPoolWorld(_DisaggWorld):
    """``_DisaggWorld`` + the cluster surface StandbyPool/ServingCluster
    need, with driver control messages RECORDED — the promote message
    must carry the target pool's role."""

    def __init__(self, n_prefill, n_decode, **kw):
        super().__init__(n_prefill, n_decode, **kw)
        self.control: list = []

    def add_workers(self, n, map_fun=None, tf_args=None, timeout=None):
        return [self.add_replica() for _ in range(n)]

    def _client_for(self, eid):
        world = self

        class _Ctl:
            def put(self, qname, item, timeout=None):
                world.control.append((eid, item))

        return _Ctl()

    def retire_worker(self, eid):
        pass


def _disagg_standby_tier(world, scheduler, pool_size, disagg):
    from tensorflowonspark_tpu.serving import ServingCluster, StandbyPool

    tier = ServingCluster(world, scheduler, monitor=None, frontend=None,
                          address=("127.0.0.1", 0))
    tier.disagg = dict(disagg)
    scheduler.on_replica_ready = tier._on_standby_ready
    tier.standbys = StandbyPool(tier, pool_size)
    tier.standbys.fill()
    return tier


def test_promote_with_role_joins_decode_pool_and_serves():
    """Satellite (ROADMAP item 2 leftover): a role-less warm standby is
    promoted INTO a killed decode gang's pool — the promote control
    message carries ``role="decode"``, the scheduler registers the
    newcomer into the decode pool, per-role accounting records it, and
    the healed pipeline serves prefill→handoff→adopt exact."""
    from tensorflowonspark_tpu.health import ClusterFailure

    world = _DisaggPoolWorld(1, 1)
    s = _disagg_scheduler(world).start()
    tier = _disagg_standby_tier(world, s, pool_size=1,
                                disagg={"prefill": 1, "decode": 1})
    try:
        assert tier.standbys.stats() == {"standbys": 1, "ready": [2]}
        world.kill(1)                                  # the decode gang
        s.on_cluster_failure(ClusterFailure("crash", "crash: worker 1",
                                            (1,)))
        tier._spawn_replacement(1, source="failure",
                                promote_source="failure")
        deadline = time.monotonic() + 10
        while (2 not in s.alive_replicas() or not world.control) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert 2 in s.alive_replicas(), "standby was never promoted"
        assert world.control, "promote control message never sent"
        assert s.replica_role(2) == "decode", \
            "the newcomer must join the DEAD gang's pool"
        [(ctl_eid, promote)] = [(e, m) for e, m in world.control
                                if m.get("op") == "standby"]
        assert ctl_eid == 2
        assert promote["op"] == "standby" and promote["event"] == "promote"
        assert promote["role"] == "decode", \
            "the promote message must carry the target pool's role"
        # a decode-pool promotion also triggers a prefix-page donation
        # request to a prefill gang (background thread — wait for it)
        deadline = time.monotonic() + 5
        while not any(m.get("op") == "prefix" for _, m in world.control) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        donations = [(e, m) for e, m in world.control
                     if m.get("op") == "prefix"]
        assert donations and donations[0][0] == 0, \
            "the donation export must go to the prefill gang"
        assert donations[0][1]["event"] == "export"
        # the healed pipeline spans the boundary: prompt -> prefill 0 ->
        # handoff -> adopted by the promoted decode gang 2
        for k in range(3):
            p = np.asarray([5 + k, 2], np.int32)
            toks, err = _collect(s.submit(p, 6))
            assert err is None and toks == _fake_tokens(p, 6)
        m = s.metrics()
        assert m["handoffs"] >= 3
        assert m["replicas"][2]["role"] == "decode"
        # per-role pool accounting
        assert tier.metrics()["standby"]["promotions"] == {
            "failure": 1, "role:decode": 1}
    finally:
        tier.standbys.stop()
        s.stop()


def test_expectation_holds_handoff_queue_through_the_heal_window():
    """When the dead decode gang was its pool's LAST, the requeued
    handoffs must WAIT for the in-flight replacement (expect_replica)
    instead of shedding as no_replica — and still fail typed once the
    heal gives up (expect_done with no replacement registered)."""
    world = _DisaggWorld(1, 1, token_delay=0.05)
    s = _disagg_scheduler(world).start()
    try:
        p = np.asarray([3, 1], np.int32)
        req = s.submit(p, 8)
        deadline = time.monotonic() + 10
        while len(req.tokens) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        s.expect_replica("decode")       # the heal announces itself
        world.kill(1)                    # ...then the only decode dies
        deadline = time.monotonic() + 10
        while s.metrics()["requeued"] < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.3)                  # dispatch must NOT shed it
        assert not req.finished, \
            "a held pool's work was shed during the heal window"
        info = world.add_replica()       # the replacement lands
        s.add_replica(info, role="decode")
        s.expect_done("decode")
        toks, err = _collect(req, timeout=15)
        assert err is None and toks == _fake_tokens(p, 8)
        # a SECOND death with no expectation restores the typed shed
        req2 = s.submit(p, 6)
        deadline = time.monotonic() + 10
        while req2.replica is None and time.monotonic() < deadline:
            time.sleep(0.005)
        world.kill(2)
        toks, err = _collect(req2, timeout=15)
        assert err is not None and err[1] == "no_replica", err
    finally:
        s.stop()


def test_promote_role_mismatch_skips_warm_pool_never_crashes():
    """A mismatched promote call (role on a unified tier, no role on a
    disagg tier) SKIPS the warm pool — returning None so the heal thread
    falls back to the cold path's explicit error — and never consumes a
    standby."""
    world = _PoolWorld(1)
    s = _scheduler(world).start()
    tier = _standby_tier(world, s, pool_size=1)
    try:
        assert tier.promote_standby("failure", role="decode") is None
        assert tier.standbys.stats()["standbys"] == 1, \
            "a skipped promotion must not consume the standby"
    finally:
        tier.standbys.stop()
        s.stop()
    world2 = _DisaggPoolWorld(1, 1)
    s2 = _disagg_scheduler(world2).start()
    tier2 = _disagg_standby_tier(world2, s2, pool_size=1,
                                 disagg={"prefill": 1, "decode": 1})
    try:
        assert tier2.promote_standby("scale_up") is None
        assert tier2.standbys.stats()["standbys"] == 1
    finally:
        tier2.standbys.stop()
        s2.stop()


class _FakeDisaggServing(_FakeServing):
    """Two-pool facade: per-role replica sets + both backlog queues, so
    the per-pool autoscalers can be driven deterministically."""

    def __init__(self, n_prefill=1, n_decode=1):
        super().__init__(replicas=n_prefill + n_decode)
        fake = self
        self.by_role = {"prefill": n_prefill, "decode": n_decode}
        self.queued_handoffs = 0
        self.outstanding_by_role = {"prefill": 0, "decode": 0}
        self.added_roles = []

        class _Sched:
            def metrics(self):
                reps = {}
                eid = 0
                for role in ("prefill", "decode"):
                    for _ in range(fake.by_role[role]):
                        reps[eid] = {
                            "alive": True, "draining": False,
                            "role": role,
                            "outstanding":
                                fake.outstanding_by_role[role]
                                // max(1, fake.by_role[role])}
                        eid += 1
                return {"queued": fake.queued,
                        "queued_handoffs": fake.queued_handoffs,
                        "ttft": {"p95_secs": None},
                        "replicas": reps}

            def emit_event(self, kind, **fields):
                fake.events.append((kind, fields))

        self.scheduler = _Sched()

    def scale_up(self, n, role=None):
        self.by_role[role] += n
        self.added_roles.extend([role] * n)
        return list(range(n))


def test_autoscaler_per_pool_signals_and_independence():
    """Per-pool controllers read DIFFERENT backlogs: prompt-queue
    pressure moves only the prefill pool, handoff-queue pressure only
    the decode pool — each within its own bounds."""
    from tensorflowonspark_tpu.serving import Autoscaler, AutoscalerConfig

    fake = _FakeDisaggServing(n_prefill=1, n_decode=1)
    pre = Autoscaler(fake, AutoscalerConfig(
        role="prefill", min_replicas=1, max_replicas=3,
        up_queue_per_replica=2.0, up_consecutive=1, up_cooldown=0.0))
    dec = Autoscaler(fake, AutoscalerConfig(
        role="decode", min_replicas=1, max_replicas=3,
        up_queue_per_replica=2.0, up_consecutive=1, up_cooldown=0.0))

    # prompt backlog only: prefill scales, decode holds
    fake.queued, fake.queued_handoffs = 9, 0
    sp, sd = pre.sample(), dec.sample()
    assert sp["alive"] == 1 and sd["alive"] == 1, "role filter leaked"
    assert sp["queued"] == 9 and sd["queued"] == 0
    assert pre.decide(sp, now=1.0)[0] == "up"
    assert dec.decide(sd, now=1.0)[0] == "hold"

    # handoff backlog only: decode scales, prefill holds
    fake.queued, fake.queued_handoffs = 0, 9
    fake.outstanding_by_role = {"prefill": 5, "decode": 5}  # not idle
    sp, sd = pre.sample(), dec.sample()
    assert sp["queued"] == 0 and sd["queued"] == 9
    assert pre.decide(sp, now=2.0)[0] == "hold"
    assert dec.decide(sd, now=2.0)[0] == "up"
    dec._scale_up(sd, "test")
    assert fake.added_roles == ["decode"], \
        "the decode controller must grow the decode pool"
    ups = [f for k, f in fake.events if k == "scale_up"]
    assert ups and ups[-1]["role"] == "decode"

    # per-pool victim selection: the decode controller's scale-down
    # victim must be a decode gang even when a prefill gang is idler
    fake.queued = fake.queued_handoffs = 0
    fake.outstanding_by_role = {"prefill": 0, "decode": 4}
    m = fake.scheduler.metrics()
    victim = dec._victim(m)
    assert victim is not None \
        and m["replicas"][victim[0]]["role"] == "decode"


@pytest.mark.integration
def test_disagg_cluster_end_to_end(tmp_path, worker_env):
    """Acceptance: a real 1-prefill + 1-decode tier serves concurrent
    clients oracle-exact, every request moves as a KV-page handoff, and
    the specialization holds — zero prefill dispatches on the decode
    gang, zero decode dispatches on the prefill gang."""
    serving = _run_serving(
        tmp_path, worker_env, num_replicas=2,
        disagg={"prefill": 1, "decode": 1},
        batcher_kwargs={"kv_page_tokens": 8})
    try:
        rng = np.random.default_rng(2)
        reqs = _requests(rng, 8)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 2):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=120).tolist()
            except Exception as e:                    # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"
        m = serving.metrics()
        assert m["handoffs"] >= len(reqs) and m["failed"] == 0
        assert m["replicas"][0]["role"] == "prefill"
        assert m["replicas"][1]["role"] == "decode"
        # heartbeat-carried engine counters prove the specialization
        time.sleep(2.5)
        nodes = serving.metrics()["nodes"]

        def _counter(eid, name):
            fam = (nodes.get(eid, {}).get("metrics") or {}).get(name)
            return sum(v for _, v in (fam or {}).get("samples", ()))

        assert _counter(1, "tfos_replica_prefill_dispatches_total") == 0, \
            "the decode gang ran a prefill"
        assert _counter(0, "tfos_replica_decode_dispatches_total") == 0, \
            "the prefill gang ran a decode step"
        assert _counter(0, "tfos_replica_sessions_total") >= len(reqs)
    finally:
        serving.shutdown(timeout=120)


@pytest.mark.integration
def test_disagg_prefill_gang_kill_mid_prefill_stays_exact(tmp_path,
                                                          worker_env):
    """Chaos, prefill side: SIGKILL prefill gang 0 mid-run; its
    in-flight prompts requeue ONCE to the surviving prefill gang and
    every accepted request completes oracle-exact."""
    env = dict(worker_env, TFOS_CHAOS="kill node=0 at_step=1")
    serving = _run_serving(
        tmp_path, env, num_replicas=3,
        disagg={"prefill": 2, "decode": 1},
        batcher_kwargs={"kv_page_tokens": 8})
    try:
        rng = np.random.default_rng(3)
        reqs = _requests(rng, 8, tmin=6, tmax=12, bmin=8, bmax=14)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 2):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=120).tolist()
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"
        m = serving.metrics()
        assert m["failed"] == 0 and m["requeued"] >= 1, m
        assert serving.scheduler.dead_replicas() == {0}
    finally:
        serving.shutdown(timeout=120)


@pytest.mark.integration
def test_disagg_decode_gang_kill_post_handoff_stays_exact(tmp_path,
                                                          worker_env):
    """Chaos, decode side: SIGKILL decode gang 1 while it streams
    adopted sessions; the stranded requests replay through the FULL
    prefill→handoff→adopt pipeline onto the surviving decode gang,
    skip-dedup keeping every client stream exact."""
    env = dict(worker_env, TFOS_CHAOS="kill node=1 at_step=3")
    serving = _run_serving(
        tmp_path, env, num_replicas=3,
        disagg={"prefill": 1, "decode": 2},
        batcher_kwargs={"kv_page_tokens": 8})
    try:
        rng = np.random.default_rng(4)
        reqs = _requests(rng, 8, bmin=10, bmax=16)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 2):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=120).tolist()
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"
        m = serving.metrics()
        assert m["failed"] == 0 and m["requeued"] >= 1, m
        assert serving.scheduler.dead_replicas() == {1}
        # the replays re-handed-off: more handoffs than completions
        assert m["handoffs"] > m["completed"] - m["requeued"]
    finally:
        serving.shutdown(timeout=120)


@pytest.mark.integration
def test_disagg_standby_promotes_into_killed_decode_gang(tmp_path,
                                                         worker_env):
    """Satellite acceptance (disagg x warm_standbys): chaos SIGKILLs the
    only decode gang while it streams adopted sessions; the heal
    PROMOTES the role-less warm standby INTO the decode pool
    (promote-with-role: control message carries role="decode", the
    engine specializes via set_role, the scheduler registers it into the
    pool) — every accepted request completes oracle-exact across the
    heal and the per-role accounting tells the story."""
    env = dict(worker_env, TFOS_CHAOS="kill node=1 at_step=3")
    serving = _run_serving(
        tmp_path, env, num_replicas=2,
        disagg={"prefill": 1, "decode": 1},
        batcher_kwargs={"kv_page_tokens": 8},
        warm_standbys=1)
    try:
        assert serving.wait_standbys(timeout=180), "standby never warmed"
        assert serving.standbys.stats() == {"standbys": 1, "ready": [2]}
        rng = np.random.default_rng(8)
        reqs = _requests(rng, 8, bmin=10, bmax=16)
        results: dict[int, list] = {}
        errors: list = []

        def run_client(cid):
            try:
                with serving.client() as c:
                    for i in range(cid, len(reqs), 2):
                        p, n = reqs[i]
                        results[i] = c.generate(p, n, timeout=240).tolist()
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=run_client, args=(cid,))
                   for cid in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert not errors, errors
        for i, (p, n) in enumerate(reqs):
            assert results[i] == _oracle(p, n), f"request {i} diverged"
        # the standby joined the DEAD gang's pool
        deadline = time.monotonic() + 90
        while 2 not in serving.scheduler.alive_replicas() \
                and time.monotonic() < deadline:
            time.sleep(0.25)
        assert 2 in serving.scheduler.alive_replicas(), \
            "standby was never promoted"
        assert serving.scheduler.replica_role(2) == "decode"
        assert serving.scheduler.dead_replicas() == {1}
        m = serving.metrics()
        assert m["failed"] == 0 and m["completed"] == m["accepted"], m
        assert m["requeued"] >= 1, "the killed decode work must replay"
        assert m["standby"]["promotions"] == {"failure": 1,
                                              "role:decode": 1}
        assert m["replicas"][2]["role"] == "decode"
        promoted = [e for e in _serving_events(tmp_path)
                    if e["kind"] == "standby_promoted"]
        assert promoted and promoted[0]["role"] == "decode"
        replaced = [e for e in _serving_events(tmp_path)
                    if e["kind"] == "replica_replaced"]
        assert replaced and replaced[0]["mode"] == "warm" \
            and replaced[0]["role"] == "decode"
    finally:
        serving.shutdown(timeout=180)
