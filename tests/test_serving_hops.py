"""Hop clocks of the driver process (``observability.hop_clocks``;
docs/observability.md "Hop clocks"): a request's way from the frontend's
accept to the replica's intake and a token message's way from the replica's
flush to the frontend's send, as two counter families in the driver's
registry.  Scheduler, frontend and client are real; the replicas are the
in-process fakes of ``test_serving_cluster``, here stamping their messages
as ``serving/replica.py`` does.
"""

import queue
import time

import numpy as np
import pytest

from test_serving_cluster import _FakeWorld, _fake_tokens, _scheduler

from tensorflowonspark_tpu import metrics, observability as obs
from tensorflowonspark_tpu.serving import ServeClient, ServeFrontend

WAY_IN = ("accept", "pending", "dispatch", "seat")
STAMPED = ("dispatch", "seat", "fetch")     # hops that need a replica stamp


class _StampedWorld(_FakeWorld):
    """Fake replicas that stamp ``t_in`` (first message alone) and
    ``t_put``; ``put_skew`` shifts ``t_put`` (a replica whose clock runs
    ahead of the driver's)."""

    def __init__(self, n, put_skew=0.0):
        self.put_skew = put_skew
        super().__init__(n)

    def _run(self, i):
        while i not in self._dead:
            try:
                item = self.inq[i].get(timeout=0.02)
            except queue.Empty:
                continue
            t_in = time.time()
            toks = _fake_tokens(item["prompt"], item["max_new_tokens"])
            for k, tok in enumerate(toks):
                self.outq[i].put({
                    "rid": item["rid"], "event": "tok", "tokens": [tok],
                    "load": 1, **({"t_in": t_in} if k == 0 else {}),
                    "t_put": time.time() + self.put_skew})
            self.outq[i].put({"rid": item["rid"], "event": "done",
                              "load": 0,
                              "t_put": time.time() + self.put_skew})


def _values(family: str) -> dict:
    fam = metrics.get_registry().counter(family, labelnames=("hop", "token"))
    return {(hop, token): fam.value(hop=hop, token=token)
            for hop in obs.SERVING_HOPS for token in obs.HOP_TOKENS}


def _exchange(world, requests: int, tokens: int, *, stream: bool = True):
    """``requests`` requests of ``tokens`` tokens through a real frontend
    and client; returns (messages moved, seconds moved, the scheduler's
    ``metrics()["hops"]``) of the exchange."""
    sched = _scheduler(world).start()
    frontend = ServeFrontend(sched, b"key")
    addr = frontend.start()
    n0 = _values("tfos_serving_hop_messages_total")
    s0 = _values("tfos_serving_hop_seconds_total")
    try:
        with ServeClient(addr, b"key") as client:
            for r in range(requests):
                prompt = np.arange(1, 4 + r, dtype=np.int32)
                if stream:
                    got = [t for d in client.generate_stream(prompt, tokens)
                           for t in d]
                else:
                    got = client.generate(prompt, tokens).tolist()
                assert got == _fake_tokens(prompt, tokens)
        hops = sched.metrics()["hops"]
    finally:
        frontend.stop()
        sched.stop()
    n1 = _values("tfos_serving_hop_messages_total")
    s1 = _values("tfos_serving_hop_seconds_total")
    return ({k: n1[k] - n0[k] for k in n1}, {k: s1[k] - s0[k] for k in s1},
            hops)


@pytest.fixture(scope="module")
def scripted():
    """4 requests of 6 one-token messages through stamping replicas."""
    return _exchange(_StampedWorld(1), 4, 6)


@pytest.mark.parametrize("hop", obs.SERVING_HOPS)
def test_every_hop_counts_the_messages_a_scripted_exchange_sent(
        scripted, hop):
    """Every hop of the way in counts the requests, every hop of the way
    back one first message a request and five next ones; a ``done``
    message is no token message."""
    moved, seconds, hops = scripted
    assert moved[hop, "first"] == 4
    assert moved[hop, "next"] == (0 if hop in WAY_IN else 20)
    assert seconds[hop, "first"] >= 0 and seconds[hop, "next"] >= 0
    # the operator's view: mean in ms and count by token kind
    assert set(hops[hop]) == ({"first"} if hop in WAY_IN
                              else {"first", "next"})
    for view in hops[hop].values():
        assert set(view) == {"mean_ms", "count"}
        assert view["count"] >= 4 and view["mean_ms"] >= 0.0


def test_the_hops_partition_a_first_tokens_way(scripted):
    """No hop exceeds the whole, and ``metrics()`` shows these hops only."""
    _, seconds, hops = scripted
    assert sum(seconds[h, "first"] for h in obs.SERVING_HOPS) < 4 * 5.0
    assert set(hops) == set(obs.SERVING_HOPS)


def test_a_message_without_stamps_is_served_and_skips_the_stamped_hops():
    """A replica of an older build (rolling upgrade): its messages carry
    neither ``t_in`` nor ``t_put``."""
    moved, _, _ = _exchange(_FakeWorld(1), 2, 5)
    for hop in obs.SERVING_HOPS:
        want = (0, 0) if hop in STAMPED else \
            (2, 0) if hop in WAY_IN else (2, 8)
        assert (moved[hop, "first"], moved[hop, "next"]) == want, hop


def test_a_negative_difference_counts_zero():
    """The replica's clock 100 s ahead of the driver's: ``fetch`` (and the
    ``dispatch`` before it) count their messages and no seconds; ``seat``,
    both stamps the replica's, is not touched by the skew."""
    moved, seconds, _ = _exchange(_StampedWorld(1, put_skew=100.0), 2, 4)
    assert moved["fetch", "first"] == 2 and moved["fetch", "next"] == 6
    assert seconds["fetch", "first"] == 0.0 == seconds["fetch", "next"]
    assert seconds["seat", "first"] >= 2 * 100.0
    clock = obs.hop_clocks()["next"]["pump"]
    before = (clock.seconds.value(), clock.messages.value())
    clock.add(-3.0)
    assert (clock.seconds.value(), clock.messages.value()) \
        == (before[0], before[1] + 1)


def test_pump_and_send_count_the_frames_written():
    """A caller that does not stream gets no TOK frame: the scheduler's
    hops count its token messages, the frontend's ``pump`` and ``send``
    none."""
    moved, _, _ = _exchange(_StampedWorld(1), 1, 5, stream=False)
    assert moved["fetch", "first"] + moved["fetch", "next"] == 5
    assert moved["accept", "first"] == 1
    for hop in ("pump", "send"):
        assert moved[hop, "first"] == moved[hop, "next"] == 0


def test_without_telemetry_nothing_is_clocked(monkeypatch):
    """``TFOS_NO_TELEMETRY=1``: no clocks are made, the events are the
    two-element tuples they were, and ``metrics()`` has no hops."""
    monkeypatch.setenv(metrics.DISABLE_ENV, "1")
    monkeypatch.setattr(metrics, "_default_registry", None)
    assert obs.hop_clocks() is None and obs.hop_means(None) == {}
    world = _StampedWorld(1)
    sched = _scheduler(world).start()
    frontend = ServeFrontend(sched, b"key")
    try:
        assert sched._hops is None and frontend._hops is None
        req = sched.submit(np.arange(1, 4, dtype=np.int32), 3)
        events = [req.events.get(timeout=10) for _ in range(4)]
        assert [len(e) for e in events] == [2, 2, 2, 2]
        assert events[-1][0] == "done"
        assert req.t_submit == 0.0 == req.t_routed
        assert sched.metrics()["hops"] == {}
    finally:
        sched.stop()


def test_hop_families_are_two_counters_over_seven_hops_and_two_kinds():
    assert obs.SERVING_HOPS == ("accept", "pending", "dispatch", "seat",
                                "fetch", "pump", "send")
    assert obs.HOP_TOKENS == ("first", "next")
    clocks = obs.hop_clocks()
    assert {t: tuple(h) for t, h in clocks.items()} \
        == {t: obs.SERVING_HOPS for t in obs.HOP_TOKENS}
    snap = metrics.get_registry().snapshot()
    for name in ("tfos_serving_hop_seconds_total",
                 "tfos_serving_hop_messages_total"):
        assert snap[name]["type"] == "counter"
        assert snap[name]["labelnames"] == ["hop", "token"]


@pytest.mark.parametrize("event", ["tok", "done"])
def test_handle_response_without_a_clock_reading_clocks_nothing(event):
    """``_handle_response`` called without ``t_got`` (as a unit test or an
    adopted scheduler may): the message is handled, no hop moves."""
    world = _StampedWorld(1)
    sched = _scheduler(world)
    n0 = _values("tfos_serving_hop_messages_total")
    rep = sched.replicas[0]
    req = sched.submit(np.arange(1, 4, dtype=np.int32), 2)
    sched._pending.remove(req)
    rep.outstanding[req.rid] = req
    try:
        sched._handle_response(rep, {"rid": req.rid, "event": event,
                                     "tokens": [5], "t_put": time.time()})
    finally:
        sched.stop()
    assert len(req.events.get(timeout=1)) == 2
    n1 = _values("tfos_serving_hop_messages_total")
    assert {k: n1[k] - n0[k] for k in n1 if n1[k] != n0[k]} == {}


def test_a_clocked_event_carries_the_gets_stamp_and_the_token_kind():
    """What the frontend's ``pump`` starts from: the ``tok`` event of a
    clocked message is ``("tok", tokens, t_got, token kind)``, the kind the
    scheduler's own (``first`` once a request)."""
    world = _StampedWorld(1)
    sched = _scheduler(world).start()
    try:
        t0 = time.time()
        req = sched.submit(np.arange(1, 4, dtype=np.int32), 3)
        events = [req.events.get(timeout=10) for _ in range(4)]
    finally:
        sched.stop()
    assert [e[0] for e in events] == ["tok", "tok", "tok", "done"]
    assert [e[3] for e in events[:3]] == ["first", "next", "next"]
    stamps = [e[2] for e in events[:3]]
    assert stamps == sorted(stamps) and t0 <= stamps[0] <= time.time()
    assert len(events[3]) == 2


class _Sink:
    """The frontend's ``send`` and nothing else of a connection."""

    def __init__(self):
        self.frames = []

    def sendall(self, data):
        self.frames.append(data)


def test_a_resumed_pump_clocks_under_the_schedulers_token_kind():
    """A reconnecting caller's pump (``skip`` > 0) starts in the middle of
    a stream: its frames are the kind the scheduler gave the messages, not
    ``first`` again, and a message the cut swallows whole is no frame."""
    world = _StampedWorld(1)
    sched = _scheduler(world)
    frontend = ServeFrontend(sched, b"key")
    n0 = _values("tfos_serving_hop_messages_total")
    req = sched.submit(np.arange(1, 4, dtype=np.int32), 3)
    now = time.time()
    for ev in (("tok", [7], now, "first"), ("tok", [8], now, "next"),
               ("tok", [9], now, "next"), ("done", 3)):
        req.events.put(ev)
    sink = _Sink()
    try:
        frontend._pump_request(sink, req, True, skip=1)
    finally:
        sched.stop()
    n1 = _values("tfos_serving_hop_messages_total")
    moved = {k: n1[k] - n0[k] for k in n1 if n1[k] != n0[k]}
    assert moved == {("pump", "next"): 2, ("send", "next"): 2}
    assert len(sink.frames) >= 3        # two TOK frames and DONE


@pytest.mark.parametrize("since", [False, True])
def test_hop_means_over_the_life_or_since_an_earlier_reading(since):
    """``hop_means(clocks, since=hop_totals(clocks))``: the means of what
    was added after the earlier reading, hops that added nothing left
    out; without ``since`` the process's life."""
    clocks = obs.hop_clocks()
    clocks["first"]["seat"].add(5.0)            # a replica's cold start
    before = obs.hop_totals(clocks)
    assert before["seat", "first"][0] >= 5.0
    clocks["first"]["seat"].add(0.030)
    clocks["next"]["fetch"].add(0.004)
    clocks["next"]["fetch"].add(0.008)
    view = obs.hop_means(clocks, since=before if since else None)
    if since:
        assert view == {"seat": {"first": {"mean_ms": pytest.approx(30.0),
                                           "count": 1}},
                        "fetch": {"next": {"mean_ms": pytest.approx(6.0),
                                           "count": 2}}}
    else:
        assert view["seat"]["first"]["count"] >= 2
        assert view["seat"]["first"]["mean_ms"] > 30.0
    assert obs.hop_totals(None) == {}
