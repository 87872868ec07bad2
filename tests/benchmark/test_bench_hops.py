"""The driver process's hop clocks (``observability.hop_clocks``; PR 40) in
the benchmark's own driver process: the scheduler and the frontend of a
serve cell run where the runner runs, so the two families stand in that
process's registry, and two ``hop_totals`` around a stretch of the run give
that stretch's hops.  No metric of the manifest reads them yet: a reader
needs the runner to take the totals at the window's two edges (PERF.md
section 7)."""

import pytest

from bench_helpers import ROOT, run_toy  # noqa: F401  (checkout on the path)

from tensorflowonspark_tpu import observability as obs

WAY_IN = ("accept", "pending", "dispatch", "seat")
RETURN = ("fetch", "pump", "send")


@pytest.mark.integration
def test_a_toy_serve_run_clocks_every_hop_and_stays_under_its_callers(
        monkeypatch):
    """A whole toy run: from the warm-up's end to the run's end every hop
    of the way in counted the same first tokens and every hop of the way
    back the same messages, and what the seven hops clocked for first
    tokens does not exceed what the callers waited for them (their sockets
    and threads lie outside the hops) and is most of it."""
    from benchmark.runners import serve_closed

    seen = {}
    warm_up, serve = serve_closed._warm_up, serve_closed._serve

    def warm_up_then_read(*args):
        out = warm_up(*args)
        seen["warm"] = obs.hop_totals(obs.hop_clocks())
        return out

    def serve_then_read(*args):
        out = serve(*args)
        seen["hops"] = obs.hop_means(obs.hop_clocks(), since=seen["warm"])
        seen["requests"] = out["requests"]
        return out

    monkeypatch.setattr(serve_closed, "_warm_up", warm_up_then_read)
    monkeypatch.setattr(serve_closed, "_serve", serve_then_read)
    result = run_toy("toy-gpt-batch-decode", 3000000401, trace=0)
    assert result["correct"] is True
    hops, requests = seen["hops"], seen["requests"]
    assert set(hops) == set(obs.SERVING_HOPS)
    assert len(requests) >= 8 and all(r["recv"] for r in requests)
    firsts = {hop: hops[hop]["first"]["count"] for hop in obs.SERVING_HOPS}
    assert set(firsts.values()) == {len(requests)}, firsts
    nexts = {hop: hops[hop]["next"]["count"] for hop in RETURN}
    assert len(set(nexts.values())) == 1 and min(nexts.values()) > 0, nexts
    assert not any("next" in hops[hop] for hop in WAY_IN)
    inside = sum(hops[hop]["first"]["mean_ms"] for hop in obs.SERVING_HOPS)
    callers = 1e3 * sum(r["recv"][0] - r["sent"] for r in requests) \
        / len(requests)
    assert 0.5 * callers < inside <= callers
