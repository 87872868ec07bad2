"""Share of the device's idle-gap seconds that no host span of the program
covers: 100 x seconds under ``host/unattributed`` over the sum of the
trace's ``idle_gaps``.  The serving loop and the batcher mark every phase
of a loop turn with a ``tfos/...`` span (``observability.REPLICA_PHASES``);
a refactor that drops one shows here as a rise.  0 where the traced steps
held no gap.  The share is of gap seconds, so it is read from every traced
serve run, also where the session's idle share was set aside for the
window's (``harness.idle_share``): which spans cover the gaps does not
depend on how many turns of which kind the session held."""


def read(run):
    trace = run.get("trace")
    if run["kind"] != "serve-closed" or not trace \
            or trace.get("idle_gaps") is None:
        return None
    total = sum(seconds for _, seconds in trace["idle_gaps"])
    if not total:
        return 0.0
    return 100.0 * sum(seconds for name, seconds in trace["idle_gaps"]
                       if name == "host/unattributed") / total
