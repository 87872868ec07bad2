"""Share of the device's idle-gap seconds that no host span of the program
covers: 100 x seconds under ``host/unattributed`` over the sum of the
trace's ``idle_gaps``.  The serving loop and the batcher mark every phase
of a loop turn with a ``tfos/...`` span (``observability.REPLICA_PHASES``);
a refactor that drops one shows here as a rise.  0 where the traced steps
held no gap; nothing where the trace session was set aside
(``harness.idle_share``: its gaps are not reported either)."""


def read(run):
    trace, idle = run.get("trace"), run.get("idle")
    if run["kind"] != "serve-closed" or not trace or not idle \
            or idle["differ"]:
        return None
    total = sum(seconds for _, seconds in trace["idle_gaps"])
    if not total:
        return 0.0
    return 100.0 * sum(seconds for name, seconds in trace["idle_gaps"]
                       if name == "host/unattributed") / total
