"""The device's idle share of the serving loop: 1 - busy / window from the
trace, over the traced steps (at least 20 by the traffic file's
``trace_steps``), cross-checked in ``harness.idle_share`` against the
measured window's own 1 - (device time per loop step x steps) / window,
which stands in its place where the two differ."""


def read(run):
    idle = run.get("idle")
    if run["kind"] != "serve-closed" or not idle:
        return None
    return idle["value"]
