"""The device's idle share of a train run: 1 - busy / window from the
trace, over one whole loss-fetch period, cross-checked in
``harness.idle_share`` against the measured window's own 1 - (device time
per step x steps) / window, which stands in its place where the two differ
(a trace taken between two stalls, or one that held the device back)."""


def read(run):
    idle = run.get("idle")
    if run["kind"] != "train-fed" or not idle:
        return None
    return idle["value"]
