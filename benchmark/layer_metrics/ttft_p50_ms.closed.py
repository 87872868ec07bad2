"""Median time from a caller's send to its first token, caller's clock,
over the requests whose first token arrived inside the window (closed
loop: some tens of them, so the median and not a tail)."""

import statistics


def read(run):
    ttft = run.get("ttft_ms")
    if run["kind"] != "serve-closed" or not ttft:
        return None
    return statistics.median(ttft)
