"""Mean host wait per step in the feed call (``DataFeed.next_batch_arrays``),
from the benchmark's own span around it in the worker's loop."""


def read(run):
    span = (run.get("spans") or {}).get("feed_wait")
    if run["kind"] != "train-fed" or not span:
        return None
    return span["mean_ms"]
