"""Tokens streamed per decode dispatch: the program's own counters
``tfos_replica_tokens_total`` / ``tfos_replica_decode_dispatches_total``,
window deltas."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("tfos_replica_decode_dispatches_total"):
        return None
    return c["tfos_replica_tokens_total"] \
        / c["tfos_replica_decode_dispatches_total"]
