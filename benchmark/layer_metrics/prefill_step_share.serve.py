"""Share of the serving loop's steps that also carried a prefill dispatch:
``tfos_replica_prefill_dispatches_total`` / ``tfos_replica_steps_total``,
window deltas, in per cent.  It is the mass of the gap distribution's
"decode step plus a prefill" mode."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("tfos_replica_steps_total"):
        return None
    return 100.0 * c["tfos_replica_prefill_dispatches_total"] \
        / c["tfos_replica_steps_total"]
