"""Window seconds over the serving loop's steps in the window
(``tfos_replica_steps_total`` delta): what one turn of the loop costs,
prefills and host work included."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("tfos_replica_steps_total"):
        return None
    return 1e3 * run["window_s"] / c["tfos_replica_steps_total"]
