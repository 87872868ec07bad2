"""Gigabytes of per-slot recurrent state a decode step read and wrote, by
the program's own account: the window delta of
``tfos_replica_state_bytes_moved_total`` over the decode dispatches.  The
run says beside it what ``shapes_brumby.retention_step`` counts as the
least for the rows seated: a program that passes over the state a third
time reads half as much again.  Nothing where the program has no such
counter or it did not move."""

from benchmark import harness, shapes_brumby


def read(run):
    c = run.get("counters") or {}
    decodes = c.get("tfos_replica_decode_dispatches_total")
    if run["kind"] != "serve-closed" or not decodes \
            or not c.get("tfos_replica_state_bytes_moved_total"):
        return None
    value = c["tfos_replica_state_bytes_moved_total"] / decodes / 1e9
    rows = harness.load_module(
        "layer_metrics", "retention_step_roofline").seated_rows(run)
    if rows is not None:
        least = shapes_brumby.retention_step(
            run["cell"]["config_data"], rows)["state_bytes"] / 1e9
        harness.say("state bytes", metric="state_bytes_per_step.serve",
                    program_gb_per_step=value, least_gb_per_step=least,
                    over_least=value / least, rows=rows)
    return value
