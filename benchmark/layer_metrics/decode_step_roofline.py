"""The decode program's share of its roofline: the least time the chip
could take to read every weight and every live K/V entry once
(``shapes.gpt_decode_step`` at the window's mean rows per step and mean
context) over the device time of one run of the decode program (the
trace's main program, chosen by name: ``trace.MAIN_PROGRAMS``)."""

from benchmark import harness, shapes


def step_work(run):
    """``(work, seconds a run, facts)`` of the decode step, or None where
    there is nothing to read (``step_mfu.serve`` reads the same)."""
    trace = run.get("trace")
    c = run.get("counters") or {}
    if run["kind"] != "serve-closed" or not trace \
            or not c.get("tfos_replica_decode_dispatches_total") \
            or not run.get("mean_context_tokens"):
        return None
    rows = c["tfos_replica_tokens_total"] \
        / c["tfos_replica_decode_dispatches_total"]
    work = shapes.gpt_decode_step(run["cell"]["config_data"], rows,
                                  rows * run["mean_context_tokens"])
    program = trace["programs"][trace["main_program"]]
    return work, program["seconds"] / program["runs"], {
        "program": trace["main_program"], "rows": rows}


def read(run):
    found = step_work(run)
    if found is None:
        return None
    work, seconds, facts = found
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           seconds)
    harness.say("roofline", metric="decode_step_roofline",
                device_ms=1e3 * seconds, **facts, **roof)
    return roof["share"]
