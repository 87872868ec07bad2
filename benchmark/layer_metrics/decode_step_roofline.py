"""The decode program's share of its roofline: the least time the chip
could take to read every weight and every live K/V entry once
(``shapes.gpt_decode_step`` at the window's mean rows per step and mean
context) over the device time of one run of the decode program (the
program that holds the device longest in the trace)."""

from benchmark import harness, shapes


def read(run):
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
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           program["seconds"] / program["runs"])
    harness.say("roofline", metric="decode_step_roofline",
                program=trace["main_program"], rows=rows,
                device_ms=1e3 * program["seconds"] / program["runs"], **roof)
    return roof["share"]
