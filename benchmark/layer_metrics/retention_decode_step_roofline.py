"""The decode program's share of its roofline for a configuration of
retention layers: the least time the chip could take to read every layer's
weights and the head once and to read and write the seated rows' state
once (``shapes_brumby.decode_step``) over the device time of one run of
``jit_tfos_decode`` in the traced steps.  The cell's share of the whole
step, named so on purpose.  Reads nothing without the state counter or a
traced decode program."""

from benchmark import harness, shapes, shapes_brumby

PROGRAM = "jit_tfos_decode"


def step_work(run):
    """``(work, seconds a run, facts)`` of the decode step, or None where
    there is nothing to read (``step_mfu.serve`` reads the same)."""
    trace = run.get("trace")
    if run["kind"] != "serve-closed" or not trace:
        return None
    program = trace["programs"].get(PROGRAM)
    rows = harness.load_module(
        "layer_metrics", "retention_step_roofline").seated_rows(run)
    if rows is None or not program or not program["runs"]:
        return None
    work = shapes_brumby.decode_step(run["cell"]["config_data"], rows)
    return work, program["seconds"] / program["runs"], {
        "program": PROGRAM, "rows": rows}


def read(run):
    found = step_work(run)
    if found is None:
        return None
    work, seconds, facts = found
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           seconds)
    harness.say("roofline", metric="retention_decode_step_roofline",
                device_ms=1e3 * seconds, **facts, **roof)
    return roof["share"]
