"""The decode program's share of its roofline for a configuration of
retention layers: the least time the chip could take to read every layer's
weights and the head once and to read and write the seated rows' state
once (``shapes_brumby.decode_step``) over the device time of one run of
``jit_tfos_decode`` in the traced steps.  The cell's share of the whole
step, named so on purpose.  Reads nothing without the state counter or a
traced decode program."""

from benchmark import harness, shapes, shapes_brumby

PROGRAM = "jit_tfos_decode"


def read(run):
    trace = run.get("trace")
    if run["kind"] != "serve-closed" or not trace:
        return None
    program = trace["programs"].get(PROGRAM)
    rows = harness.load_module(
        "layer_metrics", "retention_step_roofline").seated_rows(run)
    if rows is None or not program or not program["runs"]:
        return None
    work = shapes_brumby.decode_step(run["cell"]["config_data"], rows)
    seconds = program["seconds"] / program["runs"]
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           seconds)
    harness.say("roofline", metric="retention_decode_step_roofline",
                program=PROGRAM, rows=rows, device_ms=1e3 * seconds, **roof)
    return roof["share"]
