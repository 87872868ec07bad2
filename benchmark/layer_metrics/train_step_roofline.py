"""The train step's share of its roofline: the least time the chips could
take for the step's operations and bytes (``shapes.<model>_train_step``)
over the step program's device time.  ``run["facts"]`` says which bounds."""

from benchmark import harness, shapes


def step_work(run):
    """``(work, seconds a run, facts)`` of one chip's train step, or None
    where there is nothing to read (``step_mfu.train`` reads the same)."""
    trace = run.get("trace")
    if run["kind"] != "train-fed" or not trace:
        return None
    cfg = run["cell"]["config_data"]
    work = getattr(shapes, cfg["model"] + "_train_step")(
        cfg, run["report"]["global_batch"] // run["cell"]["chips"])
    program = trace["programs"][trace["main_program"]]
    return work, program["seconds"] / program["runs"], {
        "program": trace["main_program"]}


def read(run):
    found = step_work(run)
    if found is None:
        return None
    work, seconds, _ = found
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           seconds)
    harness.say("roofline", metric="train_step_roofline", **roof)
    return roof["share"]
