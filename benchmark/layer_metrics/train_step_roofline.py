"""The train step's share of its roofline: the least time the chips could
take for the step's operations and bytes (``shapes.<model>_train_step``)
over the step program's device time.  ``run["facts"]`` says which bounds."""

from benchmark import harness, shapes


def read(run):
    trace = run.get("trace")
    if run["kind"] != "train-fed" or not trace:
        return None
    cfg = run["cell"]["config_data"]
    work = getattr(shapes, cfg["model"] + "_train_step")(
        cfg, run["report"]["global_batch"] // run["cell"]["chips"])
    program = trace["programs"][trace["main_program"]]
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           program["seconds"] / program["runs"])
    harness.say("roofline", metric="train_step_roofline", **roof)
    return roof["share"]
