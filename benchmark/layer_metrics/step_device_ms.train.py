"""Device time of one run of the train step program, from the trace."""


def read(run):
    trace = run.get("trace")
    if run["kind"] != "train-fed" or not trace:
        return None
    program = trace["programs"][trace["main_program"]]
    return 1e3 * program["seconds"] / program["runs"]
