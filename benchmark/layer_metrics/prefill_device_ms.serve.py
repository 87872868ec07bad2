"""Device time of one run of the prefill program, read BY NAME: 1e3 x
seconds / runs of ``jit_tfos_prefill`` in the traced steps (every bucket
and group size gives that one name; ``models/serving.py::PROGRAM_NAMES``).
Every stream feels each run as one long gap.  Nothing where the traced
steps held no admission, or the program gives no such name (before
PR 25)."""


def read(run):
    trace = run.get("trace")
    if run["kind"] != "serve-closed" or not trace:
        return None
    program = trace["programs"].get("jit_tfos_prefill")
    if not program or not program["runs"]:
        return None
    return 1e3 * program["seconds"] / program["runs"]
