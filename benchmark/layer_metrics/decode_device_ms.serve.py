"""Device time of one run of the decode program, read BY NAME: 1e3 x
seconds / runs of ``jit_tfos_decode`` in the traced steps, together with
``jit_tfos_decode_sampled`` and ``jit_tfos_decode_block`` where they ran
(``models/serving.py::PROGRAM_NAMES``).  It does not depend on which
program holds the device longest, so it keeps its meaning when the decode
step gets fast and a prefill overtakes it.  A program that gives no such
name (before PR 25) reads nothing."""

from benchmark.trace import DECODE_PROGRAMS as DECODE


def read(run):
    trace = run.get("trace")
    if run["kind"] != "serve-closed" or not trace:
        return None
    ran = [trace["programs"][p] for p in DECODE if p in trace["programs"]]
    runs = sum(p["runs"] for p in ran)
    if not runs:
        return None
    return 1e3 * sum(p["seconds"] for p in ran) / runs
