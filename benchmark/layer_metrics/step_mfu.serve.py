"""The decode step's share of the chip's peak arithmetic: the operations
one step needs (the configuration's shape function, the one its step
roofline counts bytes and operations with) over the peak FLOP/s times the
device time of one run of the decode program (``shapes.roofline``'s
``mfu``).  It stands beside the kernels' rooflines: a change that takes a
kernel off the path leaves that kernel's roofline silent, and this still
bounds what it may claim.  A decode step is memory-bound, so the number is
small; it is never 0.

The step's operations are counted by the cell's own step roofline: of the
per-layer metrics the manifest declares for the cell, the one whose reader
has a ``step_work`` that finds something to read in the run (a new model
brings its reader with its files).  A rehearsal cell gets every entry, the
dense reader finds numbers in any served model's run, and a model's own
reader comes after it in the manifest: the last that reads is taken."""

from benchmark import harness, shapes


def read(run, kind="serve-closed", metric="step_mfu.serve"):
    if run["kind"] != kind:
        return None
    found = None
    for m in harness.declared_for("per_layer", run["cell"].get("name")):
        reader = harness.reader_of(m["name"], run["cell"])
        if reader is not None and hasattr(reader, "step_work"):
            found = reader.step_work(run) or found
    if found is None:
        return None
    work, seconds, facts = found
    share = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                            seconds)["mfu"]
    harness.say("mfu", metric=metric, flops=work["flops"],
                device_ms=1e3 * seconds, share=share, **facts)
    return share or None
