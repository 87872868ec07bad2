"""The train step's share of the chips' peak arithmetic: the operations
the forward and backward passes of one chip's batch need (the cell's
step roofline's ``step_work``; recomputed operations do not count)
over the peak FLOP/s times the step program's device time.  It stands
beside ``train_step_roofline``, which takes the larger of the two bounds.
``step_mfu.serve``'s reading, of a train run."""

from benchmark import harness


def read(run):
    return harness.load_module("layer_metrics", "step_mfu.serve").read(
        run, kind="train-fed", metric="step_mfu.train")
