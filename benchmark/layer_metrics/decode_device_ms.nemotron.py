"""``decode_device_ms.serve`` (the decode program's device time a run) as
``nemotron-3-nano-batch-decode`` reports it: the same reader under a name
of the cell's own, because the accepted list cannot take the cell
(``tests/benchmark`` holds that list to the cells it has; PERF.md section
7).  A ``benchmark`` PR that lets the list take the cell deletes this file
and its entry.  Another model's run, a rehearsal's included, reads
nothing here."""

from benchmark import harness


def read(run):
    if run["cell"]["config_data"].get("model") != "nemotron_h":
        return None
    return harness.load_module("layer_metrics",
                               "decode_device_ms.serve").read(run)
