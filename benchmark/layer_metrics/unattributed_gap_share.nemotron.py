"""``unattributed_gap_share.serve`` (the share of the serving loop's time that no phase clock holds) as
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
                               "unattributed_gap_share.serve").read(run)
