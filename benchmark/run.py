"""``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell, on the machine it is started on.

The cell is ``workloads/<cell>.json``; its traffic file's ``kind`` names
the runner (``runners/<kind with _>.py``).  This process never imports
jax: the runner boots the program through its normal entry points and the
chip belongs to the one child that computes.  The last line of stdout is
the result; a machine without the chips the cell asks for gets no result
and a non-zero exit code.
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmark import harness

#: set-up is process start to the window's start; the process starts when
#: this module is first imported
STARTED = time.monotonic()


def run_cell(name: str, seed: int, seconds: float, trace: int, *,
             control: bool = False, require_tpu: bool = True,
             restart_after_compile: bool = True,
             worker_env: dict | None = None,
             started: float | None = None) -> str:
    """Run one cell and return its result line (facts and comparisons are
    printed on the way).  ``require_tpu=False`` is for the CPU rehearsals
    under ``tests/benchmark``: the command line has no such switch."""
    cell = harness.load_cell(name)
    kind = cell["traffic_data"]["kind"]
    runner = harness.load_module("runners", kind.replace("-", "_"))
    opts = {"seed": int(seed), "seconds": float(seconds), "trace": int(trace),
            "control": bool(control), "require_tpu": require_tpu,
            "restart_after_compile": restart_after_compile,
            "worker_env": worker_env}
    harness.say("cell", workload=name, config=cell["config"],
                traffic=cell["traffic"], kind=kind, chips=cell["chips"],
                seed=int(seed), seconds=float(seconds), trace=int(trace))
    if "jax" in sys.modules and require_tpu:
        raise RuntimeError("the driver process imported jax before the run")
    run = runner.run(cell, opts, STARTED if started is None else started)
    if "jax" in sys.modules and require_tpu:
        raise RuntimeError("the driver process imported jax during the run")

    return result_of(run, trace)


def result_of(run: dict, trace: int) -> str:
    """The result line of a runner's account: with ``--trace 0`` the
    cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
    the device's busy time and window, and the breakdown."""
    device = dict(run["device"])
    breakdown, facts = None, {}
    if trace:
        metrics = harness.pick_metrics(
            harness.layer_metrics(run), harness.manifest()["per_layer"])
        reduced, idle = run.get("trace"), run.get("idle")
        if reduced and idle:
            device["busy_s"] = idle["busy_s"]
            device["window_s"] = idle["window_s"]
            # a session set aside for its idle share (``harness.idle_share``)
            # still says which of the serving loop's spans its gaps fell
            # under; a train session the profiler held back says nothing
            # of the window's gaps, and gives none
            keep = not idle["differ"] or run["kind"] == "serve-closed"
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"] if keep else []}
            if idle["differ"]:
                facts["session_set_aside"] = True
    else:
        metrics = harness.pick_metrics(
            harness.reported_as(run["values"], run["cell"]),
            harness.declared_for("end_to_end", run["cell"].get("name")))
    compared = run.get("compared") or []
    for row in compared:      # the run's last lines on standard error
        print(f"compared {row['name']} {row['value']!r} limit "
              f"{row['limit']!r}", file=sys.stderr, flush=True)
    return harness.result_line(
        correct=run["correct"], attempted=run["attempted"],
        failed=run["failed"], metrics=metrics, device=device,
        breakdown=breakdown, compared=compared, **facts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also compute the lower-precision control's "
                         "numbers (for setting limits; never in a check)")
    a = ap.parse_args(argv)
    try:
        line = run_cell(a.workload, a.seed, a.seconds, a.trace,
                        control=bool(a.control))
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
