"""The state-space kernel's share of its roofline in the decode program:
the least time the chip could take to read and write the SSM state of the
seated rows once in every Mamba-2 layer, with x, dt, B and C
(``shapes_nemotron.ssm_step``), over the device time of the
``tfos_ssm_step`` operations of one run of ``jit_tfos_decode``
(``trace_ssm``: summed by the operation's name; the mean call times the
Mamba-2 layers).  Memory-bound by its shapes.  The run says beside it what
the program's own ``tfos_replica_state_bytes_moved_total`` counts a step
(the whole batch's state and convolution tails, seated or parked).  A
program without the kernel or the counter, or an untraced run, reads
nothing."""

from benchmark import harness, shapes, shapes_nemotron

PROGRAM = "jit_tfos_decode"
KERNEL = "tfos_ssm_step"


def read(run):
    trace = run.get("trace") or {}
    program = (trace.get("ssm") or {}).get(PROGRAM)
    if run["kind"] != "serve-closed" or not program or not program["runs"] \
            or KERNEL not in program["kernels"]:
        return None
    rows = harness.load_module(
        "layer_metrics", "ssm_moe_decode_step_roofline").seated_rows(run)
    if rows is None:
        return None
    cfg = run["cell"]["config_data"]
    work = shapes_nemotron.ssm_step(cfg, rows)
    kernel = program["kernels"][KERNEL]
    # one call a Mamba-2 layer a run; a session that opens inside a run
    # holds only its later calls, so the mean call is taken
    seconds = kernel["seconds"] / kernel["calls"] \
        * shapes_nemotron.pattern(cfg)["mamba2"]
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           seconds)
    c = run["counters"]
    moved = c.get("tfos_replica_state_bytes_moved_total")
    harness.say("roofline", metric="ssm_step_roofline", kernel=KERNEL,
                rows=rows, calls_per_run=kernel["calls"] / program["runs"],
                device_ms=1e3 * seconds,
                least_state_gb_per_step=work["state_bytes"] / 1e9,
                program_state_gb_per_step=moved / c[
                    "tfos_replica_decode_dispatches_total"] / 1e9
                if moved else None, **roof)
    return roof["share"]
