"""The retention kernel's share of its roofline in the decode program: the
least time the chip could take to read and write the state of the seated
rows once in every retention layer, with q, k, v and the gate
(``shapes_brumby.retention_step``), over the device time of the
``tfos_retention_step`` operations of one run of ``jit_tfos_decode``
(``trace_kernels``: summed by the operation's name; the mean call times
the retention layers).  Memory-bound by its
shapes.  Rows seated per decode step come from the program's counters:
tokens streamed less the first tokens the admissions gave
(``tfos_replica_state_rows_seated_total``), over the decode dispatches.  A
program without the kernel or the counter, or an untraced run, reads
nothing."""

from benchmark import harness, shapes, shapes_brumby

PROGRAM = "jit_tfos_decode"
KERNEL = "tfos_retention_step"


def seated_rows(run):
    """Rows seated per decode step over the window, or None where the
    program has no state counter or the configuration no retention
    layer."""
    c = run.get("counters") or {}
    cfg = run["cell"]["config_data"]
    decodes = c.get("tfos_replica_decode_dispatches_total")
    if not decodes or "retention" not in cfg.get("layer_types", ()) \
            or "tfos_replica_state_rows_seated_total" not in c:
        return None
    return (c["tfos_replica_tokens_total"]
            - c["tfos_replica_state_rows_seated_total"]) / decodes


def read(run):
    trace = run.get("trace") or {}
    program = (trace.get("kernels") or {}).get(PROGRAM)
    if run["kind"] != "serve-closed" or not program or not program["runs"] \
            or KERNEL not in program["kernels"]:
        return None
    rows = seated_rows(run)
    if rows is None:
        return None
    cfg = run["cell"]["config_data"]
    work = shapes_brumby.retention_step(cfg, rows)
    kernel = program["kernels"][KERNEL]
    # one call a retention layer a run; a session that opens inside a run
    # holds that run's module event and only its later calls, so the mean
    # call is taken, not the sum over the runs counted
    seconds = kernel["seconds"] / kernel["calls"] \
        * cfg["layer_types"].count("retention")
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           seconds)
    # the prefill program too, where the session held an admission: the
    # chunked form (``ret/chunk``) has no metric of its own
    for fact, name in (("decode", PROGRAM), ("prefill", "jit_tfos_prefill")):
        p = trace["kernels"].get(name)
        if p and p["runs"] and p["scopes"]:
            harness.say(f"{fact} device time by scope", program=name,
                        runs=p["runs"], ms_per_run={
                            k: 1e3 * v / p["runs"] for k, v in
                            sorted(p["scopes"].items(),
                                   key=lambda kv: -kv[1])},
                        program_ms=1e3 * p["seconds"] / p["runs"])
    harness.say("roofline", metric="retention_step_roofline", kernel=KERNEL,
                rows=rows, calls_per_run=kernel["calls"] / program["runs"],
                device_ms=1e3 * seconds, **roof)
    return roof["share"]
