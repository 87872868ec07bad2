"""The decode program's share of its roofline for a configuration with
expert layers: the least time the chip could take to read every weight
outside the experts, the weights of the experts the step touched, the live
K/V of the attention layers and the conv state once
(``shapes_lfm2.decode_step`` at the window's mean rows per step and mean
context) over the device time of one run of ``jit_tfos_decode`` in the
traced steps.

Experts touched per decode step come from the program's counter
``tfos_replica_experts_touched_total``, less what the window's prefills
touched at most (every expert of every expert layer each): so the bytes
can only be understated.  A program without that counter, or a
configuration without experts, reads nothing."""

from benchmark import harness, shapes, shapes_lfm2

PROGRAM = "jit_tfos_decode"


def decode_experts_touched(run):
    """Experts touched per decode step, summed over the expert layers, or
    None where the program or the configuration has no such thing."""
    c = run.get("counters") or {}
    cfg = run["cell"]["config_data"]
    decodes = c.get("tfos_replica_decode_dispatches_total")
    if not decodes or not cfg.get("num_experts") \
            or not c.get("tfos_replica_experts_touched_total"):
        return None
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    prefills = c.get("tfos_replica_prefill_dispatches_total", 0.0)
    touched = c["tfos_replica_experts_touched_total"] \
        - prefills * layers * cfg["num_experts"]
    return max(touched, 0.0) / decodes


def step_work(run):
    """``(work, seconds a run, facts)`` of the decode step, or None where
    there is nothing to read (``step_mfu.serve`` reads the same)."""
    trace = run.get("trace")
    if run["kind"] != "serve-closed" or not trace \
            or not run.get("mean_context_tokens"):
        return None
    touched = decode_experts_touched(run)
    program = trace["programs"].get(PROGRAM)
    if touched is None or not program or not program["runs"]:
        return None
    c = run["counters"]
    rows = c["tfos_replica_tokens_total"] \
        / c["tfos_replica_decode_dispatches_total"]
    work = shapes_lfm2.decode_step(run["cell"]["config_data"], rows,
                                   rows * run["mean_context_tokens"], touched)
    return work, program["seconds"] / program["runs"], {
        "program": PROGRAM, "rows": rows,
        "experts_touched_per_step": touched}


def read(run):
    found = step_work(run)
    if found is None:
        return None
    work, seconds, facts = found
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           seconds)
    by_scope = (run["trace"].get("scopes") or {}).get(PROGRAM)
    if by_scope:            # the run's account of where the step's time went
        harness.say("decode device time by scope", program=PROGRAM,
                    runs=by_scope["runs"], ms_per_run={
                        k: 1e3 * v / by_scope["runs"] for k, v in
                        sorted(by_scope["scopes"].items(),
                               key=lambda kv: -kv[1])},
                    program_ms=1e3 * by_scope["seconds"] / by_scope["runs"])
    harness.say("roofline", metric="moe_decode_step_roofline",
                device_ms=1e3 * seconds, **facts, **roof)
    return roof["share"]
