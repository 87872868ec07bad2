"""The decode program's share of its roofline for a configuration of
Mamba-2, expert and attention layers, one mixer a block: the least time the
chip could take to read every weight outside the routed experts once, the
weights of the HELD experts the step touched, the seated rows' SSM state
once and write it once, and the live K/V of the attention layers
(``shapes_nemotron.decode_step`` at the window's mean rows per step and
mean context) over the device time of one run of ``jit_tfos_decode`` in
the traced steps.  The cell's share of the whole step, named so on
purpose; ``step_mfu.serve`` takes this reader's ``step_work``.

Held experts touched per decode step come from the program's counters:
``tfos_replica_experts_touched_total`` less the part its prefill
dispatches account for (``tfos_replica_prefill_experts_touched_total``);
the rows they multiplied are counted as one assignment a touched expert,
the least there can be: so the bytes can only be understated.  The share of the
router's choices that fell to held experts
(``tfos_replica_expert_assignments_held_total`` over
``..._expert_assignments_total``, decode steps and prefills alike) is said
beside the share.  A program without those counters, or a configuration
without a ``hybrid_override_pattern``, reads nothing."""

from benchmark import harness, shapes, shapes_nemotron

PROGRAM = "jit_tfos_decode"
HELD = "tfos_replica_expert_assignments_held_total"
TOUCHED = "tfos_replica_experts_touched_total"
IN_PREFILLS = "tfos_replica_prefill_experts_touched_total"


def decode_experts(run):
    """``(held experts touched per decode step summed over the expert
    layers, share of all assignments that fell to held experts)``, or None
    where the program or the configuration has no such thing."""
    c = run.get("counters") or {}
    cfg = run["cell"]["config_data"]
    decodes = c.get("tfos_replica_decode_dispatches_total")
    if not decodes or not cfg.get("hybrid_override_pattern") \
            or not c.get(TOUCHED) or HELD not in c or IN_PREFILLS not in c:
        return None
    return (c[TOUCHED] - c[IN_PREFILLS]) / decodes, \
        c[HELD] / c["tfos_replica_expert_assignments_total"]


def seated_rows(run):
    """Rows seated per decode step over the window (tokens streamed less
    the first tokens the admissions gave), or None without the state
    counter or a state-space layer."""
    c = run.get("counters") or {}
    cfg = run["cell"]["config_data"]
    decodes = c.get("tfos_replica_decode_dispatches_total")
    if not decodes or "M" not in cfg.get("hybrid_override_pattern", "") \
            or "tfos_replica_state_rows_seated_total" not in c:
        return None
    return (c["tfos_replica_tokens_total"]
            - c["tfos_replica_state_rows_seated_total"]) / decodes


def step_work(run):
    """``(work, seconds a run, facts)`` of the decode step, or None where
    there is nothing to read (``step_mfu.serve`` reads the same)."""
    trace = run.get("trace")
    if run["kind"] != "serve-closed" or not trace \
            or not run.get("mean_context_tokens"):
        return None
    experts, rows = decode_experts(run), seated_rows(run)
    program = trace["programs"].get(PROGRAM)
    if experts is None or rows is None or not program \
            or not program["runs"]:
        return None
    work = shapes_nemotron.decode_step(
        run["cell"]["config_data"], rows,
        rows * run["mean_context_tokens"], experts[0])
    return work, program["seconds"] / program["runs"], {
        "program": PROGRAM, "rows": rows,
        "held_experts_touched_per_step": experts[0],
        "held_share_of_assignments": experts[1]}


def read(run):
    found = step_work(run)
    if found is None:
        return None
    work, seconds, facts = found
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           seconds)
    for fact, name in (("decode", PROGRAM), ("prefill", "jit_tfos_prefill")):
        p = (run["trace"].get("ssm") or {}).get(name)
        if p and p["runs"] and p["scopes"]:
            harness.say(f"{fact} device time by scope", program=name,
                        runs=p["runs"], ms_per_run={
                            k: 1e3 * v / p["runs"] for k, v in
                            sorted(p["scopes"].items(),
                                   key=lambda kv: -kv[1])},
                        program_ms=1e3 * p["seconds"] / p["runs"])
    harness.say("roofline", metric="ssm_moe_decode_step_roofline",
                device_ms=1e3 * seconds, **facts, **roof)
    return roof["share"]
