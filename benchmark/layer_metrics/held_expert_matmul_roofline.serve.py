"""The expert products' share of their roofline in the decode program, for
an expert layer that holds a share of the experts beside a shared expert:
the least time the chip could take for the two grouped products of every
expert layer's touched HELD experts and the shared expert's two products
(``shapes_nemotron.expert_matmuls``: the touched held experts' weights read
once, by the program's ``experts_touched`` counter, each with the one row
it has at least) over the device time under the
``moe/experts`` and ``moe/shared`` scopes per run of ``jit_tfos_decode``
(``trace_ssm``).  It is those products' share whoever computes them."""

from benchmark import harness, shapes, shapes_nemotron

SCOPES = ("moe/experts", "moe/shared")


def read(run):
    trace = run.get("trace") or {}
    roofline = harness.load_module("layer_metrics",
                                   "ssm_moe_decode_step_roofline")
    program = (trace.get("ssm") or {}).get(roofline.PROGRAM)
    if run["kind"] != "serve-closed" or not program \
            or not program["runs"] \
            or not all(program["scopes"].get(s) for s in SCOPES):
        return None
    experts = roofline.decode_experts(run)
    if experts is None:
        return None
    c = run["counters"]
    rows = c["tfos_replica_tokens_total"] \
        / c["tfos_replica_decode_dispatches_total"]
    work = shapes_nemotron.expert_matmuls(run["cell"]["config_data"], rows,
                                          experts[0])
    seconds = sum(program["scopes"][s] for s in SCOPES) / program["runs"]
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           seconds)
    harness.say("roofline", metric="held_expert_matmul_roofline.serve",
                scopes=SCOPES, rows=rows,
                held_experts_touched_per_step=experts[0],
                held_share_of_assignments=experts[1],
                ms_by_scope={s: 1e3 * program["scopes"][s] / program["runs"]
                             for s in SCOPES},
                device_ms=1e3 * seconds, **roof)
    return roof["share"]
