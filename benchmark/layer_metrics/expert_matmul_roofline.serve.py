"""The expert matmuls' share of their roofline in the decode program: the
least time the chip could take for the three grouped products of every
expert layer (``shapes_lfm2.expert_matmuls``: the touched experts' weights
read once, by the program's ``experts_touched`` counter) over the device
time under the ``moe/experts`` scope per run of ``jit_tfos_decode``
(``trace_scopes``).  It is the grouped matmul's share whoever computes it:
XLA's ``ragged_dot`` today."""

from benchmark import harness, shapes, shapes_lfm2

SCOPE = "moe/experts"


def read(run):
    trace = run.get("trace") or {}
    roofline = harness.load_module("layer_metrics",
                                   "moe_decode_step_roofline")
    program = (trace.get("scopes") or {}).get(roofline.PROGRAM)
    if run["kind"] != "serve-closed" or not program \
            or not program["runs"] or not program["scopes"].get(SCOPE):
        return None
    touched = roofline.decode_experts_touched(run)
    if touched is None:
        return None
    c = run["counters"]
    rows = c["tfos_replica_tokens_total"] \
        / c["tfos_replica_decode_dispatches_total"]
    work = shapes_lfm2.expert_matmuls(run["cell"]["config_data"], rows,
                                      touched)
    seconds = program["scopes"][SCOPE] / program["runs"]
    roof = shapes.roofline(work, harness.peaks_for(run["device"]["kind"]),
                           seconds)
    harness.say("roofline", metric="expert_matmul_roofline.serve",
                scope=SCOPE, rows=rows, experts_touched_per_step=touched,
                device_ms=1e3 * seconds, **roof)
    return roof["share"]
