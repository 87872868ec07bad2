"""Device time of the chunked state-space scans per run of the prefill
program: 1e3 x the seconds under the ``ssm/scan`` scope of
``jit_tfos_prefill`` / its runs in the traced steps (``trace_ssm``): every
Mamba-2 layer's scan over the admitted prompts, which every stream feels as
part of the admission turn's one long gap.  Nothing where the traced steps
held no admission, or the program has no such scope."""

PROGRAM = "jit_tfos_prefill"
SCOPE = "ssm/scan"


def read(run):
    trace = run.get("trace") or {}
    program = (trace.get("ssm") or {}).get(PROGRAM)
    if run["kind"] != "serve-closed" or not program or not program["runs"] \
            or not program["scopes"].get(SCOPE):
        return None
    return 1e3 * program["scopes"][SCOPE] / program["runs"]
