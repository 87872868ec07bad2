"""Device time of the short-convolution operators per run of the decode
program: 1e3 x the seconds under the ``conv/`` scopes (``in_proj``,
``mix``, ``state_store``, ``out_proj``) of ``jit_tfos_decode`` / its runs
in the traced steps (``trace_scopes``).  Nothing where the trace has no
scopes or the program no conv layer."""

PROGRAM = "jit_tfos_decode"


def read(run):
    trace = run.get("trace") or {}
    program = (trace.get("scopes") or {}).get(PROGRAM)
    if run["kind"] != "serve-closed" or not program or not program["runs"]:
        return None
    seconds = sum(s for scope, s in program["scopes"].items()
                  if scope.startswith("conv/"))
    return 1e3 * seconds / program["runs"] if seconds else None
