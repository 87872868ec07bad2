"""Device seconds by NAMED SCOPE, from a profiler trace (``.xplane.pb``).

``trace.reduce`` keeps the ten largest operations; a layer's time is the
sum of many small ones.  Every device operation's event metadata carries
``tf_op``: the operation's name path as the program gave it
(``jit(tfos_decode)/GPT/layer_3/moe/experts/ragged_dot_general:``), with
flax's module names and the ``jax.named_scope`` names the program lays
(docs/observability.md "Profiler spans").  This reducer sums, for each
compiled program (an operation belongs to the run it started in), the
durations of the operations whose path holds ``/<scope>/``, for every
scope of :data:`SCOPES`, over the whole trace, and counts the program's
runs there.  Operations the compiler names itself and gives no path are
placed by that name where only one scope can have made them
(:data:`BY_NAME`).  A fusion carries
the path of ONE of the operations fused into it, so a scope's seconds are
those of the fusions attributed to it: good to a few per cent where XLA
fuses across a scope's edge.

``jax.profiler.ProfileData`` does not show event metadata; the raw proto
is read with the ``xplane_pb2`` that the installed ``tensorflow`` ships.
Where that import fails, or a trace has no such metadata, the reduction is
``None`` and the metrics that read it are left out.
"""

from __future__ import annotations

import bisect
import re

from benchmark import trace

#: the scopes summed: the conv operator's and the expert layer's parts
#: (``models/gpt.py::ShortConv``, ``models/moe.py``) and attention's
SCOPES = ("conv/in_proj", "conv/mix", "conv/state_store", "conv/out_proj",
          "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
          "attn/qkv", "attn/qk_norm", "attn/kv_store", "attn/kv_gather",
          "attn/scores", "attn/context", "embed", "lm_head")

#: the TPU compiler makes the grouped matmul a custom call of its own and
#: names it ``ragged-dot-*`` (with ``ragged_dot_tiling`` fusions beside it),
#: its ``tf_op`` that name and no path: the one place a program of this
#: repo makes such a call is ``models/moe.py``'s ``moe/experts`` scope
BY_NAME = ((re.compile(r"^%?ragged[-_]dot"), "moe/experts"),)


def scope_of(tf_op: str, hlo_name: str = "") -> str | None:
    for scope in SCOPES:
        if f"/{scope}/" in tf_op:
            return scope
    for pattern, scope in BY_NAME:
        if pattern.match(hlo_name):
            return scope
    return None


def reduce_space(space) -> dict | None:
    """``{program: {"runs", "seconds", "scopes": {scope: seconds}}}`` of
    the first TPU plane of a parsed ``XSpace``.  An operation belongs to
    the program whose run (``XLA Modules`` event) it started in."""
    plane = next((p for p in space.planes
                  if trace.DEVICE_PLANE.match(p.name)), None)
    if plane is None:
        return None
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    lines = {line.name: line for line in plane.lines}
    if "XLA Ops" not in lines or "XLA Modules" not in lines:
        return None

    def start_ps(line, e):
        return line.timestamp_ns * 1000 + e.offset_ps

    out: dict = {}
    runs = []                       # (start, end, program), by start
    for e in lines["XLA Modules"].events:
        name = trace.program_name(plane.event_metadata[e.metadata_id].name)
        p = out.setdefault(name, {"runs": 0, "seconds": 0.0, "scopes": {}})
        p["runs"] += 1
        p["seconds"] += e.duration_ps * 1e-12
        t = start_ps(lines["XLA Modules"], e)
        runs.append((t, t + e.duration_ps, name))
    runs.sort()
    starts = [r[0] for r in runs]
    scope_by_id: dict[int, str | None] = {}
    found = False
    for e in lines["XLA Ops"].events:
        if e.metadata_id not in scope_by_id:
            md = plane.event_metadata[e.metadata_id]
            tf_op = ""
            for s in md.stats:
                if stat_names.get(s.metadata_id) == "tf_op":
                    tf_op = s.str_value or stat_names.get(s.ref_value, "")
            scope_by_id[e.metadata_id] = scope_of(tf_op, md.name)
        scope = scope_by_id[e.metadata_id]
        t = start_ps(lines["XLA Ops"], e)
        i = bisect.bisect_right(starts, t) - 1
        if scope and i >= 0 and t < runs[i][1]:
            found = True
            scopes = out[runs[i][2]]["scopes"]
            scopes[scope] = scopes.get(scope, 0.0) + e.duration_ps * 1e-12
    return out if found else None


def reduce_file(path: str) -> dict | None:
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception:       # no such module here: nothing to read
        return None
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return reduce_space(space)
