"""Device seconds of a KERNEL BY ITS OPERATION NAME, and of the named
scopes ``trace_scopes.SCOPES`` does not list, from a profiler trace
(``.xplane.pb``): what the retention layer's metrics read.

A Pallas kernel is one device operation named for its ``pallas_call``
(``tfos_retention_step``); the layer's other parts carry their
``jax.named_scope`` path in ``tf_op`` as every operation does
(``benchmark/trace_scopes.py`` has the account).  This reducer sums, for
each compiled program (an operation belongs to the run it started in), the
durations of the operations whose name matches a kernel of :data:`KERNELS`
and of those whose path holds ``/<scope>/`` for a scope of :data:`SCOPES`.
A kernel lies inside a scope (``ret/step``): the two sums are kept apart,
``kernels`` and ``scopes``, and are not added.  Where the raw proto cannot
be read, or the trace holds none of these, the reduction is ``None`` and
the metrics that read it are left out.
"""

from __future__ import annotations

import bisect
import re

from benchmark import trace

#: ``pallas_call`` names summed (``ops/power_retention.py``)
KERNELS = ("tfos_retention_step",)

#: ``models/gpt.py::PowerRetention``'s scopes
SCOPES = ("ret/qkvg", "ret/qk_norm", "ret/chunk", "ret/step", "ret/out")


def reduce_space(space) -> dict | None:
    """``{program: {"runs", "seconds", "kernels": {name: {"seconds",
    "calls"}}, "scopes": {scope: seconds}}}`` of the first TPU plane of a
    parsed ``XSpace``."""
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
        p = out.setdefault(name, {"runs": 0, "seconds": 0.0, "kernels": {},
                                  "scopes": {}})
        p["runs"] += 1
        p["seconds"] += e.duration_ps * 1e-12
        t = start_ps(lines["XLA Modules"], e)
        runs.append((t, t + e.duration_ps, name))
    runs.sort()
    starts = [r[0] for r in runs]
    kernel_re = re.compile("|".join(re.escape(k) for k in KERNELS))
    placed: dict[int, tuple] = {}      # metadata id -> (kernel, scope)
    found = False
    for e in lines["XLA Ops"].events:
        if e.metadata_id not in placed:
            md = plane.event_metadata[e.metadata_id]
            tf_op = ""
            for s in md.stats:
                if stat_names.get(s.metadata_id) == "tf_op":
                    tf_op = s.str_value or stat_names.get(s.ref_value, "")
            m = kernel_re.search(md.name) or kernel_re.search(tf_op)
            placed[e.metadata_id] = (
                m.group(0) if m else None,
                next((s for s in SCOPES if f"/{s}/" in tf_op), None))
        kernel, scope = placed[e.metadata_id]
        if not kernel and not scope:
            continue
        t = start_ps(lines["XLA Ops"], e)
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t >= runs[i][1]:
            continue
        found = True
        program = out[runs[i][2]]
        seconds = e.duration_ps * 1e-12
        if kernel:
            k = program["kernels"].setdefault(kernel, {"seconds": 0.0,
                                                       "calls": 0})
            k["seconds"] += seconds
            k["calls"] += 1
        if scope:
            program["scopes"][scope] = program["scopes"].get(scope, 0.0) \
                + seconds
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
