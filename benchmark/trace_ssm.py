"""Device seconds of the Nemotron-H cell's kernels BY OPERATION NAME and of
its named scopes, from a profiler trace (``.xplane.pb``): what the
``ssm_*`` and ``held_expert_*`` metrics read.

``benchmark/trace_scopes.py`` has the account of where a scope's name is
found (``tf_op``) and ``benchmark/trace_kernels.py`` of a Pallas kernel's
(the operation is named for its ``pallas_call``); both fix their names in
the file, so this reducer, of the same form, takes them as arguments.  For
each compiled program (an operation belongs to the run it started in) it
sums the durations of the operations whose name matches a kernel of
:data:`KERNELS` and of those whose path holds ``/<scope>/`` for a scope of
:data:`SCOPES`.  A kernel lies inside a scope (``ssm/step``): the two sums
are kept apart and are not added.  Where the raw proto cannot be read, or
the trace holds none of these (a program without ``ops/ssm.py``), the
reduction is ``None`` and the metrics that read it are left out.
"""

from __future__ import annotations

import bisect
import re

from benchmark import trace

#: ``pallas_call`` names summed (``ops/ssm.py``, ``ops/grouped_matmul.py``)
KERNELS = ("tfos_ssm_step", "tfos_grouped_matmul")

#: ``models/gpt.py::Mamba2Mixer``'s scopes and the expert layer's products
#: (``models/moe.py``)
SCOPES = ("ssm/in_proj", "ssm/conv", "ssm/step", "ssm/scan", "ssm/gate_norm",
          "ssm/out_proj", "moe/experts", "moe/shared")


def reduce_space(space, kernels=KERNELS, scopes=SCOPES) -> dict | None:
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
    kernel_re = re.compile("|".join(re.escape(k) for k in kernels))
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
                next((s for s in scopes if f"/{s}/" in tf_op), None))
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
