"""From a profiler trace (``.xplane.pb``) to the few numbers the per-layer
metrics read: device busy time and window, time per compiled program, the
device operations that took most time, and the longest idle gaps laid
against what the host was doing.

A TPU's plane is named ``/device:TPU:<n>`` and holds the lines ``XLA
Modules`` (one event per run of a compiled program, named
``jit_<function>(<hash>)``) and ``XLA Ops`` (one event per operation, named
by its HLO text).  Host threads are other planes; the benchmark's own spans
there are ``TraceAnnotation`` events named ``bench/<span>``.

The window is cut at the starts of runs of the *main* program: from the
start of its first run in the trace to the start of a later one, so that
whatever the host does between two runs is inside the window and an idle
share cannot be read from between two stalls.  The main program is chosen
BY NAME, the step program of the loop (``MAIN_PROGRAMS``, the names
``models/serving.py::PROGRAM_NAMES`` and the train step give), so that a
long prefill (121 ms against a 26 ms decode step in one cell) cannot
overtake it; only a trace that holds none of those names falls back to the
program that holds the device longest.  With a ``period`` the window is a
whole number of periods of that many runs.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: idle gaps shorter than this between two operations are the device's own
#: sequencing, not the host's doing
MIN_GAP_S = 20e-6
HOST_SPAN_PREFIXES = ("bench/", "tfos/")
#: the serving loop's decode step, whichever of its three programs ran
DECODE_PROGRAMS = ("jit_tfos_decode", "jit_tfos_decode_sampled",
                   "jit_tfos_decode_block")
#: the one prefill (every bucket and group size gives that name)
PREFILL_PROGRAMS = ("jit_tfos_prefill",)
#: the programs a loop turn or a train step is counted by
MAIN_PROGRAMS = DECODE_PROGRAMS + ("jit_tfos_train_step",)


def load(path: str) -> list[dict]:
    """The trace as plain data: ``[{"name", "lines": [{"name", "events":
    [(name, start_s, duration_s)]}]}]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def program_name(module_event: str) -> str:
    """``jit_step(8991787937997031288)`` -> ``jit_step``."""
    return module_event.split("(", 1)[0]


def op_name(hlo: str) -> str:
    """A stable, short name for an operation from its HLO text:
    ``%fusion.94 = bf16[256,56,56,256]{3,0,2,1:T(8,128)} fusion(...)`` ->
    ``fusion.94 bf16[256,56,56,256]``."""
    m = re.match(r"^%?([^\s=]+)\s*=\s*(\(?[a-z0-9]+\[[0-9,]*\])?", hlo)
    if not m:
        return hlo[:60]
    return m.group(1) + (" " + m.group(2).lstrip("(") if m.group(2) else "")


def _merge(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return sorted(line["events"], key=lambda e: e[1])
    return []


def reduce(planes: list[dict], period: int | None = None) -> dict | None:
    """Reduce a loaded trace; ``None`` where no TPU plane holds a program
    that ran twice (nothing to read)."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        return None
    modules = _line(devices[0], "XLA Modules")
    seconds: dict[str, float] = {}
    for name, _, dur in modules:
        seconds[program_name(name)] = seconds.get(program_name(name), 0) + dur
    if not seconds:
        return None
    named = [name for name in MAIN_PROGRAMS if name in seconds]
    main = max(named or seconds, key=lambda k: (seconds[k], k))
    runs = [e for e in modules if program_name(e[0]) == main]
    if len(runs) < 2:
        return None
    steps = len(runs) - 1
    if period:
        steps = (steps // period) * period
        if steps < period:
            return None
    t0, t1 = runs[0][1], runs[steps][1]
    window = t1 - t0

    def clip(events):
        return [(n, max(s, t0), min(s + d, t1)) for n, s, d in events
                if s + d > t0 and s < t1]

    busy_per_device, merged0 = [], None
    for plane in devices:
        merged = _merge([(a, b) for _, a, b in clip(_line(plane, "XLA Ops"))])
        busy_per_device.append(sum(b - a for a, b in merged))
        if merged0 is None:
            merged0 = merged
    busy = sum(busy_per_device) / len(busy_per_device)

    programs: dict[str, dict] = {}
    for n, a, b in clip(modules):
        p = programs.setdefault(program_name(n), {"runs": 0, "seconds": 0.0})
        p["runs"] += 1
        p["seconds"] += b - a
    # an operation's name is unique only inside its program: name it
    # <program>/<operation>, the program being the run it started in
    starts = [s for _, s, _ in modules]
    ops: dict[str, float] = {}
    for n, a, b in clip(_line(devices[0], "XLA Ops")):
        i = bisect.bisect_right(starts, a) - 1
        name = (program_name(modules[i][0]) + "/" if i >= 0 else "") \
            + op_name(n)
        ops[name] = ops.get(name, 0.0) + (b - a)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]

    # idle gaps on the first device, each laid against the host span that
    # covers most of it
    host = [(n, s, s + d) for p in planes if not DEVICE_PLANE.match(p["name"])
            for line in p["lines"] for n, s, d in line["events"]
            if n.startswith(HOST_SPAN_PREFIXES) and s + d > t0 and s < t1]
    edges = [[t0, t0]] + merged0 + [[t1, t1]]
    gaps: dict[str, float] = {}
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b - a < MIN_GAP_S:
            continue
        best, cover = "host/unattributed", 0.0
        for n, s, e in host:
            c = min(e, b) - max(s, a)
            if c > cover:
                best, cover = n, c
        gaps[best] = gaps.get(best, 0.0) + (b - a)
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]

    return {"devices": len(devices), "main_program": main, "steps": steps,
            "window_s": window, "busy_s": busy,
            "busy_per_device_s": busy_per_device, "programs": programs,
            "device_ops": [[n, s / steps] for n, s in top_ops],
            "idle_gaps": [[n, s] for n, s in top_gaps]}


def reduce_dir(trace_dir: str, period: int | None = None) -> dict | None:
    return reduce(load(find_xplane(trace_dir)), period=period)
