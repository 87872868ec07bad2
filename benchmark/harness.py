"""What every runner shares: finding a cell's files by name, the facts and
comparisons a run prints, the reduction of client records to gaps, and the
assembly of the result line.

Nothing here imports jax: the process that runs ``benchmark.run`` drives
the program's entry points and never touches the chip (one process per
chip; a parent that holds it starves its child).
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

#: the checkout: BENCHMARK.json and the benchmark's own directories live here
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

class NoChip(RuntimeError):
    """The chip-owning child found no TPU, or fewer chips than the cell
    asks for: the run prints no result and exits non-zero."""


def say(fact: str, **fields) -> None:
    """One JSON line of a run's own account (never the last line)."""
    print(json.dumps({"fact": fact, **fields}), flush=True)


def load_json(*parts: str):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """A cell is ``workloads/<name>.json``; its configuration and traffic
    are the files its ``config`` and ``traffic`` keys name."""
    if not name or any(c in name for c in "/\\") or name.startswith("."):
        raise ValueError(f"not a cell name: {name!r}")
    path = os.path.join(BENCH, "workloads", f"{name}.json")
    if not os.path.exists(path):
        raise ValueError(f"no cell {name!r}: {path} does not exist")
    cell = load_json("workloads", f"{name}.json")
    cell["name"] = name
    cell["config_data"] = load_json("configs", f"{cell['config']}.json")
    cell["traffic_data"] = load_json("traffic", f"{cell['traffic']}.json")
    if cell["chips"] not in (1, 4):
        raise ValueError(f"cell {name}: chips must be 1 or 4")
    return cell


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module.  Found by file, not by
    import path: a metric's name may hold dots."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"benchmark/{kind}/{name}.py does not exist")
    mod_name = f"benchmark.{kind}." + name.replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json (have {sorted(table)})")
    return table[device_kind]


# ------------------------------------------------------------- comparisons

def limits_for(reference_limits: dict, cfg: dict) -> dict:
    """``{name: [limit, where it comes from]}``: the reference's limits,
    set from chip readings (``reference/*.py``), and over them those of a
    configuration's own ``limits`` key.  Only a rehearsal at toy size has
    that key: a configuration named in ``BENCHMARK.json`` may only tighten
    (``tests/benchmark`` holds it to that), and every ``compared`` line
    says where its limit came from."""
    out = {name: [float(v), "reference"]
           for name, v in reference_limits.items()}
    for name, v in cfg.get("limits", {}).items():
        out[name] = [float(v), "configuration"]
    return out


class Comparisons:
    """Every number a run compares, beside its limit; ``correct`` is their
    conjunction.  A limit of 0 is an exact comparison."""

    def __init__(self):
        self.rows: list[dict] = []

    def add(self, name: str, value, limit, limit_from: str = "harness"
            ) -> bool:
        value = float(value)
        ok = math.isfinite(value) and value <= float(limit)
        row = {"name": name, "value": value, "limit": float(limit),
               "limit_from": limit_from, "ok": ok}
        self.rows.append(row)
        say("compared", **row)
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


# ------------------------------------------------------------ gap reduction

def nearest_rank(values, q: float) -> float:
    """The ``q``-th percentile (0 < q <= 100) by the nearest-rank rule: the
    smallest value with at least q % of the values at or below it.  Never
    interpolated: between two modes an interpolated percentile reads a
    time no request ever saw."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no values")
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def token_gaps(requests: list[dict], t0: float, t1: float) -> list[float]:
    """Seconds between consecutive streamed tokens of one request, pooled
    over ``requests``, for tokens received inside [t0, t1).  A request's
    first token has no predecessor and is left out (its wait is the time
    to first token, another quantity).  ``requests`` hold ``recv``: one
    receive time per token, in order."""
    gaps = []
    for r in requests:
        recv = r["recv"]
        for a, b in zip(recv, recv[1:]):
            if t0 <= b < t1:
                gaps.append(b - a)
    return gaps


def gap_modes(gaps: list[float], width: float = 0.05) -> list[dict]:
    """The modes of a pooled gap distribution: clusters of sorted values in
    which each lies within ``width`` of the cluster's first.  For the
    run's account of where its gaps lie (the ``serve window`` fact)."""
    modes: list[list[float]] = []
    for g in sorted(gaps):
        if modes and g <= modes[-1][0] * (1.0 + width):
            modes[-1].append(g)
        else:
            modes.append([g])
    n = len(gaps)
    return [{"from_ms": m[0] * 1e3, "to_ms": m[-1] * 1e3,
             "share": len(m) / n} for m in modes]


def tail_in_mode(gaps: list[float], q: float, around: float = 0.5,
                 width: float = 0.05) -> dict:
    """Whether the ``q``-th percentile of the pooled gaps lies inside a
    mode: the percentiles ``around`` points below and above it lie within
    ``width`` of it (a mode's width in ``gap_modes``), so that a few gaps
    more or fewer in the tail move the reading by less than that and not
    along the slope between two modes (where a 95th percentile spread 2.4
    .. 12.9 % over five runs of one tree; my chip runs, PR 37)."""
    below, at, above = (nearest_rank(gaps, p)
                        for p in (q - around, q, q + around))
    return {"percentile": q, "inside_a_mode": above - below <= width * at,
            "ms": {str(q - around): 1e3 * below, str(q): 1e3 * at,
                   str(q + around): 1e3 * above},
            "span_share": (above - below) / at,
            "beyond": sum(1 for g in gaps if g > at)}


# ---------------------------------------------------------------- idle share

#: points by which the trace session's idle share may differ from the
#: measured window's before the session is set aside
IDLE_AGREE_POINTS = 5.0


def device_seconds(reduced: dict, dispatched: dict, steps: float) -> dict:
    """The device seconds of a measured window, from a traced session and
    the window's own counts: for every group of programs in ``dispatched``
    (``{(program names): runs in the window}``) the group's mean run in the
    session times its runs in the window, and every other program of the
    session at its seconds per traced step times the window's ``steps``.
    Each program is counted by its own runs, so a session that holds more
    prefills than the window's share (or none) no longer tilts the figure
    (it read -2.96 % idle in one cell and 12.2 % against a traced 0.9 % in
    another while only the main program's time x steps was counted; my
    chip runs, PRs 32 and 37).  A group that ran in the window and not in
    the session is named under ``missing`` and counts nothing."""
    total, missing, counted = 0.0, [], set()
    for names, runs in dispatched.items():
        ran = [reduced["programs"][n] for n in names
               if n in reduced["programs"]]
        counted.update(names)
        n = sum(p["runs"] for p in ran)
        if n:
            total += sum(p["seconds"] for p in ran) / n * runs
        elif runs:
            missing.append(names[0])
    rest = sum(p["seconds"] for name, p in reduced["programs"].items()
               if name not in counted)
    total += rest / reduced["steps"] * steps
    return {"seconds": total, "missing": missing}


def idle_share(reduced: dict, device_s: float, window_s: float) -> dict:
    """The device's idle share of a traced run, from two sides: 1 - busy /
    window of the profiler's session (``reduced``), and 1 - ``device_s`` /
    ``window_s`` of the run's measured window (``device_seconds``).  Where
    they agree the session's own figures stand.  Where they differ by more
    than ``IDLE_AGREE_POINTS`` the session did not see what the window saw:
    it sat between two stalls, or the profiler held the device back (on
    this installation some sessions leave the device idle 0.2 .. 1.2 s
    between ResNet-50 steps and read 64 .. 91 % idle, others of the same
    process read 0.015 %; my chip runs, PR 24, PERF.md section 6).  The run
    then reports the window's figures and says so."""
    from_trace = 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
    from_window = 100.0 * (1.0 - device_s / window_s)
    sound = abs(from_trace - from_window) <= IDLE_AGREE_POINTS
    out = {"from_trace": from_trace, "from_window": from_window,
           "traced_steps": reduced["steps"], "differ": not sound,
           "value": from_trace if sound else from_window,
           "busy_s": reduced["busy_s"] if sound else device_s,
           "window_s": reduced["window_s"] if sound else window_s}
    say("idle share cross-check", reported="trace session" if sound
        else "measured window", session_set_aside=not sound, **out)
    return out


# -------------------------------------------------------------- result line

def memory_peak_bytes(stats_per_device: list[dict]) -> int:
    """Peak bytes on the fullest chip.  The runtime reports live buffers
    (``peak_bytes_in_use``) apart from what it reserves for the compiled
    programs' temporaries (``bytes_reserved``); a chip is as full as the
    larger of the live peak and the live buffers plus that reserve."""
    def one(s: dict) -> int:
        return max(int(s.get("peak_bytes_in_use", 0)),
                   int(s.get("bytes_in_use", 0))
                   + int(s.get("peak_bytes_reserved",
                               s.get("bytes_reserved", 0))))
    return max((one(s) for s in stats_per_device), default=0)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, breakdown: dict | None = None,
                compared: list | None = None, **facts) -> str:
    """The last line of a run: the contract's keys, then any ``facts`` (the
    contract: the driver ignores any other key), and last ``compared``: every
    number ``correct`` was decided from, beside its limit."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": float(v["value"]), "unit": v["unit"]}
                       for k, v in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out.update(facts)
    out["compared"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                       for r in compared or ()}
    return json.dumps(out)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def declared_for(section: str, cell: str | None) -> list[dict]:
    """The entries of the manifest's ``section`` that are ``cell``'s: those
    without a ``workloads`` key, and those that list it.  (A dense model's
    roofline reader finds numbers in an expert model's run too; they
    describe no program that ran, and the manifest does not list that cell
    for it.)  A cell the manifest does not hold, a rehearsal at toy size,
    gets every entry."""
    declared = manifest()
    if cell not in {w["name"] for w in declared["workloads"]}:
        return declared[section]
    return [m for m in declared[section]
            if cell in m.get("workloads", (cell,))]


def reported_as(values: dict, cell: dict) -> dict:
    """``values`` under the names ``cell`` reports them by: its file's
    ``metric_names`` (``{the harness's name: the manifest's}``) renames
    what it lists.  A cell whose runs spread more widely than the others'
    reports the same quantities under names of its own, with bounds of
    their own, and does not widen the bounds the steadier cells stand on
    (PERF.md section 2)."""
    names = cell.get("metric_names", {})
    return {names.get(k, k): v for k, v in values.items()}


def pick_metrics(values: dict, declared: list[dict]) -> dict:
    """Of the numbers a run produced, those the manifest declares, with
    the manifest's unit.  A number nothing produced is left out."""
    out = {}
    for m in declared:
        if values.get(m["name"]) is not None:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def reader_of(name: str, cell: dict):
    """The reader of the per-layer metric ``cell`` reports as ``name``:
    ``layer_metrics/<name>.py``, or, for a name the cell's ``metric_names``
    gives a metric, the reader of the name it stands for.  None where
    there is no such file: a rehearsal cell, which gets every entry, has
    no reader for another cell's own names."""
    for harness_name, own in cell.get("metric_names", {}).items():
        if own == name:
            name = harness_name
    if not os.path.exists(os.path.join(BENCH, "layer_metrics", f"{name}.py")):
        return None
    return load_module("layer_metrics", name)


def layer_metrics(run: dict) -> dict:
    """Every per-layer metric the manifest declares for the run's cell
    whose reader (``reader_of``) finds something to read in ``run``."""
    cell = run["cell"]
    listed = cell.get("name") in {w["name"] for w in manifest()["workloads"]}
    values = {}
    for m in declared_for("per_layer", cell.get("name")):
        reader = reader_of(m["name"], cell)
        if reader is None:
            if listed:
                raise ValueError(f"no reader for {m['name']!r}")
            continue
        value = reader.read(run)
        if value is not None:
            values[m["name"]] = float(value)
    return values
