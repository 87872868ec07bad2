"""Capture an xprof trace of the ResNet-50 train step and print where the
time goes.

The round-2 verdict's weakest number is 0.24 compute MFU on the b256 bf16
train step (the old sweep's `bench_artifacts/resnet_sweep.json`, not re-measured on the attached chip); closing that gap
needs evidence, not guesses.  This script jits the exact `stage_resnet` step
from `scripts/tpu_sweep.py`, traces a few executions with `jax.profiler`, and
converts the xplane with the installed `xprof` package into an HLO-level
self-time table — the single-chip equivalent of opening the trace viewer.

    python scripts/profile_resnet.py --batch 512 [--stem s2d] [--remat]

Writes `bench_artifacts/resnet_profile_b<batch>[_s2d][_remat].json` with the
top ops by self time plus category totals (convolution vs fusion vs
data-formatting etc.), and prints the table.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def capture(batch: int, stem: str, remat: bool, bn: str = "f32") -> str:
    """Run the sweep's resnet step under the profiler; return the logdir."""
    import jax

    from scripts import tpu_sweep

    logdir = tempfile.mkdtemp(prefix="resnet_prof_")
    # stage_resnet warms up and times; wrap just the timed window by tracing
    # the whole call — compile happens outside the trace via its own warmup,
    # so the trace is dominated by the steady-state steps.
    with jax.profiler.trace(logdir):
        tpu_sweep.stage_resnet(batch, remat=remat, stem=stem, bn=bn,
                               write=False)
    return logdir


def summarize(logdir: str) -> dict:
    """xplane → HLO self-time table via the xprof converter.

    Tries ``hlo_stats`` (device-side, what we want on TPU) and falls back
    to ``framework_op_stats``; raises rather than returning an empty table
    so a trace that captured no device events (seen with the CPU backend)
    fails loudly instead of writing a vacuous artifact."""
    from xprof.convert import raw_to_tool_data

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane under {logdir}")
    tried = {}
    for tool in ("hlo_stats", "framework_op_stats"):
        data, _ = raw_to_tool_data.xspace_to_tool_data(paths, tool, {})
        if isinstance(data, bytes):
            data = data.decode()
        table = json.loads(data)
        # Shapes seen from the converter: one gviz dict
        # ({cols: [...], rows: [{c: [{v}]}]}), a LIST of gviz dicts
        # (framework_op_stats), or a plain list-of-lists with a header row.
        candidates = table if isinstance(table, list) else [table]
        cols, rows = [], []
        if candidates and isinstance(candidates[0], dict):
            for t in candidates:
                if not (isinstance(t, dict) and t.get("rows")):
                    continue
                t_cols = [c.get("label") or c.get("id") for c in t["cols"]]
                if cols and t_cols != cols:
                    # different schema (e.g. a diagnostics side-table) —
                    # its cells would be read under the wrong indices
                    continue
                cols = cols or t_cols
                rows += [[cell.get("v") if isinstance(cell, dict) else cell
                          for cell in (r["c"] if isinstance(r, dict) else r)]
                         for r in t["rows"]]
        elif candidates:  # list-of-lists with header
            cols, rows = candidates[0], candidates[1:]
        tried[tool] = len(rows)
        if rows:
            return {"tool": tool, "cols": cols, "rows": rows}
    raise RuntimeError(
        f"profiler trace under {logdir} yielded no rows from any tool "
        f"({tried}); the backend likely emitted no device events")


def report(tab: dict, top: int = 25) -> dict:
    cols = [str(c).lower() for c in tab["cols"]]

    def col(*names):
        for n in names:
            for i, c in enumerate(cols):
                if n in c:
                    return i
        return None

    # hlo_stats: "HLO op name"/"category"/"Total self time (us)";
    # framework_op_stats: "Operation Name"/"Operation Type"/
    # "Total self-time (us)"
    i_cat = col("category", "operation type")
    i_name = col("hlo op name", "op name", "operation name", "name")
    i_self = col("total self time (us)", "total self-time (us)",
                 "self time", "self-time")
    i_frac = col("self time (%)", "self-time on device (%)", "%")
    missing = [label for label, idx in
               (("category", i_cat), ("op name", i_name),
                ("self time", i_self)) if idx is None]
    if missing:
        raise RuntimeError(
            f"{tab.get('tool', 'hlo_stats')} table lacks expected "
            f"column(s) {missing}; columns present: {tab['cols']}")
    rows = tab["rows"]
    by_cat: dict[str, float] = {}
    for r in rows:
        try:
            by_cat[str(r[i_cat])] = by_cat.get(str(r[i_cat]), 0.0) + float(r[i_self])
        except (TypeError, ValueError, IndexError):
            continue
    total = sum(by_cat.values()) or 1.0
    cats = sorted(by_cat.items(), key=lambda kv: -kv[1])
    top_rows = sorted(
        (r for r in rows if len(r) > max(i_self, i_name, i_cat)
         and (isinstance(r[i_self], (int, float)) or
              str(r[i_self]).replace(".", "", 1).isdigit())),
        key=lambda r: -float(r[i_self]))[:top]
    def pct_of(r):
        # the '%' column can be absent, short, or NULL in gviz rows; the
        # computed fraction is always available as the fallback
        if i_frac is not None and len(r) > i_frac:
            try:
                return float(r[i_frac])
            except (TypeError, ValueError):
                pass
        return round(100 * float(r[i_self]) / total, 2)

    out = {
        "category_pct": {k: round(100 * v / total, 1) for k, v in cats},
        "top_ops": [{"category": r[i_cat], "op": str(r[i_name])[:120],
                     "self_us": float(r[i_self]),
                     "pct": pct_of(r)}
                    for r in top_rows],
    }
    print("== category self-time % ==")
    for k, v in out["category_pct"].items():
        print(f"  {v:6.1f}%  {k}")
    print(f"== top {top} ops ==")
    for o in out["top_ops"]:
        print(f"  {o['pct']:6.2f}%  [{o['category']}] {o['op']}")
    return out


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--stem", default="conv7", choices=("conv7", "s2d"))
    p.add_argument("--remat", action="store_true")
    p.add_argument("--bn", default="f32", choices=("f32", "bf16"),
                   help="BatchNorm dtype — profile the tuned bf16-BN "
                        "operating point with --bn bf16")
    p.add_argument("--logdir", default=None,
                   help="summarize an existing trace instead of capturing")
    args = p.parse_args()

    logdir = args.logdir or capture(args.batch, args.stem, args.remat,
                                    args.bn)
    out = report(summarize(logdir))
    tag = f"b{args.batch}" + ("_s2d" if args.stem == "s2d" else "") + \
        ("_remat" if args.remat else "") + \
        ("_bnbf16" if args.bn == "bf16" else "")
    path = os.path.join(REPO, "bench_artifacts", f"resnet_profile_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print("wrote", os.path.relpath(path, REPO))


if __name__ == "__main__":
    main()
