"""Join the MFU evidence into one ranked attack verdict (VERDICT r4 item 2).

Three artifacts triangulate where ResNet-50's measured ~0.24 MFU goes and
what moved it:

- ``resnet_profile_b256.json`` (xprof category/self-time split — WHERE the
  step time lives: convolution fusions vs BN/elementwise vs copies/infeed);
- ``resnet_mxu_ceiling.json`` (analytic padding ceiling 0.735 — proof the
  gap is software, and which layers have the worst tile efficiency);
- ``resnet_sweep.json`` xla-labeled rows (the flag attack: scoped-VMEM
  96/128 MiB, latency-hiding scheduler off — measured A/Bs vs the b256
  control).

Run after the ``resnet_profile`` and ``resnet_b256_vmem*``/``nolhs`` sweep
stages land; writes ``bench_artifacts/mfu_attack.json`` with a ranked
category table, per-flag deltas, and a one-line verdict for the
performance ledger.  Degrades gracefully: missing artifacts are reported
as pending rather than crashing, so a partial capture still yields a
partial verdict.
"""

from __future__ import annotations

import argparse
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(REPO, "bench_artifacts")


def _load(name: str):
    path = os.path.join(ART, name)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--profile", default="resnet_profile_b256.json")
    p.add_argument("--batch", type=int, default=256)
    args = p.parse_args()

    out: dict = {"inputs": {}, "pending": []}

    prof = _load(args.profile)
    out["inputs"]["profile"] = args.profile if prof else None
    if prof:
        cats = prof.get("category_pct", {})
        out["category_pct"] = cats
        # attack ranking: anything that is not the conv fusions themselves
        # is overhead a software change can target.  xprof keeps
        # "convolution fusion" distinct from plain "loop fusion"/
        # "fusion" (BN/elementwise) — only the former is conv work
        conv_keys = [k for k in cats if "conv" in k.lower()]
        conv_pct = sum(cats[k] for k in conv_keys)
        out["conv_like_pct"] = round(conv_pct, 1)
        out["non_conv_pct"] = round(sum(cats.values()) - conv_pct, 1)
        out["top_ops"] = prof.get("top_ops", [])[:10]
    else:
        out["pending"].append("resnet_profile (xprof category split)")

    ceil = _load("resnet_mxu_ceiling.json")
    cfg = None
    if ceil:
        cfg = next((c for c in ceil.get("configs", [])
                    if c.get("batch") == args.batch), None)
    if cfg:
        out["padding_ceiling_mfu"] = cfg["padding_ceiling_mfu"]
        out["worst_tile_layers"] = cfg.get("worst_tile_layers", [])[:3]
    elif ceil:
        out["pending"].append(
            f"resnet_mxu_ceiling config for batch {args.batch}")
    else:
        out["pending"].append("resnet_mxu_ceiling (analytic roofline)")

    sweep = _load("resnet_sweep.json")
    control = None
    flags = []
    if sweep:
        rows = sweep.get("rows", [])
        for r in rows:
            if (r.get("batch") == args.batch and not r.get("remat")
                    and r.get("stem", "conv7") == "conv7"
                    and r.get("bn", "f32") == "f32"
                    and not r.get("loop")):
                if r.get("xla"):
                    flags.append(r)
                else:
                    control = r
    if control:
        out["control"] = {"images_per_sec": control["images_per_sec"],
                          "mfu": control.get("mfu")}
        out["flag_attack"] = [
            {"xla": r["xla"], "images_per_sec": r["images_per_sec"],
             "mfu": r.get("mfu"),
             "speedup_vs_control": round(
                 r["images_per_sec"] / control["images_per_sec"], 4)}
            for r in sorted(flags, key=lambda r: -r["images_per_sec"])]
        if not flags:
            out["pending"].append(
                f"resnet_b{args.batch} vmem96/vmem128/nolhs flag A/Bs")
    elif sweep is None:
        out["pending"].append("resnet_sweep.json (no sweep captured)")
    elif flags:
        # flags without a control: report them raw so a sweep that
        # lost only the control run is distinguishable
        out["flag_rows_without_control"] = [
            {"xla": r["xla"], "images_per_sec": r["images_per_sec"],
             "mfu": r.get("mfu")} for r in flags]
        out["pending"].append(
            f"resnet_sweep b{args.batch} CONTROL row (flag rows exist)")
    else:
        out["pending"].append(f"resnet_sweep b{args.batch} control row")

    # one-line verdict for the ledger
    bits = []
    if "control" in out and out.get("flag_attack"):
        best = out["flag_attack"][0]
        if best["speedup_vs_control"] > 1.01:
            bits.append(f"flag {best['xla']} moves b{args.batch} "
                        f"{best['speedup_vs_control']:.3f}x "
                        f"(mfu {out['control']['mfu']} -> {best['mfu']})")
        else:
            bits.append(f"no flag moved b{args.batch} beyond +1% "
                        f"(best {best['xla']} "
                        f"{best['speedup_vs_control']:.3f}x)")
    if prof is not None and "non_conv_pct" in out:
        bits.append(f"xprof: {out['non_conv_pct']}% of self-time outside "
                    "conv-like categories is the attackable overhead")
    if out["pending"]:
        bits.append("pending: " + "; ".join(out["pending"]))
    out["verdict"] = " | ".join(bits) if bits else "no inputs available"

    path = os.path.join(ART, "mfu_attack.json")
    os.makedirs(ART, exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out.get("verdict")))
    print(f"wrote {os.path.relpath(path, REPO)}")


if __name__ == "__main__":
    main()
