"""Validate the scaling model against ground truth it can check today.

VERDICT r3 item 5: 64 rows of predictions must not float free of
measurement.  Two checks, each an independent joint between the model and
reality:

(a) **single-chip compute** — two anchor-independent checks against
    ``bench_artifacts/resnet_sweep.json`` (the model's MFU may be
    anchored on the b256 row itself — ``scaling_model._anchor_mfu`` —
    so a direct predicted-vs-measured at b256 would be circular):
    the model's per-device FLOP count vs the FLOPs the bench implied at
    the anchor row, and the b256→b128 batch-linearity prediction vs the
    measured b128 row.

(b) **collective bytes across a real process boundary** — the bytes the
    model prices are extracted from single-process HLO
    (``scaling_model.py --child``).  Here the SAME ``bert_tp_sp_dp`` n=8
    workload is compiled over 2 processes x 4 CPU devices
    (``jax.distributed``, the ``tests/test_distributed.py`` regime, dp
    spanning the process boundary) and the cross-process program's HLO
    is put through the same extractor.  Matching per-(op, axes) bytes =
    the single-process pricing transfers to multi-process deployment.

Writes the ``validation`` section into
``bench_artifacts/scaling_model.json`` (which ``scaling_model.py``
preserves across artifact rewrites) and prints a summary.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys

SCRIPTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(SCRIPTS)
sys.path.insert(0, SCRIPTS)
sys.path.insert(0, REPO)

ARTIFACT = os.path.join(REPO, "bench_artifacts", "scaling_model.json")
SWEEP = os.path.join(REPO, "bench_artifacts", "resnet_sweep.json")

DIST_WORKLOAD = "bert_tp_sp_dp"
DIST_N = 8  # 2 procs x 4 devices


# ---------------------------------------------------------------------------
# (a) predicted t_compute vs the measured ResNet-50 step
# ---------------------------------------------------------------------------
def validate_single_chip() -> dict:
    """Two NON-circular checks (the model's MFU may be anchored on the
    very b256 row in the sweep artifact, so 'predicted vs measured at
    b256' would validate nothing once the anchor updates):

    - **FLOP accounting**: the model's per-device FLOPs (cost_analysis +
      loop-dot corrections) vs the FLOPs the bench itself implied at the
      anchor row (``measured_mfu x peak x step_ms``).  Independent of
      which MFU number the model assumes.
    - **Batch linearity**: predict the b128 step by scaling the
      b256-anchored time by FLOPs ratio and compare against the measured
      b128 row — a cross-config generalization the anchor can't absorb.
    """
    import scaling_model as sm

    with open(ARTIFACT) as f:
        art = json.load(f)
    row = next(r for r in art["results"]
               if r["workload"] == "resnet50_dp" and r["n"] == 8)
    peak = art["assumptions"]["peak_bf16_flops_per_chip"]

    # the SAME selection the model's anchor uses (best-MFU among
    # config-matched rows) — first-match would diverge once re-runs
    # append a second matching row
    anchor = sm.best_measured_row("resnet_sweep.json",
                                  prefer=sm.IS_MODELED_RESNET)
    # the b128 row must match the anchor's config in everything but
    # batch (bn follows IS_MODELED_RESNET — comparing a bf16-BN anchor
    # against an f32-BN b128 row would fold the BN-dtype delta into the
    # linearity check)
    b128 = sm.best_measured_row(
        "resnet_sweep.json",
        prefer=lambda r: r.get("batch") == 128
        and sm.IS_MODELED_RESNET({**r, "batch": 256}))
    if b128 is not None and b128.get("batch") != 128:
        b128 = None  # prefer-filter found nothing; best-MFU row is not b128
    out = {
        "workload": "resnet50_dp",
        "flops_per_device_model": row["flops_per_device"],
        "measured_source": "bench_artifacts/resnet_sweep.json",
    }
    if anchor:
        bench_flops = anchor["mfu"] * peak * anchor["step_ms"] / 1e3
        out["flop_accounting"] = {
            "what": "model per-device FLOPs vs the FLOPs the bench "
                    "implied at the anchor row (mfu x peak x step) — "
                    "anchor-independent",
            "anchor_row": {k: anchor.get(k) for k in
                           ("batch", "stem", "bn", "step_ms", "mfu")},
            "bench_implied_flops": round(bench_flops, 0),
            "delta_pct": round(
                100 * (row["flops_per_device"] / bench_flops - 1), 2),
        }
    if anchor and b128:
        pred_ms = anchor["step_ms"] * 128 / 256  # dp: FLOPs ∝ batch
        out["batch_linearity"] = {
            "what": "b256-anchored time scaled by FLOPs ratio vs the "
                    "measured b128 row — cross-config generalization",
            "predicted_step_ms": round(pred_ms, 2),
            "measured_step_ms": b128["step_ms"],
            "delta_pct": round(100 * (pred_ms / b128["step_ms"] - 1), 2),
        }
    return out


# ---------------------------------------------------------------------------
# (b) collective bytes: single-process HLO vs 2-process x 4-device HLO
# ---------------------------------------------------------------------------
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def dist_child(process_id: int, coordinator: str) -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=2, process_id=process_id)
    import scaling_model as sm

    built = sm.WORKLOADS[DIST_WORKLOAD](DIST_N)
    mesh, jitted, abstract_args, loop_trip = built[:4]
    with mesh:  # same trace context as scaling_model.child / the dryrun
        compiled = jitted.lower(*abstract_args).compile()
    if process_id == 0:
        hlo = compiled.as_text()
        comps = sm._split_computations(hlo)
        mult = sm._loop_multipliers(comps, loop_trip)
        colls = sm.extract_collectives(hlo, dict(mesh.shape),
                                       loop_trip=loop_trip,
                                       comps=comps, mult=mult)
        print(json.dumps({
            "summary": sm._summarize(colls),
            "num_processes": jax.process_count(),
            "local_devices": jax.local_device_count(),
            "global_devices": jax.device_count(),
            "mesh": dict(mesh.shape),
        }))
    jax.distributed.shutdown()


def validate_cross_process() -> dict:
    # reference: a FRESH single-process extraction of the same
    # (workload, n) with the same code — exactly what the model prices.
    # (Not the committed artifact row: that may predate model-code
    # changes, and this check is about process count, not code drift.)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={DIST_N}"
    r = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "scaling_model.py"),
         "--child", "--workload", DIST_WORKLOAD, "--n", str(DIST_N)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"single-process reference child failed:\n"
                           f"{r.stderr[-3000:]}")
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    import scaling_model as sm
    single = sm._summarize(rec["collectives"])

    coordinator = f"localhost:{_free_port()}"
    env = dict(os.environ)
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--dist-child",
         "--process-id", str(i), "--coordinator", coordinator],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, env=env, cwd=REPO) for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=900)
            if p.returncode != 0:
                raise RuntimeError(f"dist child failed "
                                   f"(rc={p.returncode}):\n{err[-3000:]}")
            outs.append(out)
    finally:
        # never orphan the peer: it would block in jax.distributed
        # initialize/shutdown waiting for the failed process
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
    multi = json.loads(outs[0].strip().splitlines()[-1])
    assert multi["num_processes"] == 2 and multi["global_devices"] == 8

    keys = sorted(set(single) | set(multi["summary"]))
    per_key = {}
    tot_s = tot_m = 0.0
    for k in keys:
        bs = single.get(k, {}).get("bytes", 0.0)
        bm = multi["summary"].get(k, {}).get("bytes", 0.0)
        tot_s += bs
        tot_m += bm
        per_key[k] = {
            "single_process_bytes": bs,
            "two_process_bytes": bm,
            # strict-JSON safe: no float('inf') tokens in the artifact
            "delta_pct": round(100 * (bm / bs - 1), 2) if bs else None,
            **({"only_in": "two_process"} if bm and not bs else
               {"only_in": "single_process"} if bs and not bm else {}),
        }
    return {
        "workload": DIST_WORKLOAD, "n": DIST_N,
        "what": "per-(op, axes) collective bytes from single-process HLO "
                "(what the model prices) vs the same program compiled "
                "over 2 processes x 4 devices (jax.distributed, dp "
                "spanning the process boundary)",
        "two_process_mesh": multi["mesh"],
        "total_bytes_single_process": tot_s,
        "total_bytes_two_process": tot_m,
        "total_delta_pct": round(100 * (tot_m / tot_s - 1), 2) if tot_s
        else None,
        "per_collective": per_key,
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--part", choices=("a", "b", "all"), default="all")
    p.add_argument("--dist-child", action="store_true")
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--coordinator", default=None)
    p.add_argument("--dry", action="store_true",
                   help="print the validation instead of writing it into "
                        "the artifact")
    args = p.parse_args()

    if args.dist_child:
        dist_child(args.process_id, args.coordinator)
        return

    validation = {}
    if args.part in ("a", "all"):
        sc = validate_single_chip()
        validation["single_chip_compute"] = sc
        if "flop_accounting" in sc:
            fa = sc["flop_accounting"]
            print(f"(a) FLOP accounting: model {sc['flops_per_device_model']:.3e}"
                  f" vs bench-implied {fa['bench_implied_flops']:.3e}"
                  f" ({fa['delta_pct']:+.2f}%)")
        if "batch_linearity" in sc:
            bl = sc["batch_linearity"]
            print(f"(a) batch linearity: predicted b128 "
                  f"{bl['predicted_step_ms']} ms vs measured "
                  f"{bl['measured_step_ms']} ms ({bl['delta_pct']:+.2f}%)")
    if args.part in ("b", "all"):
        validation["cross_process_collectives"] = validate_cross_process()
        v = validation["cross_process_collectives"]
        print(f"(b) {v['workload']} n={v['n']}: total collective bytes "
              f"single-proc {v['total_bytes_single_process']:.3e} vs "
              f"2-proc {v['total_bytes_two_process']:.3e} "
              f"({v['total_delta_pct']:+.2f}%)")

    if args.dry:
        print(json.dumps(validation, indent=2))
        return
    with open(ARTIFACT) as f:
        art = json.load(f)
    # subsection replacement: a fresh part carries no 'stale' marker; a
    # part that was NOT re-run keeps the per-part marker scaling_model.py
    # set on rewrite
    art.setdefault("validation", {}).update(validation)
    with open(ARTIFACT, "w") as f:
        json.dump(art, f, indent=2)
    print(f"wrote validation section into {ARTIFACT}")


if __name__ == "__main__":
    main()
