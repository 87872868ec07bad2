"""Smoke tests for the on-chip sweep orchestrator (``scripts/tpu_sweep.py``).

The sweep produced the single-chip rows under ``bench_artifacts/``; a
regression that breaks a stage silently costs a whole chip run.  These smokes
run the stages in ``SWEEP_SMOKE`` mode (tiny shapes, CPU, ``smoke_``-prefixed
artifacts that can never clobber real-chip data) inside the example tier.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.example

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SWEEP = os.path.join(ROOT, "scripts", "tpu_sweep.py")


def _smoke_env():
    return {**os.environ, "SWEEP_SMOKE": "1", "JAX_PLATFORMS": "cpu"}


def _run_stage(*argv, timeout=420):
    proc = subprocess.run([sys.executable, SWEEP, *argv],
                          capture_output=True, text=True, timeout=timeout,
                          env=_smoke_env(), cwd=ROOT)
    assert proc.returncode == 0, (
        f"tpu_sweep {argv} failed:\n{proc.stdout}\n{proc.stderr}")
    return proc.stdout


def _remove_smoke_artifacts():
    art = os.path.join(ROOT, "bench_artifacts")
    for name in os.listdir(art):
        if name.startswith("smoke_"):
            os.remove(os.path.join(art, name))


@pytest.fixture(autouse=True)
def _clean_smoke_artifacts():
    # before AND after: a killed prior run (teardown never ran) must not
    # leave stale smoke rows for _merge_row to fold into this run's
    _remove_smoke_artifacts()
    yield
    _remove_smoke_artifacts()


def test_resnet_stage_loop_vs_eager():
    """Eager and single-dispatch fori_loop rows both land in the artifact,
    keyed separately."""
    _run_stage("--stage", "resnet", "--batch", "8")
    _run_stage("--stage", "resnet", "--batch", "8", "--loop")
    with open(os.path.join(ROOT, "bench_artifacts",
                           "smoke_resnet_sweep.json")) as f:
        rows = json.load(f)["rows"]
    keys = {(r["batch"], r["remat"], r["stem"], r["bn"], r["loop"])
            for r in rows}
    assert (8, False, "conv7", "f32", False) in keys
    assert (8, False, "conv7", "f32", True) in keys
    assert all(r["images_per_sec"] > 0 for r in rows)


def test_gpt_train_stage():
    _run_stage("--stage", "gpt_train", "--batch", "2")
    with open(os.path.join(ROOT, "bench_artifacts",
                           "smoke_gpt_train_sweep.json")) as f:
        rows = json.load(f)["rows"]
    assert rows and rows[0]["tokens_per_sec"] > 0
    # the analytic count (the MFU numerator) must be populated
    assert rows[0]["flops_analytic"] > 0


def test_only_filter_respects_given_order():
    """--only runs stages in the order GIVEN, not list-definition order —
    so a resume can put diagnosis stages first."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        from tpu_sweep import _select_stages
    finally:
        sys.path.pop(0)
    stages = [("a", ["x"], 1), ("b", ["y"], 2), ("c", ["z"], 3)]
    assert [s[0] for s in _select_stages(stages, "c,a")] == ["c", "a"]
    assert [s[0] for s in _select_stages(stages, "b, c ,b")] == ["b", "c"]
    with pytest.raises(SystemExit):
        _select_stages(stages, "c,nope")


def test_sweep_parent_never_touches_the_device():
    """The orchestrating parent only starts stage subprocesses: importing
    it loads no jax, and it has no device probe and no per-stage git
    commit (the chip's copy of the checkout is not a repository) — one
    process at a time owns the chip, and it is always a stage's own."""
    code = ("import sys; sys.path.insert(0, 'scripts'); import tpu_sweep; "
            "assert 'jax' not in sys.modules, 'parent imported jax'; "
            "assert not hasattr(tpu_sweep, 'probe'); "
            "assert not hasattr(tpu_sweep, '_commit_artifacts')")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=60,
                          env=_smoke_env())
    assert proc.returncode == 0, proc.stderr


def test_only_filter_validates_before_any_stage():
    """A typo'd stage name fails fast — before any stage starts."""
    proc = subprocess.run(
        [sys.executable, SWEEP, "--only", "definitely_not_a_stage"],
        capture_output=True, text=True, timeout=60, env=_smoke_env(),
        cwd=ROOT)
    assert proc.returncode != 0
    assert "not in the stage list" in proc.stderr


def test_serving_stage_dual_regime():
    """The serving stage reports both arrival regimes (steady backlog +
    bursty waves) with occupancy, admission fraction, and batched
    prefill-dispatch counts."""
    _run_stage("--stage", "serving", timeout=560)
    with open(os.path.join(ROOT, "bench_artifacts",
                           "smoke_serving_throughput.json")) as f:
        row = json.load(f)
    for label in ("steady", "bursty"):
        assert row[f"{label}_tps"] > 0
        assert 0 < row[f"{label}_occupancy"] <= 1
        assert 0 <= row[f"{label}_admission_frac"] < 1
        # batched group admission: fewer prefill dispatches than requests
        assert row[f"{label}_prefill_dispatches"] < row["requests"]
    assert row["static_occupancy"] <= 1
    assert row["speedup_bursty"] > 0
    # speculative row: repetitive traffic must actually accept drafts
    assert row["spec_acceptance"] > 0
    assert row["spec_tokens_per_dispatch"] > 1


def test_bert_squad_stage_l5_path():
    """The BERT-SQuAD stage drives the real L5 pipeline (TFEstimator.fit
    -> cluster -> queue feed) and reports a measured row via the result
    file."""
    _run_stage("--stage", "bert_squad", timeout=560)
    with open(os.path.join(ROOT, "bench_artifacts",
                           "smoke_bert_squad.json")) as f:
        row = json.load(f)
    assert row["examples_per_sec"] > 0
    assert row["timed_steps"] >= 5
    assert 0 <= row["feed_wait_frac"] < 1
    assert "TFEstimator" in row["path"]
    import math
    assert math.isfinite(row["loss"])


def test_mfu_attack_join(tmp_path, monkeypatch):
    """mfu_attack joins profile + roofline + flag rows into a ranked
    verdict, and degrades to named pendings when captures are missing."""
    import importlib.util as ilu

    spec = ilu.spec_from_file_location(
        "mfu_attack", os.path.join(ROOT, "scripts", "mfu_attack.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    art = tmp_path / "bench_artifacts"
    art.mkdir()
    monkeypatch.setattr(mod, "ART", str(art))
    monkeypatch.setattr(mod, "REPO", str(tmp_path))

    (art / "resnet_profile_b256.json").write_text(json.dumps({
        "category_pct": {"convolution fusion": 60.0, "copy": 25.0,
                         "all-reduce": 15.0},
        "top_ops": [{"category": "copy", "op": "copy.1", "self_us": 90.0,
                     "pct": 25.0}]}))
    (art / "resnet_mxu_ceiling.json").write_text(json.dumps({
        "configs": [{"batch": 256, "padding_ceiling_mfu": 0.73,
                     "worst_tile_layers": [{"layer": "s1b1_1x1a",
                                            "tile_efficiency": 0.3}]}]}))
    (art / "resnet_sweep.json").write_text(json.dumps({"rows": [
        {"batch": 256, "remat": False, "stem": "conv7", "bn": "f32",
         "loop": False, "xla": "", "images_per_sec": 2000.0, "mfu": 0.24},
        {"batch": 256, "remat": False, "stem": "conv7", "bn": "f32",
         "loop": False, "xla": "vmem96", "images_per_sec": 2100.0,
         "mfu": 0.252},
        {"batch": 256, "remat": False, "stem": "conv7", "bn": "f32",
         "loop": False, "xla": "nolhs", "images_per_sec": 1900.0,
         "mfu": 0.228}]}))

    import sys as _sys
    monkeypatch.setattr(_sys, "argv", ["mfu_attack.py"])
    mod.main()
    out = json.loads((art / "mfu_attack.json").read_text())
    assert out["pending"] == []
    assert out["non_conv_pct"] == 40.0
    assert out["flag_attack"][0]["xla"] == "vmem96"
    assert out["flag_attack"][0]["speedup_vs_control"] == 1.05
    assert "vmem96" in out["verdict"] and "1.050x" in out["verdict"]
    assert "40.0%" in out["verdict"]


def test_parse_compiler_options_coerces_types():
    """--compiler-options values that look like ints/bools must reach
    compile() typed — PJRT rejects stringly-typed values for typed
    options with an opaque compile-time error (ADVICE r5 item 3)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("tpu_sweep_mod", SWEEP)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    parse = mod._parse_compiler_options

    assert parse("xla_tpu_scoped_vmem_limit_kib=98304") == {
        "xla_tpu_scoped_vmem_limit_kib": 98304}
    assert parse("a=true,b=False,c=text,d=-3,e=0.5") == {
        "a": True, "b": False, "c": "text", "d": -3, "e": 0.5}
    with pytest.raises(ValueError, match="k=v"):
        parse("novalue")
