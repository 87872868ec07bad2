"""CPU rehearsal of ``chip_smoke.py``: the same phase functions, through
the same entry points (``TPUCluster.run`` / ``ServingCluster.run``), at
tiny widths under ``JAX_PLATFORMS=cpu`` — so a wrong path, argument or
check is found here and not on the chip.  Widths are function arguments;
the script itself has no rehearsal switch, and must refuse to report
``"ok": true`` for a device that is not a TPU."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY_RESNET = dict(batch=8, image=32, stage_sizes=(1, 1), num_filters=8,
                   num_classes=10)
TINY_GPT = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                intermediate_size=64, max_position_embeddings=64)
TINY_SERVE = dict(gpt=TINY_GPT, dtype="float32", prompt_lens=(3, 7, 12),
                  new_tokens=6, kv_page_tokens=8, require_tpu=False)


def _cpu_env(devices: int) -> dict:
    return {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}


@pytest.mark.integration
@pytest.mark.parametrize("dp", [1, 4])
def test_train_phase_rehearsal(dp):
    """Train through TPUCluster -> queues/shm -> DataFeed; dp=4 is the
    four-chip phase on four virtual CPU devices."""
    report = chip_smoke.run_train_phase(
        dp=dp, steps=4, require_tpu=False, worker_env=_cpu_env(4),
        **TINY_RESNET)
    assert report["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert len(report["losses"]) == 4
    assert report["losses"][-1] < report["losses"][0]
    assert report["param_shard_devices"] == dp
    assert report["batch_shard_devices"] == dp
    assert report["shm_conns"] > 0


@pytest.mark.integration
def test_serve_phase_rehearsal_aot_second_boot_loads(tmp_path, monkeypatch):
    """Serve through ServingCluster with the replica-side greedy
    reference; the second AOT boot loads every executable and reads the
    persistent cache — both under the ONE directory the environment
    names, and nowhere else."""
    cache = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    env = {**_cpu_env(1), "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    first = chip_smoke.run_serve_phase(aot_cache=True, worker_env=env,
                                       **TINY_SERVE)
    assert first["tokens_compared"] == 18
    assert first["cache_dir"] == str(cache)
    assert first["aot"]["compile"] > 0 and first["aot"]["load"] == 0
    second = chip_smoke.run_serve_phase(aot_cache=True, worker_env=env,
                                        **TINY_SERVE)
    assert second["aot"]["load"] > 0 and second["aot"]["compile"] == 0
    assert second["persistent_cache"]["hits"] > 0
    assert second["streams"] == first["streams"]
    assert os.listdir(cache / "aot")


@pytest.mark.integration
def test_serve_phase_rehearsal_tp4_gang_matches_one_chip():
    """The four-chip serve phase on four virtual devices: a tp=4 gang is
    token-identical to the one-device replica, its members off jax."""
    env = _cpu_env(4)
    solo = chip_smoke.run_serve_phase(worker_env=env, **TINY_SERVE)
    gang = chip_smoke.run_serve_phase(mesh={"tp": 4}, worker_env=env,
                                      **TINY_SERVE)
    assert gang["streams"] == solo["streams"]
    assert gang["members_off_chip"] == 3
    assert solo["members_off_chip"] is None


@pytest.mark.integration
def test_verify_phase_scores_served_tokens_against_the_reference():
    """The teacher-forced check: a stream the reference itself would
    produce scores gap 0 at every token; one wrong token (what a bad KV
    page or position would emit) is deviations away, far over the
    near-tie tolerance."""
    solo = chip_smoke.run_serve_phase(worker_env=_cpu_env(1), **TINY_SERVE)
    assert solo["streams_identical"] == 3 and solo["near_ties"] == []
    good = solo["streams"][2]
    bad = list(good)
    bad[3] = (bad[3] + 17) % TINY_GPT["vocab_size"]
    prompt = solo["prompts"][2]
    scored = chip_smoke.run_verify_phase(
        [(prompt, good), (prompt, bad)], gpt=TINY_GPT,
        dtype="float32", seed=0, worker_env=_cpu_env(1))
    assert max(scored[0]["gaps"]) == 0.0
    assert scored[0]["argmax"] == good
    assert scored[1]["gaps"][3] > 10 * chip_smoke.TIE_TOL_SIGMAS
    assert scored[1]["argmax"][3] == good[3]


def test_same_device_refuses_a_non_tpu():
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert chip_smoke._same_device([tpu, tpu], want_count=1) == tpu
    with pytest.raises(RuntimeError, match="not 1 TPU"):
        chip_smoke._same_device([cpu, cpu], want_count=1)
    with pytest.raises(RuntimeError, match="not 4 TPU"):
        chip_smoke._same_device([tpu, tpu], want_count=4)
    with pytest.raises(RuntimeError, match="different devices"):
        chip_smoke._same_device([tpu, cpu], want_count=1)


@pytest.mark.integration
def test_script_fails_without_a_tpu_and_prints_no_ok():
    """As the driver runs it in the sandbox: no accelerator -> non-zero
    exit, and no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
    # the host line did print, with the codec that loaded
    host = json.loads(r.stdout.splitlines()[0])
    assert host["tfrecord_codec"].startswith(("native:", "python"))
