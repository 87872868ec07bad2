"""Without a TPU the command exits non-zero and prints no result."""

import os
import subprocess
import sys

import pytest

from bench_helpers import ROOT

pytestmark = pytest.mark.integration


def test_command_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "toy-resnet-fed", "--seed", "316", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_the_driver_process_stays_off_jax_through_a_whole_run(tmp_path):
    """The parent drives the entry points and must never import jax: on
    the chip a parent that holds it starves its child.  (The other
    rehearsals run under pytest, where jax is already imported.)"""
    code = (
        "import json, sys\n"
        "from benchmark import run\n"
        "if __name__ == '__main__':\n"
        "    line = run.run_cell('toy-resnet-fed', 317, 1.0, 0,\n"
        "        require_tpu=False, restart_after_compile=False,\n"
        "        worker_env={'JAX_PLATFORMS': 'cpu'})\n"
        "    print(json.dumps({'jax_in_driver': 'jax' in sys.modules,\n"
        "                      'correct': json.loads(line)['correct']}))\n")
    path = str(tmp_path / "driver_off_jax.py")
    with open(path, "w") as f:
        f.write(code)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run([sys.executable, path], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {
        "jax_in_driver": False, "correct": True}
