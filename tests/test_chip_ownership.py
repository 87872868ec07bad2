"""One process for each chip: the coordinating processes stay off jax.

A TPU chip belongs to one process at a time (libtpu holds a host-wide lock
from backend initialisation until exit), so the driver — which only
reserves, feeds, schedules and serves sockets — must never import jax: a
driver that did would hold the chip its own worker needs.  Each case runs
in a FRESH interpreter, because the pytest process itself has jax loaded.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHECK = "\nimport sys\nassert 'jax' not in sys.modules, 'jax was imported'\n"


def _fresh(code: str, timeout: float = 120) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", code + _CHECK], cwd=REPO,
                          capture_output=True, text=True, timeout=timeout,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.mark.parametrize("code", [
    "import tensorflowonspark_tpu",
    "import tensorflowonspark_tpu.serving",
    "import tensorflowonspark_tpu.batch",
    "import tensorflowonspark_tpu.continual",
    "import tensorflowonspark_tpu.serving.frontend, "
    "tensorflowonspark_tpu.serving.scheduler, "
    "tensorflowonspark_tpu.serving.sharded, "
    "tensorflowonspark_tpu.serving.standby",
    # a mesh= tier's driver (and every gang member) builds a GangSpec
    "from tensorflowonspark_tpu.serving.sharded import GangSpec; "
    "assert GangSpec({'tp': 4}).devices == 4",
    "import chip_smoke",
], ids=["package", "serving", "batch", "continual", "serving-driver-side",
        "gang-spec", "chip_smoke"])
def test_import_leaves_jax_unloaded(code):
    r = _fresh(code)
    assert r.returncode == 0, r.stderr[-2000:]


@pytest.mark.integration
def test_submit_parent_stays_off_jax(tmp_path):
    """scripts/submit.py's parent path: parse, load the map_fun, boot a
    worker, shut down — without the parent importing jax."""
    code = (
        "import runpy, sys\n"
        "sys.argv = ['submit.py', '--num_workers', '1', '--cpu', "
        "'tests.cluster_funcs:fn_noop']\n"
        "try:\n"
        "    runpy.run_path('scripts/submit.py', run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert not e.code, e.code\n")
    r = _fresh(code, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "submit: job finished" in r.stdout


def test_gang_axes_restate_the_mesh_axes():
    from tensorflowonspark_tpu.parallel.mesh import AXES
    from tensorflowonspark_tpu.serving import sharded

    assert sharded.AXES == AXES


def test_assert_off_accelerator_names_the_offender():
    from tensorflowonspark_tpu.device_info import (ChipOwnershipError,
                                                   assert_off_accelerator)

    import jax  # noqa: F401 — this (pytest) process has it loaded

    with pytest.raises(ChipOwnershipError, match="gang 0 member rank 1"):
        assert_off_accelerator("gang 0 member rank 1")
    r = _fresh("from tensorflowonspark_tpu.device_info import "
               "assert_off_accelerator; assert_off_accelerator('driver')")
    assert r.returncode == 0, r.stderr[-2000:]


def test_chip_busy_hint_names_a_held_chip():
    """The failure a second jax process shows on the attached v5e (it
    fails within seconds, it does not hang) is named for what it is."""
    from tensorflowonspark_tpu.device_info import chip_busy_hint

    tb = ("RuntimeError: Unable to initialize backend 'tpu': ABORTED: "
          "Internal error when accessing libtpu multi-process lockfile. "
          "Run \"$ sudo rm /tmp/libtpu_lockfile\".")
    hint = chip_busy_hint(tb)
    assert hint.startswith("ChipOwnershipError")
    assert "Do NOT remove" in hint
    assert chip_busy_hint("ValueError: boom") is None
