"""Test fixtures.

Mirrors the reference's test backbone (SURVEY.md §4): the reference tests run
against Spark's ``local-cluster[N, cores, mem]`` master — real multi-process
distribution on one machine, fail-fast (``spark.task.maxFailures=1``).  Here
the analogue is (a) an 8-device CPU-simulated mesh inside the test process
(``--xla_force_host_platform_device_count=8``) for sharding tests, and (b)
``LocalProcessBackend`` worker processes for orchestration tests.
"""

import os

# Must happen before any jax import anywhere in the test session.
os.environ["JAX_PLATFORMS"] = "cpu"
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

import pytest  # noqa: E402

# The tests run on the CPU backend (JAX_PLATFORMS above); the chip is only
# ever reached through chip_smoke.py.
import jax  # noqa: E402

# Persistent XLA compile cache: the suite is compile-bound on this class of
# box (mostly >1s jit compiles); cached re-runs skip straight to execution.
# Keyed by HLO hash, so code changes invalidate exactly the programs they
# touch.  Placement is the program's one rule (util.compilation_cache_dir):
# JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache — the
# same directory node.run hands every spawned worker, so multi-process
# tests are warm on re-runs too.
from tensorflowonspark_tpu.util import enable_compilation_cache  # noqa: E402

enable_compilation_cache(min_compile_secs=0.2)
# CPU compiles of the tiny test models mostly fall in the 0.2-1.0s band
# the workers' 1.0s default threshold would skip
os.environ.setdefault("TFOS_CACHE_MIN_COMPILE_SECS", "0.2")


@pytest.fixture(scope="session")
def jax_cpu_mesh_devices():
    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 simulated CPU devices, got {len(devices)}"
    return devices


@pytest.fixture()
def worker_env(tmp_path):
    """Env for spawned worker processes: force CPU, keep fail-fast."""
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    }
