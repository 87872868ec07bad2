"""Shared by the benchmark's CPU rehearsals: run one toy cell through
``benchmark.run.run_cell`` with the harness's look for a chip skipped."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORKER_ENV = {"JAX_PLATFORMS": "cpu",
              "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def run_toy(cell: str, seed: int, *, seconds: float = 1.0, trace: int = 0,
            control: bool = False) -> dict:
    import time

    from benchmark import run

    line = run.run_cell(cell, seed, seconds, trace, control=control,
                        require_tpu=False, restart_after_compile=False,
                        worker_env=WORKER_ENV, started=time.monotonic())
    return json.loads(line)
