"""Accelerator discovery and visibility.

Equivalent of the reference's ``tensorflowonspark/gpu_info.py``, which shells
out to ``nvidia-smi`` to pick free GPUs and returns a ``CUDA_VISIBLE_DEVICES``
string (``gpu_info.py::get_gpus``).  On TPU there is no contention-prone
per-process device picker: libtpu owns the chips on a host and JAX enumerates
them (``jax.devices()``).  What remains useful — and what this module provides
— is (a) lazily-imported device/topology introspection, (b) the
``TPU_VISIBLE_DEVICES``-style visibility env for tests and multi-process
single-host runs, and (c) a ``get_gpus``-compatible shim for API parity.
"""

from __future__ import annotations

import logging
import os
import sys

logger = logging.getLogger(__name__)

MAX_RETRIES = 3  # API parity with gpu_info.MAX_RETRIES; unused on TPU.


class ChipOwnershipError(RuntimeError):
    """A process that must stay off the accelerator touched it, or a
    worker found its chip held by another process."""


def assert_off_accelerator(who: str) -> None:
    """Raise :class:`ChipOwnershipError` if this process has imported jax.

    A chip belongs to one process at a time (libtpu holds a host-wide
    lock from backend initialisation until the process exits).  Processes
    that only coordinate — the driver, the shard members of a serving
    gang whose leader owns every chip of the host — therefore never
    import jax at all: a coordinator that initialised a backend would
    make the process that runs the model fail at start-up."""
    if "jax" in sys.modules:
        raise ChipOwnershipError(
            f"{who} imported jax: this process only coordinates and must "
            "stay off the accelerator, which belongs to the one process "
            "that runs the model")


def chip_busy_hint(traceback_text: str) -> str | None:
    """Name the failure when ``traceback_text`` is a worker dying because
    another process holds the chip, else None.

    Measured on the attached v5e (jax 0.9.0, libtpu 0.0.34): the second
    process to initialise the TPU backend fails within seconds — it does
    not hang — with ``Unable to initialize backend 'tpu': ABORTED:
    Internal error when accessing libtpu multi-process lockfile``, and
    libtpu's own message advises deleting the lock file, which would let
    two processes fight over one chip."""
    if "libtpu multi-process lockfile" not in traceback_text:
        return None
    return ("ChipOwnershipError: another process on this host already "
            "holds the TPU — a chip belongs to ONE process at a time.  "
            "Usual causes: the driver (or a parent) imported jax and "
            "initialised the backend before starting this worker, or two "
            "workers were started on a host whose chips one process owns.  "
            "Do NOT remove /tmp/libtpu_lockfile; stop the other process.")


def num_local_devices() -> int:
    """Number of accelerator devices visible to this process."""
    import jax

    return jax.local_device_count()


def device_summary() -> list[dict]:
    """Introspect visible devices (kind, id, process, coords if TPU)."""
    import jax

    out = []
    for d in jax.devices():
        out.append({
            "id": d.id,
            "process_index": d.process_index,
            "platform": d.platform,
            "kind": getattr(d, "device_kind", "unknown"),
            "coords": getattr(d, "coords", None),
        })
    return out


def visibility_env(device_ids=None, platform: str | None = None,
                   host_device_count: int | None = None) -> dict:
    """Build the env-var dict that controls device visibility for a child.

    The reference computed ``CUDA_VISIBLE_DEVICES`` per executor
    (``gpu_info.py::get_gpus`` randomized free-GPU picking); the TPU analogue
    is ``TPU_VISIBLE_DEVICES``/``TPU_PROCESS_BOUNDS`` for chip partitioning
    and ``--xla_force_host_platform_device_count`` for CPU-simulated meshes.
    """
    env = {}
    if device_ids is not None:
        csv = ",".join(str(i) for i in device_ids)
        env["TPU_VISIBLE_DEVICES"] = csv
        env["CUDA_VISIBLE_DEVICES"] = csv  # harmless parity; ignored on TPU
    if platform:
        env["JAX_PLATFORMS"] = platform
    if host_device_count:
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={host_device_count}"
        env["XLA_FLAGS"] = (flags + " " + flag).strip()
    return env


def get_gpus(num_gpu: int = 1, worker_index: int = -1, format_as_csv: bool = True):
    """API-parity shim for ``gpu_info.py::get_gpus``.

    On TPU hosts all chips belong to the single training process, so this
    returns the first ``num_gpu`` local device ids rather than probing
    ``nvidia-smi``.  Kept so reference-era user code keeps importing cleanly.
    """
    ids = list(range(num_local_devices()))[:num_gpu]
    if worker_index >= 0 and not ids:
        ids = [worker_index % max(1, num_local_devices())]
    return ",".join(map(str, ids)) if format_as_csv else ids
