"""Small utilities shared across the framework.

Equivalent of the reference's ``tensorflowonspark/util.py``
(``single_node_env``, executor-id port-file dedup, ``find_in_path``) plus the
path-resolution helper that lives in ``TFNode.py::hdfs_path`` upstream.
"""

from __future__ import annotations

import os
import socket
import logging

logger = logging.getLogger(__name__)


#: the checkout (the directory that holds the package): the default
#: compile cache lives inside it, at a path that never moves
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compilation_cache_dir() -> str:
    """THE compile-cache directory of every process of this program.

    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it — then no
    code sets another — else the fixed, git-ignored ``<checkout>/.jax_cache``.
    Never derived from a temp dir, a uid, a pid or the time: the directory
    is part of XLA's cache key, so a cache that moves never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache")


def aot_cache_dir() -> str:
    """The serving tier's serialized-executable cache (``serving/aot.py``):
    a sub-directory of :func:`compilation_cache_dir`, so one variable
    places both."""
    return os.path.join(compilation_cache_dir(), "aot")


def enable_compilation_cache(min_compile_secs: float = 1.0) -> str:
    """Turn on XLA's persistent compilation cache at
    :func:`compilation_cache_dir`, for a process that imported jax before
    the environment could place the cache (workers get it from the env
    ``node.run`` exports).  Returns the directory."""
    import jax

    cache_dir = compilation_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    return cache_dir


def single_node_env(num_devices: int | None = None, platform: str | None = None) -> None:
    """Configure env for a single-node (no-cluster) run.

    Reference: ``util.py::single_node_env`` (sets ``CUDA_VISIBLE_DEVICES``
    and clears cluster env).  TPU version: clear any stale coordination env
    and optionally force a platform / virtual device count.
    """
    for var in ("TF_CONFIG", "TFOS_COORDINATOR", "TFOS_NUM_PROCESSES",
                "TFOS_PROCESS_ID"):
        os.environ.pop(var, None)
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
    if num_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={num_devices}"
        if flag not in flags:
            os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()


def split_evenly(items: list, n: int) -> list[list]:
    """Split ``items`` into at most ``n`` non-empty contiguous partitions.

    Shared by the cluster feeder's RDD-partition stand-in and DataFrame
    construction so both layers agree on partition shapes.
    """
    n = max(1, min(n, len(items)) if items else 1)
    size = (len(items) + n - 1) // n
    return [items[i * size:(i + 1) * size]
            for i in range(n) if items[i * size:(i + 1) * size]]


def find_in_path(path: str, file_name: str) -> str | bool:
    """Find a file within a search-path string.  Reference: ``util.py::find_in_path``."""
    for p in path.split(os.pathsep):
        candidate = os.path.join(p, file_name)
        if os.path.exists(candidate) and os.path.isfile(candidate):
            return candidate
    return False


def get_free_port(host: str = "") -> int:
    """Reserve an ephemeral port (bind + close), as the reference's node
    runtime does when pre-binding the TF server port (``TFSparkNode.py::run``)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((host, 0))
        return s.getsockname()[1]


def hdfs_path(ctx, path: str) -> str:
    """Resolve a user path against the cluster's default FS / working dir.

    Reference: ``TFNode.py::hdfs_path`` — absolute schemes pass through,
    relative paths are joined against ``ctx.defaultFS`` + working dir.  On
    TPU-VM clusters the default FS is typically ``gs://`` or a local/NFS dir.
    """
    if any(path.startswith(p) for p in ("hdfs://", "gs://", "viewfs://", "file://", "s3://")):
        return path
    if path.startswith("/"):
        default_fs = getattr(ctx, "default_fs", "") or ""
        if default_fs and not default_fs.startswith("file://"):
            return default_fs.rstrip("/") + path
        return path
    # relative path
    working_dir = getattr(ctx, "working_dir", None) or os.getcwd()
    default_fs = getattr(ctx, "default_fs", "") or ""
    if default_fs and not default_fs.startswith("file://"):
        return f"{default_fs.rstrip('/')}/{working_dir.lstrip('/')}/{path}"
    return os.path.join(working_dir, path)
