"""TF-version compatibility shims, reinterpreted for the TPU stack.

Equivalent of the reference's ``tensorflowonspark/compat.py`` (~60 LoC),
which papered over TF 2.x API churn with ``export_saved_model``,
``disable_auto_shard`` and ``is_gpu_available``.  The rebuild keeps the same
three names so reference-era user code imports cleanly, mapping each to its
TPU-native meaning.
"""

from __future__ import annotations

import logging

logger = logging.getLogger(__name__)


def export_saved_model(model, export_dir: str, is_chief: bool = False):
    """Reference: ``compat.py::export_saved_model(model, dir, is_chief)``.

    ``model`` here is either a ``(fn, params, example_inputs)`` triple or a
    dict with those keys; delegates to :func:`checkpoint.export_model`
    (StableHLO export, the SavedModel equivalent).  Chief-only, like the
    reference.
    """
    from tensorflowonspark_tpu.checkpoint import export_model

    if isinstance(model, dict):
        fn, params, inputs = model["fn"], model["params"], model["example_inputs"]
    else:
        fn, params, inputs = model
    return export_model(export_dir, fn, params, inputs, is_chief=is_chief)


def disable_auto_shard(options) -> None:
    """Reference: ``compat.py::disable_auto_shard(options)`` — turned off
    tf.data auto-sharding under MultiWorkerMirrored.  SPMD JAX input
    pipelines shard explicitly (``ctx.executor_id`` / ``shard_batch``), so
    there is nothing to disable; kept as a no-op for source compatibility."""
    logger.debug("disable_auto_shard: no-op on the TPU stack")


def is_gpu_available() -> bool:
    """Reference: ``compat.py::is_gpu_available()``.  Interpreted as "is an
    accelerator available" — true for TPU or GPU backends."""
    import jax

    try:
        return jax.devices()[0].platform != "cpu"
    except RuntimeError:
        return False


# -- the rebuild's own API churn --------------------------------------------
# One installation is supported: jax/jaxlib 0.9.0 (libtpu 0.0.34, flax
# 0.12.3).  The names below stay the single place the ``parallel/`` modules
# reach jax's shard_map family through (``tfos-check``'s compat-discipline
# rule keys on them), so the next jax drift is absorbed here — but they
# carry no branch for a release that is not installed.

def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None, **kwargs):
    """``jax.shard_map``; ``check_vma=None`` leaves jax's default."""
    import jax

    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def axis_size(name):
    """``jax.lax.axis_size`` (``NameError`` on an axis no enclosing
    ``shard_map`` binds)."""
    from jax import lax

    return lax.axis_size(name)


def pcast(x, axes, *, to: str = "varying"):
    """``jax.lax.pcast``."""
    from jax import lax

    return lax.pcast(x, axes, to=to)


def vma_of(x) -> frozenset:
    """The varying-manual-axes set of ``x`` (``jax.typeof(x).vma``) — empty
    outside ``shard_map`` and under ``check_vma=False``."""
    import jax

    return frozenset(jax.typeof(x).vma)


def has_vma() -> bool:
    """Whether this jax has the varying-manual-axes type system
    (``jax.typeof`` + ``lax.pcast``): the installed one does."""
    return True


def bound_axes() -> tuple:
    """Axis names an enclosing ``shard_map`` binds (empty outside one) —
    the "am I inside shard_map" probe for code whose inputs carry no vma
    (``check_vma=False``)."""
    import jax

    return tuple(jax.sharding.get_abstract_mesh().manual_axes)
