"""tensorflowonspark_tpu — a TPU-native rebuild of TensorFlowOnSpark.

Re-implements the capabilities of ``dailong/TensorFlowOnSpark`` (reference:
``tensorflowonspark/`` package — see SURVEY.md) as an idiomatic JAX/XLA/TPU
framework.  Where the reference co-locates one TensorFlow node per Spark
executor and feeds it RDD partitions through multiprocessing queues, this
package co-locates one JAX process per TPU host, bootstraps the cluster via a
TCP rendezvous + ``jax.distributed``, and feeds data through batch-granularity
socket queues into the device infeed.

Public API (mirrors the reference's user-facing contract,
``tensorflowonspark/TFCluster.py`` / ``TFNode.py`` / ``pipeline.py``):

    from tensorflowonspark_tpu import TPUCluster, InputMode
    cluster = TPUCluster.run(map_fun, args, num_workers, input_mode=InputMode.SPARK)
    cluster.train(data, num_epochs)
    preds = cluster.inference(data)
    cluster.shutdown()

Inside ``map_fun(args, ctx)`` the user pulls data with ``ctx.get_data_feed()``
(the ``TFNode.DataFeed`` equivalent).
"""

__version__ = "0.1.0"

# Importing this package never imports jax: the driver process stays off
# the accelerator, which belongs to one process at a time — the worker
# that runs the user's map_fun (tests/test_chip_ownership.py pins this).
from tensorflowonspark_tpu.cluster import (InputMode, TPUCluster,  # noqa: F401
                                           run_with_recovery)
from tensorflowonspark_tpu.datafeed import DataFeed  # noqa: F401
from tensorflowonspark_tpu.health import (ClusterFailure, ClusterMonitor,  # noqa: F401
                                          HeartbeatReporter)
from tensorflowonspark_tpu.node import NodeContext  # noqa: F401
from tensorflowonspark_tpu.checkpoint import (CheckpointManager, ExportedModel,  # noqa: F401
                                              export_model, restore_checkpoint,
                                              save_checkpoint)

from tensorflowonspark_tpu.data import Dataset, device_prefetch  # noqa: F401
from tensorflowonspark_tpu.dataframe import DataFrame, Row  # noqa: F401
from tensorflowonspark_tpu.estimator import (Estimator, EvalSpec,  # noqa: F401
                                             TrainSpec, train_and_evaluate)
from tensorflowonspark_tpu.preemption import PreemptionGuard  # noqa: F401
from tensorflowonspark_tpu.pipeline import (Namespace, Pipeline,  # noqa: F401
                                            ParamGridBuilder, TFEstimator,
                                            TFModel, TrainValidationSplit,
                                            CrossValidator)

# Reference-named façade modules: a reference user's
# ``from tensorflowonspark import TFCluster, TFNode`` maps 1:1 onto
# ``from tensorflowonspark_tpu import TFCluster, TFNode`` (module objects
# with the reference's entry points — TFCluster.run(sc, ...),
# TFNode.DataFeed, TFManager.start/connect, gpu_info.get_gpus, compat.*).
from tensorflowonspark_tpu import (TFCluster, TFManager, TFNode,  # noqa: F401,E402
                                   TFSparkNode, compat, gpu_info)

# Online serving tier (docs/serving.md): ServingCluster / ServeClient over
# ContinuousBatcher replicas.  Safe to import eagerly — the replica-side
# jax/model imports happen inside the worker map_fun, not at import time.
from tensorflowonspark_tpu import serving  # noqa: F401,E402

# Telemetry plane (docs/observability.md): process-local metrics registry
# with heartbeat-carried aggregation + Prometheus exposition, and
# end-to-end request tracing with the tfos_trace timeline stitcher.
from tensorflowonspark_tpu import metrics, tracing  # noqa: F401,E402

# Batch-inference plane (docs/batch.md): manifest-driven shard streaming
# with per-shard checkpointed progress and resumable bulk predict.  Safe
# to import eagerly — worker-side jax/model imports happen in the map_fun.
from tensorflowonspark_tpu import batch  # noqa: F401,E402

# Continual-learning loop (docs/continual.md): a standing
# train→eval→rollout pipeline — checkpoint publication into the model
# registry, offline gating on the batch plane, journaled live rollout.
from tensorflowonspark_tpu import continual  # noqa: F401,E402
