"""Reference-facade parity: TFCluster/TFNode/TFManager/gpu_info/compat.

A reference user's imports and call shapes must work verbatim (SURVEY.md §2a
symbol names); these tests exercise each façade module end to end.
"""

import numpy as np
import pytest

from tests import cluster_funcs as funcs


def test_tfcluster_run_reference_signature(tmp_path):
    from tensorflowonspark_tpu import TFCluster

    cluster = TFCluster.run(
        None, funcs.fn_sum_feed, {"batch_size": 8}, 2, 0, False,
        TFCluster.InputMode.SPARK, reservation_timeout=60,
        worker_env={"JAX_PLATFORMS": "cpu"}, working_dir=str(tmp_path))
    cluster.train(list(range(40)), num_epochs=1)
    cluster.shutdown(timeout=120)
    total = 0
    for f in tmp_path.glob("sum.*"):
        s, n = f.read_text().split(":")
        total += int(s)
    assert total == sum(range(40))


def test_tfnode_surface():
    from tensorflowonspark_tpu import TFNode

    assert TFNode.DataFeed is not None
    assert callable(TFNode.hdfs_path)
    assert callable(TFNode.start_cluster_server)
    assert callable(TFNode.export_saved_model)


def test_tfmanager_start_connect():
    import secrets

    from tensorflowonspark_tpu import TFManager

    key = secrets.token_bytes(8)
    mgr = TFManager.start(key, ["input", "output", "error"], mode="remote")
    try:
        addr = mgr.addr
        client = TFManager.connect(addr, key)
        client.put("input", [1, 2, 3])
        assert mgr.queue_get("input", timeout=5) == [1, 2, 3]
        client.close()
    finally:
        mgr.stop()


def test_gpu_info_shim():
    from tensorflowonspark_tpu import gpu_info

    csv = gpu_info.get_gpus(1)
    assert isinstance(csv, str)
    assert gpu_info.MAX_RETRIES >= 1


def test_compat_shims(tmp_path):
    import jax.numpy as jnp

    from tensorflowonspark_tpu import compat
    from tensorflowonspark_tpu.checkpoint import ExportedModel

    compat.disable_auto_shard(object())  # no-op, must not raise
    assert isinstance(compat.is_gpu_available(), bool)

    def fn(params, x):
        return params["w"] * x

    out = compat.export_saved_model(
        (fn, {"w": jnp.asarray(2.0)}, [np.zeros((3,), np.float32)]),
        str(tmp_path / "exp"), is_chief=True)
    assert out is not None
    model = ExportedModel.load(str(tmp_path / "exp"))
    got = model(np.asarray([1.0, 2.0, 3.0], np.float32))
    np.testing.assert_allclose(list(got.values())[0], [2.0, 4.0, 6.0])


def test_tfsparknode_aliases():
    from tensorflowonspark_tpu import TFSparkNode
    from tensorflowonspark_tpu.node import NodeContext

    assert TFSparkNode.TFNodeContext is NodeContext
    assert callable(TFSparkNode.run)


def test_tfcluster_run_rejects_scless_signature():
    import pytest

    from tensorflowonspark_tpu import TFCluster

    with pytest.raises(TypeError, match="SparkContext"):
        TFCluster.run(funcs.fn_noop, {}, 2, 0)


def test_compat_shard_map_family_on_the_installed_jax():
    """The shard_map-family shims are thin names over the ONE installed
    jax (0.9.0) — no branch for another release: inside ``shard_map``
    they report the bound axes and the varying set, outside it nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from tensorflowonspark_tpu import compat

    assert compat.has_vma() is True
    assert compat.bound_axes() == ()
    assert compat.vma_of(jnp.ones(2)) == frozenset()
    with pytest.raises(NameError):
        compat.axis_size("sp")

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "sp"))
    seen = {}

    def body(x):
        seen["bound"] = set(compat.bound_axes())
        seen["vma"] = compat.vma_of(x)
        seen["n"] = compat.axis_size("sp")
        zeros = compat.pcast(jnp.zeros(x.shape), ("sp",), to="varying")
        seen["cast"] = compat.vma_of(zeros)
        return x + zeros

    out = compat.shard_map(body, mesh=mesh, in_specs=P(None, "sp"),
                           out_specs=P(None, "sp"))(jnp.ones((2, 4)))
    assert out.shape == (2, 4)
    assert seen == {"bound": {"dp", "sp"}, "vma": frozenset({"sp"}),
                    "n": 2, "cast": frozenset({"sp"})}
    # check_vma=False: no varying set to read, the axes are still bound
    compat.shard_map(body, mesh=mesh, in_specs=P(None, "sp"),
                     out_specs=P(None, "sp"), check_vma=False)(
                         jnp.ones((2, 4)))
    assert seen["bound"] == {"dp", "sp"}
