"""Expert-parallel MoE: the all_to_all dispatch path vs a local oracle.

Routing and capacity are decided per token-shard from local information
only, so the exact oracle for an ``ep``-sharded run is ``moe_fn`` itself
built with ``ep=1`` (all experts local, no collectives) applied to each
shard's tokens on one device.  The distributed path — one-hot dispatch,
two ``all_to_all`` hops, per-owner expert compute — must reproduce it
bit-for-bit in values AND parameter gradients.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.parallel import make_mesh
from tensorflowonspark_tpu.parallel.mesh import MeshSpec
from tensorflowonspark_tpu.parallel.moe import make_moe_layer, moe_apply

HID, FFN, EXPERTS = 8, 16, 4


@pytest.mark.parametrize("ep,dp,top_k", [(2, 1, 1), (2, 2, 2), (4, 1, 2)])
def test_moe_matches_local_oracle(ep, dp, top_k):
    mesh = make_mesh(MeshSpec(ep=ep, dp=dp),
                     devices=jax.devices()[:ep * dp])
    moe_fn, init_fn, param_specs = make_moe_layer(
        HID, FFN, EXPERTS, top_k=top_k, ep=ep)
    oracle_fn, _, _ = make_moe_layer(HID, FFN, EXPERTS, top_k=top_k, ep=1)
    params = init_fn(jax.random.key(0))

    shards = ep * dp
    t_local = 6
    x = jax.random.normal(jax.random.key(1), (shards * t_local, HID))

    y, aux = moe_apply(mesh, moe_fn, params, x, param_specs=param_specs)

    # oracle: each token shard routed independently with all experts local.
    # token order on the mesh axis (dp, ep): dp is the outer axis.
    y_parts, aux_parts = [], []
    for s in range(shards):
        xs = x[s * t_local:(s + 1) * t_local]
        ys, auxs = oracle_fn(params, xs)
        y_parts.append(ys)
        aux_parts.append(auxs)
    y_ref = jnp.concatenate(y_parts)
    aux_ref = jnp.mean(jnp.stack(aux_parts))

    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)

    # ---- gradients ----
    def loss_dist(p):
        y, aux = moe_apply(mesh, moe_fn, p, x, param_specs=param_specs)
        return jnp.mean(y ** 2) + 0.01 * aux

    def loss_ref(p):
        parts = [oracle_fn(p, x[s * t_local:(s + 1) * t_local])
                 for s in range(shards)]
        y = jnp.concatenate([p_[0] for p_ in parts])
        aux = jnp.mean(jnp.stack([p_[1] for p_ in parts]))
        return jnp.mean(y ** 2) + 0.01 * aux

    g_dist = jax.jit(jax.grad(loss_dist))(params)
    g_ref = jax.grad(loss_ref)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-6),
        jax.device_get(g_dist), jax.device_get(g_ref))


def test_moe_capacity_drops_tokens():
    """With a tiny capacity factor, overflow tokens are dropped (zero
    output), never mis-routed."""
    moe_fn, init_fn, _ = make_moe_layer(
        HID, FFN, EXPERTS, top_k=1, capacity_factor=0.25, ep=1)
    params = init_fn(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (16, HID))
    y, _ = moe_fn(params, x)
    # capacity = 0.25*16*1/4 = 1 slot per expert -> at most 4 nonzero rows
    nonzero = np.count_nonzero(np.abs(np.asarray(y)).sum(-1) > 1e-7)
    assert nonzero <= EXPERTS


def test_moe_rejects_bad_expert_count():
    with pytest.raises(ValueError, match="must divide"):
        make_moe_layer(HID, FFN, 6, ep=4)


# -- the served expert layer (models/moe.py): which grouped product it runs --
# ``parallel/moe.py`` above is another layer; this one is the dropless
# SparseMoE of a served decoder block, whose grouped products are the
# kernel ``ops.grouped_matmul`` on one TPU and ``ragged_dot`` elsewhere.

from tensorflowonspark_tpu.models import GPTConfig  # noqa: E402
from tensorflowonspark_tpu.models import moe as served  # noqa: E402


def _expert_cfg(**kw):
    return GPTConfig(**{**dict(
        vocab_size=61, hidden_size=32, num_layers=2, num_heads=4,
        intermediate_size=64, max_position_embeddings=64, num_experts=8,
        num_experts_per_tok=4, moe_intermediate_size=16,
        num_dense_layers=0, mlp="swiglu", dtype=jnp.float32), **kw})


def _layer_text(cfg, rows, tokens):
    """The jaxpr of one expert layer over ``[rows, tokens]``, as text."""
    layer = served.SparseMoE(cfg)
    x = jax.ShapeDtypeStruct((rows, tokens, cfg.hidden_size), cfg.dtype)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.key(0), jnp.zeros(x.shape, x.dtype)))["params"]
    return str(jax.make_jaxpr(lambda p, x: layer.apply(
        {"params": p}, x, mutable=[served.STATS]))(params, x))


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The rule sees a TPU backend; the kernel still sees the CPU and
    would run under the interpreter."""
    monkeypatch.setattr(served, "_on_tpu", lambda: True)


@pytest.mark.parametrize("why,rows,tokens", [
    ("a decode step: 32 rows of one token, 4 assignments an expert", 32, 1),
    ("a one-row prefill of the 1024 bucket, 128 an expert", 1, 1024),
    ("a four-row prefill of the 1024 bucket, 512 an expert", 4, 1024),
])
def test_on_one_tpu_the_expert_layer_runs_the_kernel_at_every_shape(
        as_on_tpu, why, rows, tokens):
    cfg = _expert_cfg()
    assert served.streams_experts_once(), why
    assert served.grouped_matmul_calls(cfg) == 2 * cfg.num_expert_layers == 4
    text = _layer_text(cfg, rows, tokens)
    assert "ragged_dot" not in text and text.count("pallas_call") == 2, why


def test_off_the_tpu_the_expert_layer_keeps_ragged_dot():
    cfg = _expert_cfg()
    assert not served.streams_experts_once()
    assert served.grouped_matmul_calls(cfg) == 0
    assert _layer_text(cfg, 32, 1).count("ragged_dot_general[") == 3


def test_a_mesh_of_several_devices_keeps_ragged_dot(as_on_tpu):
    from jax.sharding import Mesh

    cfg = _expert_cfg()
    devs = np.asarray(jax.devices())
    if devs.size < 2:
        pytest.skip("one device")
    with Mesh(devs[:2].reshape(1, 2), ("dp", "tp")):
        assert not served.streams_experts_once()
        assert served.grouped_matmul_calls(cfg) == 0
        assert _layer_text(cfg, 32, 1).count("ragged_dot_general[") == 3
    with Mesh(devs[:1].reshape(1, 1), ("dp", "tp")):
        assert served.streams_experts_once()


def test_a_model_without_experts_holds_no_kernel_call(as_on_tpu):
    dense = GPTConfig(num_layers=2, hidden_size=32, num_heads=2,
                      vocab_size=50, max_position_embeddings=32)
    assert served.grouped_matmul_calls(dense) == 0


@pytest.mark.parametrize("rows,tokens", [(32, 1), (2, 24)])
def test_both_paths_compute_the_same_layer_and_sow_the_same_counts(
        monkeypatch, rows, tokens):
    cfg = _expert_cfg()
    layer = served.SparseMoE(cfg)
    x = jax.random.normal(jax.random.key(1), (rows, tokens, 32), jnp.float32)
    params = layer.init(jax.random.key(0), x)["params"]
    with jax.default_matmul_precision("highest"):
        want, stats = layer.apply({"params": params}, x,
                                  mutable=[served.STATS])
        monkeypatch.setattr(served, "_on_tpu", lambda: True)
        got, kstats = layer.apply({"params": params}, x,
                                  mutable=[served.STATS])
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert jax.tree.leaves(kstats)[0].tolist() \
        == jax.tree.leaves(stats)[0].tolist() \
        and jax.tree.leaves(stats)[0][0] == rows * tokens * 4


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "the kernel"])
@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
def test_first_matrices_stored_hidden_axis_last_give_the_same_layer(
        monkeypatch, kernel, activation):
    """``moe_up_transposed`` says how ``w_up`` (and a gated expert's
    ``w_gate``) are stored, whatever the activation, and changes nothing
    else."""
    cfg = _expert_cfg(moe_activation=activation)
    x = jax.random.normal(jax.random.key(1), (2, 24, 32), jnp.float32)
    params = served.SparseMoE(cfg).init(jax.random.key(0), x)["params"]
    assert params["w_up"].shape == (8, 32, 16)
    assert ("w_gate" in params) == (activation == "swiglu")
    stored = {k: jnp.swapaxes(v, 1, 2) if k in ("w_up", "w_gate") else v
              for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = served.SparseMoE(cfg).apply(
            {"params": params}, x, mutable=[served.STATS])
        if kernel:
            monkeypatch.setattr(served, "_on_tpu", lambda: True)
        got, _ = served.SparseMoE(
            dataclasses.replace(cfg, moe_up_transposed=True)).apply(
                {"params": stored}, x, mutable=[served.STATS])
    np.testing.assert_allclose(got, want, atol=1e-6)
