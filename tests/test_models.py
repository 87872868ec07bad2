"""Model zoo tests: forward shapes + one optimization step each, at toy sizes."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflowonspark_tpu.models import (Bert, BertConfig,
                                          BertForQuestionAnswering,
                                          BertForSequenceClassification,
                                          CifarResNet, MNISTNet, ResNet50,
                                          UNet, WideDeep)

# bfloat16 storage against float32 on the same weights, two toy blocks deep
BF16_LOGIT_ATOL = 0.05
BF16_GRAD_RTOL = 0.1
BF16_GRAD_ATOL = 2e-3     # leaf norms are 0.01-0.2; one is near 0

TINY_BERT = BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                       num_heads=4, intermediate_size=64,
                       max_position_embeddings=64, dtype=jnp.float32)

# The big-model smoke tests jit init/apply instead of running eagerly:
# eager dispatch of a deep conv net is the slow path on a 1-core box
# (inception eager ≈ 50 s), and only jitted programs land in the
# persistent compile cache conftest enables — cached re-runs of these
# tests are seconds, not minutes.


def test_mnist_forward_and_step():
    model = MNISTNet()
    x = jnp.zeros((4, 28, 28, 1))
    params = jax.jit(model.init)(jax.random.key(0), x)
    logits = jax.jit(model.apply)(params, x)
    assert logits.shape == (4, 10)

    def loss_fn(p):
        out = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            out, jnp.zeros(4, jnp.int32)).mean()

    g = jax.jit(jax.grad(loss_fn))(params)
    assert jnp.isfinite(jax.tree.reduce(lambda a, b: a + b.sum(), g, 0.0))


def test_cifar_resnet_forward_train_mode():
    model = CifarResNet(dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = jax.jit(partial(model.init, train=True))(
        jax.random.key(0), x)
    assert "batch_stats" in variables
    logits, updates = jax.jit(
        partial(model.apply, train=True, mutable=["batch_stats"]))(
            variables, x)
    assert logits.shape == (2, 10)
    assert "batch_stats" in updates


def test_resnet50_forward_shape():
    model = ResNet50(num_classes=1000, dtype=jnp.float32)
    x = jnp.zeros((1, 64, 64, 3))  # small spatial for test speed
    variables = jax.jit(model.init)(jax.random.key(0), x)
    logits = jax.jit(model.apply)(variables, x)
    assert logits.shape == (1, 1000)


def test_s2d_stem_exactly_matches_conv7_stem():
    """The space-to-depth stem is the 7×7/s2 stem under an exact weight
    transform (MLPerf ResNet trick) — same params everywhere else, full
    forward outputs must agree to float32 tolerance."""
    from tensorflowonspark_tpu.models.resnet import (ResNet, BasicBlock,
                                                     conv7_stem_to_s2d_kernel)

    k = dict(stage_sizes=(1, 1), block=BasicBlock, num_classes=7,
             dtype=jnp.float32)
    m7 = ResNet(**k)
    ms = ResNet(**k, stem="s2d")
    x = jax.random.normal(jax.random.key(0), (2, 64, 64, 3), jnp.float32)
    v7 = m7.init(jax.random.key(1), x)
    k7 = v7["params"]["Conv_0"]["kernel"]
    assert k7.shape == (7, 7, 3, 64)
    vs = {**v7, "params": {**v7["params"],
                           "Conv_0": {"kernel": conv7_stem_to_s2d_kernel(k7)}}}
    out7 = m7.apply(v7, x)
    outs = ms.apply(vs, x)
    np.testing.assert_allclose(np.asarray(outs), np.asarray(out7),
                               rtol=1e-5, atol=1e-5)


def test_s2d_stem_trains_from_scratch():
    from tensorflowonspark_tpu.models.resnet import ResNet, BasicBlock

    model = ResNet(stage_sizes=(1, 1), block=BasicBlock, num_classes=5,
                   stem="s2d", dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.key(0), x, train=True)
    assert variables["params"]["Conv_0"]["kernel"].shape == (4, 4, 12, 64)
    logits, _ = model.apply(variables, x, train=True,
                            mutable=["batch_stats"])
    assert logits.shape == (2, 5)


@pytest.mark.parametrize("block", ["Bottleneck", "BasicBlock"])
@pytest.mark.parametrize("dtype,norm_dtype", [
    (jnp.bfloat16, None), (jnp.float32, None), (jnp.bfloat16, jnp.float32)],
    ids=["bf16", "f32", "bf16-norm-f32"])
def test_resnet_stores_activations_in_its_dtype(dtype, norm_dtype, block):
    """What lies between two layers is stored in the model's ``dtype``
    (an explicit ``norm_dtype`` wins); parameters and statistics stay
    float32; a float32 model is the program it was; a bfloat16 model
    agrees with the float32 one on the same weights."""
    from tensorflowonspark_tpu.models import resnet

    k = dict(stage_sizes=(1, 1), block=getattr(resnet, block), num_classes=5,
             num_filters=8)
    model = resnet.ResNet(**k, dtype=dtype, norm_dtype=norm_dtype)
    full = resnet.ResNet(**k, dtype=jnp.float32, norm_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (4, 32, 32, 3), jnp.float32)
    labels = jnp.arange(4) % 5
    variables = jax.jit(partial(model.init, train=True))(jax.random.key(1), x)
    # a block's last scale starts at 0, which silences the block
    variables = jax.tree.map(lambda p: jnp.where(p == 0, 0.2, p), variables)

    @partial(jax.jit, static_argnums=0)
    def loss_and_grad(m, params):
        def loss_fn(params):
            logits, state = m.apply(
                {**variables, "params": params}, x, train=True,
                mutable=["batch_stats", "intermediates"],
                capture_intermediates=True)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            return loss, (logits, state)
        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    (_, (logits, state)), grads = loss_and_grad(model, variables["params"])
    stored = jnp.dtype(dtype if norm_dtype is None else norm_dtype)
    held = {tuple(k.key for k in path[:-2]): leaf.dtype for path, leaf
            in jax.tree_util.tree_leaves_with_path(state["intermediates"])}
    norms = [name for name in held if name and "BatchNorm" in name[-1]]
    blocks = [name for name in held if len(name) == 1 and block in name[0]]
    assert len(blocks) == 2, held
    assert len(norms) == (9 if block == "Bottleneck" else 6), held
    assert {held[name] for name in norms + blocks} == {stored}, held
    assert logits.dtype == jnp.float32
    for leaf in jax.tree.leaves((variables["params"], grads,
                                 state["batch_stats"])):
        assert leaf.dtype == jnp.float32

    (_, (full_logits, _)), full_grads = loss_and_grad(
        full, variables["params"])
    if jnp.dtype(dtype) == jnp.float32:
        # the rule changes nothing where the caller asked for float32
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(full_logits))
        return
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full_logits),
                               atol=BF16_LOGIT_ATOL)

    def leaf_norms(g):
        return np.asarray([jnp.linalg.norm(leaf)
                           for leaf in jax.tree.leaves(g)])

    np.testing.assert_allclose(leaf_norms(grads), leaf_norms(full_grads),
                               rtol=BF16_GRAD_RTOL, atol=BF16_GRAD_ATOL)


def test_unet_preserves_spatial_dims():
    model = UNet(num_classes=3, features=(8, 16, 32), dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 1))
    variables = jax.jit(model.init)(jax.random.key(0), x)
    out = jax.jit(model.apply)(variables, x)
    assert out.shape == (2, 32, 32, 3)


def test_bert_trunk_and_heads():
    ids = jnp.ones((2, 16), jnp.int32)
    mask = jnp.ones((2, 16), bool)
    trunk = Bert(TINY_BERT)
    params = trunk.init(jax.random.key(0), ids, mask)
    hidden = trunk.apply(params, ids, mask)
    assert hidden.shape == (2, 16, 32)

    qa = BertForQuestionAnswering(TINY_BERT)
    qp = qa.init(jax.random.key(1), ids, mask)
    start, end = qa.apply(qp, ids, mask)
    assert start.shape == end.shape == (2, 16)

    cls = BertForSequenceClassification(TINY_BERT, num_classes=3)
    cp = cls.init(jax.random.key(2), ids, mask)
    assert cls.apply(cp, ids, mask).shape == (2, 3)


def test_bert_scan_layers_stacked_params_and_grads():
    """scan_layers+remat: one stacked block, masked attention still works,
    gradients reach every leaf."""
    import dataclasses

    cfg = dataclasses.replace(TINY_BERT, scan_layers=True, remat=True)
    ids = jnp.ones((2, 8), jnp.int32)
    mask = jnp.array([[1] * 6 + [0] * 2] * 2, bool)
    trunk = Bert(cfg)
    params = trunk.init(jax.random.key(0), ids, mask)
    assert "layers" in params["params"] and "layer_0" not in params["params"]
    stacked = jax.tree.leaves(params["params"]["layers"])[0]
    assert stacked.shape[0] == cfg.num_layers

    out = trunk.apply(params, ids, mask)
    assert out.shape == (2, 8, cfg.hidden_size)
    g = jax.grad(lambda p: jnp.mean(trunk.apply(p, ids, mask) ** 2))(params)
    assert all(float(jnp.abs(x).sum()) > 0 for x in jax.tree.leaves(g))

    # mask participates on the scan path too
    full = trunk.apply(params, ids, jnp.ones((2, 8), bool))
    assert not np.allclose(np.asarray(full[:, :6]), np.asarray(out[:, :6]))


@pytest.mark.parametrize("train", [False, True])
def test_bert_loop_remat_gradients(train):
    """Regression (same class as the GPT r5 fix): the loop branch's
    ``nn.remat(EncoderLayer)`` must mark ``train`` static — a traced
    kwarg breaks ``deterministic=not train`` with
    ``TracerBoolConversionError`` under jit."""
    import dataclasses

    cfg = dataclasses.replace(TINY_BERT, scan_layers=False, remat=True)
    ids = jnp.ones((2, 8), jnp.int32)
    mask = jnp.array([[1] * 6 + [0] * 2] * 2, bool)
    trunk = Bert(cfg)
    params = trunk.init(jax.random.key(0), ids, mask)

    def loss(p):
        out = trunk.apply(
            p, ids, mask, train=train,
            rngs={"dropout": jax.random.key(3)} if train else None)
        return jnp.mean(out.astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss))(params)
    assert all(float(jnp.abs(x).sum()) > 0 for x in jax.tree.leaves(g))


def test_bert_attention_mask_blocks_padding():
    ids = jnp.ones((1, 8), jnp.int32)
    trunk = Bert(TINY_BERT)
    params = trunk.init(jax.random.key(0), ids)
    full = trunk.apply(params, ids, jnp.ones((1, 8), bool))
    # padding tokens masked out: outputs at unmasked positions must differ
    # from the all-visible case if mask actually participates
    half = trunk.apply(params, ids, jnp.array([[1, 1, 1, 1, 0, 0, 0, 0]], bool))
    assert not np.allclose(np.asarray(full[:, :4]), np.asarray(half[:, :4]))


def test_bert_with_ring_attention(jax_cpu_mesh_devices):
    from functools import partial

    from tensorflowonspark_tpu.parallel import make_mesh, ring_self_attention

    mesh = make_mesh(sp=4)
    cfg_ring = BertConfig(vocab_size=128, hidden_size=32, num_layers=1,
                          num_heads=4, intermediate_size=64,
                          max_position_embeddings=64, dtype=jnp.float32,
                          dropout_rate=0.0,
                          attention_fn=partial(ring_self_attention, mesh))
    cfg_dense = dataclasses.replace(cfg_ring, attention_fn=None)
    ids = jnp.ones((2, 32), jnp.int32)
    model_ring = Bert(cfg_ring)
    model_dense = Bert(cfg_dense)
    params = model_dense.init(jax.random.key(0), ids)
    out_dense = model_dense.apply(params, ids)
    out_ring = model_ring.apply(params, ids)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense),
                               rtol=2e-4, atol=2e-5)


def test_bert_ring_attention_respects_mask(jax_cpu_mesh_devices):
    """Regression: the custom attention_fn path must consume the padding
    mask (it was silently dropped before)."""
    from functools import partial

    from tensorflowonspark_tpu.parallel import make_mesh, ring_self_attention

    mesh = make_mesh(sp=4)
    cfg_ring = BertConfig(vocab_size=128, hidden_size=32, num_layers=1,
                          num_heads=4, intermediate_size=64,
                          max_position_embeddings=64, dtype=jnp.float32,
                          dropout_rate=0.0,
                          attention_fn=partial(ring_self_attention, mesh))
    cfg_dense = dataclasses.replace(cfg_ring, attention_fn=None)
    ids = jnp.ones((2, 32), jnp.int32)
    mask = jnp.arange(32)[None, :] < 20
    mask = jnp.broadcast_to(mask, (2, 32))
    params = Bert(cfg_dense).init(jax.random.key(0), ids)
    out_dense = Bert(cfg_dense).apply(params, ids, mask)
    out_ring = Bert(cfg_ring).apply(params, ids, mask)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense),
                               rtol=2e-4, atol=2e-5)
    # and the mask must actually change the result
    out_nomask = Bert(cfg_ring).apply(params, ids)
    assert not np.allclose(np.asarray(out_ring), np.asarray(out_nomask))


def test_wide_deep_forward_and_grad():
    model = WideDeep(vocab_sizes=(50, 30, 20), embed_dim=4, mlp_dims=(16, 8),
                     num_dense=5)
    dense = jnp.ones((4, 5))
    cat = jnp.array([[0, 1, 2]] * 4, jnp.int32)
    params = model.init(jax.random.key(0), dense, cat)
    logit = model.apply(params, dense, cat)
    assert logit.shape == (4,)

    def loss_fn(p):
        out = model.apply(p, dense, cat)
        return optax.sigmoid_binary_cross_entropy(out, jnp.ones(4)).mean()

    g = jax.grad(loss_fn)(params)
    leaves = jax.tree.leaves(g)
    assert leaves and all(jnp.isfinite(l).all() for l in leaves)


def test_inception_v3_forward_shape():
    from tensorflowonspark_tpu.models import InceptionV3

    model = InceptionV3(num_classes=11, dtype=jnp.float32)
    x = jnp.zeros((1, 75, 75, 3))  # smallest supported spatial extent
    variables = jax.jit(lambda x: model.init(
        {"params": jax.random.key(0), "dropout": jax.random.key(1)},
        x, train=True))(x)
    assert "batch_stats" in variables
    logits, updates = jax.jit(lambda v, x: model.apply(
        v, x, train=True, mutable=["batch_stats"],
        rngs={"dropout": jax.random.key(1)}))(variables, x)
    assert logits.shape == (1, 11)
    assert "batch_stats" in updates
    # inference path: no dropout rng needed
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (1, 11)


def test_inception_v3_aux_head_canonical_size():
    from tensorflowonspark_tpu.models import InceptionV3

    model = InceptionV3(num_classes=7, aux_logits=True, dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((2, 299, 299, 3), jnp.float32)

    def init(x):
        return model.init({"params": jax.random.key(0),
                           "dropout": jax.random.key(1)}, x, train=True)

    variables = jax.eval_shape(init, x)

    def fwd(v, x):
        return model.apply(v, x, train=True, mutable=["batch_stats"],
                           rngs={"dropout": jax.random.key(1)})

    (out, _updates) = jax.eval_shape(fwd, variables, x)
    logits, aux = out
    assert logits.shape == (2, 7)
    assert aux.shape == (2, 7)
