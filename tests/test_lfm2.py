"""The layer pattern (short convolution + attention), per-head q/k norm and
the dropless expert layer, against the plain float32 reference
(``benchmark/reference/lfm2.py``, imported here for the CPU comparison), at
a small size with every feature on and seeded random weights.  Comparisons
are of LOGITS: the batcher's cache is probed with the tokens it would feed
next, and what comes back is held against the reference's full forward over
the whole sequence so far."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import lfm2 as ref
from tensorflowonspark_tpu.models import (GPT, ContinuousBatcher, DraftModel,
                                          GPTConfig, init_cache,
                                          lookup_generate)
from tensorflowonspark_tpu.models import moe
from tensorflowonspark_tpu.models.gpt import rewind_cache

adapter = harness.load_module("models", "lfm2")

CFG = {"hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 4,
       "layer_types": ["conv", "conv", "full_attention", "conv"],
       "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
       "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
       "moe_intermediate_size": 48, "norm_eps": 1e-5, "rope_theta": 1e6,
       "vocab_size": 211, "max_position_embeddings": 64, "dtype": "float32",
       "init_std": 0.3, "expert_bias_std": 0.3}
#: float32 everywhere: what differs from the reference is the order of
#: sums (the cache, the grouped matmuls), a few 1e-6 of logits of size ~10
TOL = 2e-4


@pytest.fixture(scope="module")
def made():
    with jax.default_matmul_precision("highest"):
        return adapter.gpt_config(CFG), ref.make_weights(3, CFG)


def _ref_last(params, seq):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            params, jnp.asarray(seq, jnp.int32)[None], CFG)[0, -1])


def _prompt(i, n):
    return np.random.default_rng([7, i]).integers(0, 211, n).astype(np.int32)


def test_full_forward_matches_the_reference():
    """Eight layers, two periods of the pattern; the batcher tests below
    run one period (a CPU compile per program is what they cost)."""
    CFG = dict(globals()["CFG"], num_hidden_layers=8,
               layer_types=["conv", "conv", "full_attention", "conv"] * 2)
    with jax.default_matmul_precision("highest"):
        cfg, params = adapter.gpt_config(CFG), ref.make_weights(3, CFG)
    ids = jnp.asarray(np.stack([_prompt(0, 23), _prompt(1, 23)]))
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, ids, CFG)
        got = GPT(cfg).apply({"params": params}, ids)
    np.testing.assert_allclose(got, want, atol=TOL)
    # the program's own initialiser lays the same tree out
    import flax

    init = flax.core.meta.unbox(
        jax.eval_shape(lambda: GPT(cfg).init(jax.random.key(0), ids)))
    assert jax.tree.map(lambda a: a.shape, init["params"]) \
        == jax.tree.map(lambda a: a.shape, params)


# ------------------------------------------------------------ expert layer

def _moe_setup(bias=None, n=12):
    cfg = dataclasses.replace(adapter.gpt_config(CFG), num_layers=2,
                              layer_types=None, num_dense_layers=0)
    layer = moe.SparseMoE(cfg)
    u = jax.random.normal(jax.random.key(1), (2, n // 2, 64), jnp.float32)
    p = ref.make_layer(ref.seed_key(5), CFG, 3)["moe"]
    if bias is not None:
        p = dict(p, expert_bias=jnp.asarray(bias, jnp.float32))
    return layer, p, u


def _moe_ref(p, u):
    with jax.default_matmul_precision("highest"):
        return ref.experts(u, jax.tree.map(
            lambda a: a.astype(jnp.float32), p), CFG)


def test_the_router_bias_selects_and_does_not_weight():
    layer, p, u = _moe_setup()
    with jax.default_matmul_precision("highest"):
        flat = u.reshape(-1, 64)
        sel, w = moe.route(flat, p["router"], p["expert_bias"], 2)
        sel0, _ = moe.route(flat, p["router"], jnp.zeros(8), 2)
        prob = jax.nn.sigmoid(flat @ p["router"])
    # the drawn bias changes some selections ...
    assert (np.sort(sel, -1) != np.sort(sel0, -1)).any()
    # ... the chosen are the top of p + bias ...
    want = np.argsort(-(np.asarray(prob) + np.asarray(p["expert_bias"])),
                      axis=-1)[:, :2]
    assert (np.sort(sel, -1) == np.sort(want, -1)).all()
    # ... and the weights are of p alone, normalised over the chosen
    chosen = np.take_along_axis(np.asarray(prob), np.asarray(sel), -1)
    np.testing.assert_allclose(
        w, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # the same shift on every expert's bias selects nothing else
    sel1, w1 = moe.route(flat, p["router"], p["expert_bias"] + 3.0, 2)
    assert (sel1 == sel).all()
    np.testing.assert_allclose(w1, w, rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": p}, u)
    np.testing.assert_allclose(got, _moe_ref(p, u)[0], atol=2e-5)


def test_no_assignment_is_dropped_when_every_row_picks_the_same_experts():
    """A capacity would drop here: all rows choose experts 0 and 5."""
    bias = np.zeros(8)
    bias[[0, 5]] = 100.0
    layer, p, u = _moe_setup(bias, n=40)
    with jax.default_matmul_precision("highest"):
        got, stats = layer.apply({"params": p}, u, mutable=[moe.STATS])
    want, sel = _moe_ref(p, u)
    assert (np.asarray(sel) == np.array([0, 5])).all()
    np.testing.assert_allclose(got, want, atol=2e-5)
    # assignments made, the busiest expert's, experts touched, and the
    # assignments that fell to experts held here (all of them: every
    # expert is held)
    assert jax.tree.leaves(stats)[0].tolist() == [80, 40, 2, 80]


# ---------------------------------------------------------------- refusals

def _refused(made, what):
    cfg, params = made
    paged = dict(kv_page_tokens=4, prefix_cache=False)
    if what == "prefix-cache":
        ContinuousBatcher(cfg, params, max_batch=2, prefix_cache=True)
    elif what == "speculative_k":
        ContinuousBatcher(cfg, params, max_batch=2, speculative_k=2)
    elif what == "prefill_only":
        ContinuousBatcher(cfg, params, max_batch=2, prefill_only=True,
                          **paged)
    elif what == "set_draft":
        small = dataclasses.replace(cfg, layer_types=None, num_experts=None,
                                    num_layers=1)
        ContinuousBatcher(cfg, params, max_batch=2, **paged).set_draft(
            DraftModel(small, None, window=8))
    elif what == "adopt_session":
        ContinuousBatcher(cfg, params, max_batch=2, **paged).adopt_session(
            {"v": 1})
    elif what == "set_role":
        ContinuousBatcher(cfg, params, max_batch=2, **paged).set_role(
            "prefill")
    elif what == "lookup_generate":
        lookup_generate(cfg, params, jnp.zeros((1, 4), jnp.int32), 4)
    elif what == "rewind_cache":
        rewind_cache(init_cache(cfg, params, 1), 0)
    elif what == "scan_layers":
        dataclasses.replace(cfg, scan_layers=True)
    elif what == "mesh":
        from tensorflowonspark_tpu.serving.sharded import \
            default_shard_params

        default_shard_params(cfg, params, None)


@pytest.mark.parametrize("what", [
    "prefix-cache", "speculative_k", "prefill_only", "set_draft",
    "adopt_session", "set_role", "lookup_generate", "rewind_cache",
    "scan_layers", "mesh"])
def test_what_the_conv_state_cannot_follow_refuses_loudly(made, what):
    with pytest.raises(ValueError) as e:
        _refused(made, what)
    # a refusal names the layer type that caused it
    assert "conv" in str(e.value)


def test_errors_state_the_cache_kinds(made):
    cfg, params = made
    assert "conv_state of 3 conv layer(s)" in cfg.cache_kinds
    assert "K/V of 1 full_attention layer(s)" in cfg.cache_kinds
    dense = GPTConfig(num_layers=2, hidden_size=32, num_heads=2,
                      vocab_size=50)
    assert dense.cache_kinds == \
        "K/V of 2 full_attention layer(s) (positional, rewindable)"
    dense_params = GPT(dense).init(jax.random.key(0),
                                   jnp.zeros((1, 2), jnp.int32))["params"]
    b = ContinuousBatcher(dense, dense_params, max_batch=1)
    with pytest.raises(ValueError, match="K/V of 2 full_attention"):
        b.set_draft(DraftModel(dense, dense_params, window=4))


def test_conv_layers_turn_the_prefix_index_off_by_default(made):
    """What the code can work out is no option: with conv layers the
    shared-prefix index is off unless asked for, and asking refuses,
    naming the state that shared pages cannot carry."""
    cfg, params = made
    b = ContinuousBatcher(cfg, params, max_batch=2)
    assert b._pages.prefix_cache is False
    assert b.export_prefix_cache() is None
    with pytest.raises(ValueError) as e:
        ContinuousBatcher(cfg, params, max_batch=2, prefix_cache=True)
    assert cfg.cache_kinds in str(e.value)


def test_config_validation():
    kw = dict(num_layers=2, hidden_size=32, num_heads=2, vocab_size=50)
    with pytest.raises(ValueError, match="layer_types"):
        GPTConfig(layer_types=("conv",), **kw)
    with pytest.raises(ValueError, match="layer_types"):
        GPTConfig(layer_types=("conv", "window"), **kw)
    with pytest.raises(ValueError, match="num_experts"):
        GPTConfig(num_experts=4, num_experts_per_tok=2, **kw)
    with pytest.raises(ValueError, match="prefill_rows_max"):
        cfg = GPTConfig(**kw)
        ContinuousBatcher(cfg, None, max_batch=2, prefill_rows_max=3)
    assert GPTConfig(layer_types=["conv", "full_attention"],
                     **kw).layer_types == ("conv", "full_attention")


def test_decode_program_carries_the_new_scopes(made):
    cfg, params = made
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=4,
                          prefix_cache=False)
    text = b._step.lower(params, b.cache, jnp.zeros((2,), jnp.int32)
                         ).as_text(debug_info=True)
    for scope in ("conv/in_proj", "conv/mix", "conv/state_store",
                  "conv/out_proj", "moe/router", "moe/dispatch",
                  "moe/experts", "moe/combine", "attn/qk_norm"):
        assert f"/{scope}" in text, scope


def test_a_dense_model_builds_the_program_it_built(made):
    """The new fields at their defaults add nothing to a dense model's
    decode step: no stats ride its tokens, no conv leaf is in its cache."""
    dense = GPTConfig(num_layers=2, hidden_size=32, num_heads=2,
                      vocab_size=50, max_position_embeddings=32)
    params = GPT(dense).init(jax.random.key(0),
                             jnp.zeros((1, 2), jnp.int32))["params"]
    b = ContinuousBatcher(dense, params, max_batch=2, kv_page_tokens=4)
    out, _ = jax.eval_shape(b._step, params, b.cache,
                            jnp.zeros((2,), jnp.int32))
    assert out.shape == (2,)
    keys = {getattr(p[-1], "key", None) for p, _ in
            jax.tree_util.tree_flatten_with_path(b.cache)[0]}
    assert keys == {"index", "pos", "block_table", "k", "v"}
    text = b._step.lower(params, b.cache, jnp.zeros((2,), jnp.int32)
                         ).as_text()
    assert "ragged" not in text and "conv_state" not in text
