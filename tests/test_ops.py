"""Pallas flash-attention kernel vs the dense oracle (interpret mode on CPU).

Mirrors the reference's test posture of exercising real code paths without
special hardware (SURVEY.md §4: `local-cluster` on one machine); here the
kernels run under the Pallas interpreter so CI needs no TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import flash_attention
from tensorflowonspark_tpu.parallel.ring_attention import reference_attention


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


def _qkv(seed, B, T, H, D, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (_rand(ks[0], B, T, H, D, dtype=dtype),
            _rand(ks[1], B, T, H, D, dtype=dtype),
            _rand(ks[2], B, T, H, D, dtype=dtype))


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv(0, 2, 64, 4, 16)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_forward_key_padding_mask():
    q, k, v = _qkv(1, 2, 48, 2, 8)
    mask = jnp.arange(48)[None, :] < jnp.array([[30], [48]])
    got = flash_attention(q, k, v, mask=mask, block_q=16, block_k=16)
    want = reference_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_ragged_seq_len_padded_internally():
    # 50 is not a block multiple → exercises the padding path.
    q, k, v = _qkv(2, 1, 50, 2, 8)
    got = flash_attention(q, k, v, block_q=16, block_k=16)
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_cross_attention_lengths():
    B, H, D = 2, 2, 8
    ks = jax.random.split(jax.random.key(3), 3)
    q = _rand(ks[0], B, 24, H, D)
    k = _rand(ks[1], B, 40, H, D)
    v = _rand(ks[2], B, 40, H, D)
    got = flash_attention(q, k, v, block_q=16, block_k=16)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    p = jax.nn.softmax(s, axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_bf16_forward_close():
    q, k, v = _qkv(4, 1, 32, 2, 16, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=16, block_k=16)
    assert got.dtype == jnp.bfloat16
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    q, k, v = _qkv(5, 2, 32, 2, 8)
    mask = jnp.arange(32)[None, :] < jnp.array([[32], [20]])

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, mask=mask, causal=causal,
                            block_q=16, block_k=16)
        return jnp.sum(jnp.sin(o))  # non-trivial cotangent

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(reference_attention(q, k, v, mask=mask,
                                                   causal=causal)))

    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-4,
                                   err_msg=f"d{name}")


def test_gradients_ragged_padding():
    q, k, v = _qkv(6, 1, 20, 2, 8)  # padded to 24 internally

    f = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, block_q=8, block_k=8) ** 2))
    d = jax.grad(lambda q: jnp.sum(reference_attention(q, k, v) ** 2))
    np.testing.assert_allclose(f(q), d(q), atol=5e-5, rtol=5e-4)
    assert np.all(np.isfinite(f(q)))


def test_jit_and_vjp_compile_once():
    q, k, v = _qkv(7, 1, 32, 2, 8)
    step = jax.jit(jax.grad(lambda q: flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16).sum()))
    assert np.all(np.isfinite(step(q)))


def test_as_bert_attention_fn():
    """flash_attention plugs into BertConfig.attention_fn unchanged."""
    import functools
    from tensorflowonspark_tpu.models import Bert, BertConfig

    cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=1,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=32, dropout_rate=0.0,
                     dtype=jnp.float32,
                     attention_fn=functools.partial(
                         flash_attention, block_q=16, block_k=16))
    ids = jnp.ones((2, 16), jnp.int32)
    mask = jnp.arange(16)[None, :] < jnp.array([[16], [9]])
    params = Bert(cfg).init(jax.random.key(0), ids, mask)
    out = Bert(cfg).apply(params, ids, mask)
    assert out.shape == (2, 16, 32)
    assert np.all(np.isfinite(out))

    dense = BertConfig(**{**cfg.__dict__, "attention_fn": None})
    want = Bert(dense).apply(params, ids, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_pick_block_bounds_padding_waste():
    from tensorflowonspark_tpu.ops.flash_attention import _pick_block

    # just past a 512 boundary: pad to one extra 128-tile, not a full 512
    block, padded = _pick_block(520, 512)
    assert padded == 640 and block == 128
    # exact multiples keep the big block
    assert _pick_block(4096, 512) == (512, 4096)
    assert _pick_block(2048, 512) == (512, 2048)
    # an explicit sub-128 tile (interpret-mode tests) stays tiny
    assert _pick_block(48, 16) == (16, 48)
    # a default-sized request always yields lane-aligned (128-multiple)
    # blocks: a short ragged length pads to one 128 tile
    assert _pick_block(20, 512) == (128, 128)
    assert _pick_block(100, 512) == (128, 128)


def test_flash_odd_length_past_block_boundary():
    """T just past the block size must stay correct through _pick_block."""
    q, k, v = _qkv(8, 1, 136, 2, 8)  # 136 = 128 + 8
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_fully_masked_rows_yield_zeros():
    """A batch row whose key-padding mask is all-False must produce zeros
    (and zero gradients), not the mean of V (the online-softmax degenerate
    case ADVICE.md round 1 flagged)."""
    q, k, v = _qkv(9, 2, 16, 2, 8)
    mask = np.ones((2, 16), bool)
    mask[1, :] = False  # batch row 1: every key masked

    out = flash_attention(q, k, v, mask=jnp.asarray(mask),
                          block_q=16, block_k=16)
    out = np.asarray(out)
    assert np.all(out[1] == 0.0), "fully-masked row must be exactly zero"
    # row 0 unchanged vs dense
    want = reference_attention(q[:1], k[:1], v[:1])
    np.testing.assert_allclose(out[:1], np.asarray(want), atol=2e-5, rtol=2e-5)

    # gradients: masked row contributes exactly nothing
    def loss(q, k, v):
        return (flash_attention(q, k, v, mask=jnp.asarray(mask),
                                block_q=16, block_k=16) ** 2).sum()

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (dq, dk, dv):
        g = np.asarray(g)
        assert np.all(np.isfinite(g))
        assert np.all(g[1] == 0.0), "masked batch row must get zero grads"


class TestSlidingWindow:
    def _dense_windowed(self, q, k, v, window):
        T = q.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (q.shape[-1] ** 0.5)
        pos = jnp.arange(T)
        keep = (pos[:, None] >= pos[None, :]) & \
               (pos[None, :] > pos[:, None] - window)
        s = jnp.where(keep[None, None], s.astype(jnp.float32), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))

    @pytest.mark.parametrize("window", [1, 5, 48, 200])
    def test_matches_dense_band_oracle(self, window):
        from tensorflowonspark_tpu.ops import flash_attention

        B, T, H, D = 2, 128, 2, 16
        q = jax.random.normal(jax.random.key(0), (B, T, H, D))
        k = jax.random.normal(jax.random.key(1), (B, T, H, D))
        v = jax.random.normal(jax.random.key(2), (B, T, H, D))
        got = flash_attention(q, k, v, causal=True, window=window,
                              block_q=32, block_k=32)
        want = self._dense_windowed(q, k, v, window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients_match_band_oracle(self):
        from tensorflowonspark_tpu.ops import flash_attention

        B, T, H, D, W = 1, 64, 2, 8, 13
        q = jax.random.normal(jax.random.key(3), (B, T, H, D))
        k = jax.random.normal(jax.random.key(4), (B, T, H, D))
        v = jax.random.normal(jax.random.key(5), (B, T, H, D))

        def f_flash(q, k, v):
            return flash_attention(q, k, v, causal=True, window=W,
                                   block_q=16, block_k=16).sum()

        def f_dense(q, k, v):
            return self._dense_windowed(q, k, v, W).sum()

        gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_window_requires_causal_and_positive(self):
        from tensorflowonspark_tpu.ops import flash_attention

        x = jnp.zeros((1, 16, 1, 8))
        with pytest.raises(ValueError, match="causal"):
            flash_attention(x, x, x, window=4)
        with pytest.raises(ValueError, match=">= 1"):
            flash_attention(x, x, x, causal=True, window=0)


def test_flash_blocks_anchor_on_sweep_artifact(tmp_path, monkeypatch):
    """Default block sizes come from the committed on-chip block sweep
    when one exists, and fall back to 512x512 otherwise."""
    import importlib

    # ops/__init__ shadows the submodule name with the function, so a
    # plain `import ... as` would bind the function — load the module
    fa_mod = importlib.import_module(
        "tensorflowonspark_tpu.ops.flash_attention")

    art = tmp_path / "flash_sweep.json"
    monkeypatch.setattr(fa_mod, "_FLASH_SWEEP_PATH", str(art))

    fa_mod._tuned_blocks.cache_clear()
    assert fa_mod._tuned_blocks() == (512, 512)  # no artifact yet

    art.write_text('{"best_block": "1024x256"}')
    fa_mod._tuned_blocks.cache_clear()
    assert fa_mod._tuned_blocks() == (1024, 256)

    art.write_text('{"best_block": "garbage"}')
    fa_mod._tuned_blocks.cache_clear()
    assert fa_mod._tuned_blocks() == (512, 512)
    fa_mod._tuned_blocks.cache_clear()  # leave no tmp-path state behind
