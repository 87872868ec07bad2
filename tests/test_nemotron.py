"""One mixer a block THROUGH THE MODEL AND THE BATCHER, against the plain
float32 reference ``benchmark/reference/nemotron_h.py`` (the state-space
layer token by token: no chunked form, no cache, no grouped product) at a
small size with seeded random weights: hidden 48, pattern ``MEM*E``, 8
Mamba-2 heads of 16 in 2 groups with a state of 8, attention of 4 query / 2
key/value heads of 16 (64 over a hidden size of 48), experts 4-7 of 16 held
beside a shared expert, an untied head.  Comparisons are of LOGITS, float32
at highest precision on both sides."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import nemotron_h as ref
from tensorflowonspark_tpu.models import (GPT, ContinuousBatcher, DraftModel,
                                          GPTConfig, greedy_generate,
                                          init_cache, lookup_generate)
from tensorflowonspark_tpu.models import gpt, moe

adapter = harness.load_module("models", "nemotron_h")

CFG = dict(harness.load_json("configs", "toy-nemotron.json"),
           dtype="float32")
#: float32 everywhere: what differs from the reference is the order of
#: sums (the chunked scan's products against the recurrence's, the grouped
#: products against every expert on every token); logits of size ~10
TOL = 2e-4


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def made():
    with jax.default_matmul_precision("highest"):
        return adapter.gpt_config(CFG), ref.make_weights(3, CFG)


_forward = jax.jit(lambda params, ids: ref.forward(params, ids, CFG))


def _ref_logits(params, seq):
    """The reference's logits at every position of ``seq`` (causal: those
    of a prefix are the first rows)."""
    return np.asarray(_forward(params, jnp.asarray(seq, jnp.int32)[None])[0])


def _prompt(i, n):
    return np.random.default_rng([42, i]).integers(0, 211, n).astype(np.int32)


def _probe(b, params):
    """Logits of the NEXT position of every active slot: the batcher's
    cache, fed what the next step would feed it, cache not kept."""
    b.settle()
    toks = jnp.asarray([s.tokens[-1] if s else 0 for s in b.slots],
                       jnp.int32)
    return np.asarray(_next_logits(b.model, params, b.cache, toks))


@functools.partial(jax.jit, static_argnums=(0,))
def _apply(model, params, cache, tokens, lengths=None):
    """One cached forward, compiled: ``(logits, cache)``."""
    logits, vars_ = model.apply(
        {"params": params, "cache": cache}, tokens,
        mutable=["cache", moe.STATS],
        **({} if lengths is None else {"lengths": lengths}))
    return logits, vars_["cache"]


def _next_logits(model, params, cache, toks):
    return _apply(model, params, cache, toks[:, None])[0][:, 0]


# ------------------------------------------------------------ the layers

def _layer(made, index):
    cfg, params = made
    return cfg, params[f"layer_{index}"], jax.random.normal(
        jax.random.key(index), (2, 21, CFG["hidden_size"]))


def test_mamba2_mixer_is_the_reference_recurrence(made):
    """``decode=False``: the chunked scan from an empty state (chunks of 8
    over 21 tokens), the convolution with its bias, the gate before the
    grouped norm."""
    cfg, p, u = _layer(made, 0)
    got = gpt.Mamba2Mixer(cfg).apply({"params": p["ssm"]}, u)
    want = ref.mamba2(u, ref._base._f32(p["ssm"]), CFG)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_expert_layer_is_the_reference_share_beside_the_shared_expert(made):
    cfg, p, u = _layer(made, 1)
    got, stats = moe.SparseMoE(cfg).apply({"params": p["moe"]}, u,
                                          mutable=[moe.STATS])
    want, sel = ref.experts(u, ref._base._f32(p["moe"]), CFG)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # made, the busiest held expert's, held experts touched, held
    counts = np.bincount(np.asarray(sel).ravel(), minlength=16)[4:8]
    assert jax.tree.leaves(stats)[0].tolist() == [
        2 * 21 * 3, counts.max(), (counts > 0).sum(), counts.sum()]
    assert 0 < counts.sum() < 2 * 21 * 3


def test_attention_has_no_positions_and_heads_wider_than_the_stream(made):
    cfg, p, u = _layer(made, 3)
    assert cfg.head_dim * cfg.num_heads == 64 > cfg.hidden_size == 48
    got = gpt.CausalSelfAttention(cfg).apply({"params": p["attn"]}, u)
    want = ref.attention(u, ref._base._f32(p["attn"]), CFG)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert "pos_emb" not in made[1]


def test_a_block_is_its_mixer_alone(made):
    """No second norm and no feed-forward half in any block; the expert
    layer's first matrices are stored with the hidden axis last (a width
    of 24, as one of 1856, is not whole lane tiles)."""
    cfg, params = made
    assert set(params["layer_0"]) == {"ln1", "ssm"}
    assert set(params["layer_1"]) == {"ln1", "moe"}
    assert set(params["layer_3"]) == {"ln1", "attn"}
    assert params["layer_1"]["moe"]["w_up"].shape == (4, 24, 48)
    assert params["layer_1"]["moe"]["w_down"].shape == (4, 24, 48)
    assert params["layer_1"]["moe"]["router"].shape == (48, 16)
    assert cfg.moe_up_transposed
    assert [cfg.is_expert_layer(i) for i in range(5)] \
        == [False, True, False, False, True]
    assert (cfg.num_expert_layers, cfg.num_attention_layers,
            cfg.num_state_layers, cfg.num_experts_held) == (2, 1, 2, 4)


# -------------------------------------------------------------- the model

def test_full_forward_is_the_reference(made):
    cfg, params = made
    seq = _prompt(0, 29)
    got = GPT(cfg).apply({"params": params}, jnp.asarray(seq)[None])[0]
    np.testing.assert_allclose(got, _ref_logits(params, seq), atol=TOL)


@pytest.mark.parametrize("split", [1, 7, 8, 9, 16, 17])
def test_prefill_then_decode_through_the_cache_is_the_full_forward(made,
                                                                   split):
    """A block of ``split`` tokens (the chunked scan: chunks of 8, so one
    short of a chunk, a whole one, one over, two), then one token at a
    time through the recurrent step, the convolution's tail and the K/V
    cache."""
    cfg, params = made
    seq = _prompt(1, 24)
    model = GPT(cfg, decode=True)
    cache = init_cache(cfg, params, 1)
    outs = []
    for lo, hi in [(0, split)] + [(t, t + 1) for t in range(split, 24)]:
        logits, cache = _apply(model, params, cache,
                               jnp.asarray(seq[lo:hi])[None])
        outs.append(logits[0])
    np.testing.assert_allclose(jnp.concatenate(outs), _ref_logits(params, seq),
                               atol=TOL)


def test_padded_rows_take_both_states_at_their_own_lengths(made):
    """Rows of 9, 14 and 3 valid tokens right-padded to 16 (the last
    shorter than the convolution's 3-token tail): the logits are each
    row's last valid position's, and decode goes on from there."""
    cfg, params = made
    cfg = dataclasses.replace(cfg, per_row_positions=True)
    prompts = [_prompt(2 + i, n) for i, n in enumerate((9, 14, 3))]
    ids = np.zeros((3, 16), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :p.size] = p
        ids[i, p.size:] = 200 - i          # a pad that is not the prompt's
    lengths = jnp.asarray([p.size for p in prompts])
    model = GPT(cfg, decode=True)
    logits, cache = _apply(model, params, init_cache(cfg, params, 3),
                           jnp.asarray(ids), lengths)
    cache = gpt.set_cache_counters(cache, lengths)
    nxt = jnp.asarray([5, 6, 7], jnp.int32)
    after, _ = _apply(model, params, cache, nxt[:, None])
    for i, p in enumerate(prompts):
        want = _ref_logits(params, np.append(p, int(nxt[i])))
        np.testing.assert_allclose(logits[i, 0], want[-2], atol=TOL)
        np.testing.assert_allclose(after[i, 0], want[-1], atol=TOL)


# ------------------------------------------------------- the share (guide §4)

def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(made):
    """The deployment divides every expert layer over 8 chips: here 16
    experts, 2 to a share.  The routed parts of the 8 shares, each
    computed by the PROGRAM's layer told which experts it holds (with the
    weights a chip of the deployment would hold: each expert's drawn from
    its global number), plus the shared expert counted once, add up to
    what the reference gives for the whole layer with all 16 experts."""
    uncut = dict(CFG, n_routed_experts=16, experts_held_first=0)
    whole = ref._base._f32(ref.make_layer(ref.seed_key(5), uncut, 1)["moe"])
    u = jax.random.normal(jax.random.key(9), (2, 19, 48))
    want, _ = ref.experts(u, whole, uncut)
    shared = ref.relu2(u, whole["shared_up"], whole["shared_down"])
    total = shared
    held = 0
    for chip in range(8):
        share = dict(CFG, n_routed_experts=2, experts_held_first=2 * chip)
        p = ref.make_layer(ref.seed_key(5), share, 1)["moe"]
        np.testing.assert_array_equal(p["w_up"],
                                      whole["w_up"][2 * chip:2 * chip + 2])
        cfg = adapter.gpt_config(share)
        assert cfg.experts_held == (2 * chip, 2)
        got, stats = moe.SparseMoE(cfg).apply({"params": p}, u,
                                              mutable=[moe.STATS])
        total = total + (got - shared)
        held += int(jax.tree.leaves(stats)[0][3])
        # the reference given the same share computes the same part
        np.testing.assert_allclose(got, ref.experts(u, ref._base._f32(p),
                                                    share)[0], atol=2e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert held == 2 * 19 * 3       # every assignment fell to one share


def test_experts_held_must_lie_inside_the_routers_width(made):
    cfg, _ = made
    with pytest.raises(ValueError, match="does not lie inside the 16"):
        dataclasses.replace(cfg, experts_held=(14, 4))
    with pytest.raises(ValueError, match="needs mixer_only=True"):
        dataclasses.replace(cfg, mixer_only=False)
    # the experts carry their own activation: the dense layers' ``mlp``
    # no longer has to be 'swiglu' for a configuration with experts
    assert cfg.mlp == "gelu" and cfg.moe_activation == "relu2"
    with pytest.raises(ValueError, match="moe_activation"):
        dataclasses.replace(cfg, moe_activation="gelu")
    with pytest.raises(ValueError, match="'learned', 'rope' or 'none'"):
        dataclasses.replace(cfg, pos_encoding="alibi")


# ------------------------------------------------------------ the batcher

def test_batcher_holds_pages_ssm_state_and_conv_tail_in_one_model(made):
    """Three kinds of per-sequence state through the one mechanism: K/V
    pages for the attention layer, ``ssm_state`` and ``ssm_conv`` rows for
    the two Mamba-2 layers.  Rows of UNEQUAL prompt length in one padded
    prefill, then decode steps: at every step each row's next-position
    logits are the reference's over the whole sequence so far."""
    cfg, params = made
    b = ContinuousBatcher(cfg, params, max_batch=3, kv_page_tokens=4,
                          prefix_cache=False)
    assert b.load()["total_pages"] == 3 * 16
    leaves = {p[-1].key for p, _ in
              jax.tree_util.tree_flatten_with_path(b.cache)[0]}
    assert leaves == {"index", "block_table", "k", "v", "ssm_state",
                      "ssm_conv"}
    assert b.cache["layer_0"]["ssm"]["ssm_state"].shape == (3, 2, 8, 64)
    assert b.cache["layer_0"]["ssm"]["ssm_conv"].shape == (3, 3, 160)
    prompts = [_prompt(10 + i, n) for i, n in enumerate((9, 14, 11))]
    for p in prompts:
        b.submit(p, 12)
    b.step()
    assert b.prefill_dispatches == 1 and b.state_rows_seated == 3
    seen = []       # (row, tokens so far, the next position's logits)
    for _ in range(4):
        got = _probe(b, params)
        seen += [(i, len(s.tokens), got[i]) for i, s in enumerate(b.slots)]
        b.step()
    for i, s in enumerate(b.slots):
        want = _ref_logits(params, np.concatenate([prompts[s.request_id],
                                                   s.tokens]))
        for row, n, got in seen:
            if row == i:
                np.testing.assert_allclose(
                    got, want[prompts[s.request_id].size + n - 1], atol=TOL)
    # the state's bytes a step: both Mamba-2 layers' SSM state (float32)
    # and convolution tail (3 x 160 channels), read and written once
    assert gpt.state_step_bytes(b.cfg, 3) \
        == 2 * 2 * 3 * (8 * 16 * 8 * 4 + 3 * 160 * 4)
    assert b.state_bytes_moved == b.decode_steps * gpt.state_step_bytes(
        b.cfg, 3)
    assert 0 < b.expert_assignments_held < b.expert_assignments
    assert b.experts_touched <= 2 * 4 * (b.decode_dispatches
                                         + b.prefill_dispatches)


def test_served_tokens_are_the_solo_greedy_tokens_with_steps_run_ahead(made):
    cfg, params = made
    b = ContinuousBatcher(cfg, params, max_batch=2, prefill_rows_max=1,
                          prefix_cache=False)
    prompts = [_prompt(20 + i, n) for i, n in enumerate((7, 12, 17, 10))]
    ids = [b.submit(p, 9) for p in prompts]
    out = b.run()
    for rid, p in zip(ids, prompts):
        want = np.asarray(greedy_generate(cfg, params, jnp.asarray(p)[None],
                                          9))[0, p.size:]
        assert out[rid].tolist() == want.tolist()
    assert b.decode_ahead_dispatches > 0


def test_a_parked_row_is_cleared_and_its_slot_reseated(made):
    """A finished row's two states are zeroed at park, and the next request
    seated in that slot starts from ITS prefill, not from what was left."""
    cfg, params = made
    b = ContinuousBatcher(cfg, params, max_batch=1, prefix_cache=False)
    b.submit(_prompt(30, 13), 5)
    b.run()
    for leaf in ("ssm_state", "ssm_conv"):
        assert float(jnp.abs(b.cache["layer_0"]["ssm"][leaf]).max()) == 0.0
    p = _prompt(31, 10)
    b.submit(p, 6)
    b.step()
    got = _probe(b, params)[0]
    seq = np.concatenate([p, b.slots[0].tokens])
    np.testing.assert_allclose(got, _ref_logits(params, seq)[-1], atol=TOL)


def test_a_prompt_admitted_in_slices_carries_both_states(made):
    """``prefill_chunk=4``: a prompt of 23 tokens enters in slices, each
    starting from the SSM state and the convolution tail the last left;
    the tokens are those of the one-call admission."""
    cfg, params = made
    p = _prompt(40, 23)
    whole = ContinuousBatcher(cfg, params, max_batch=2, prefix_cache=False)
    rid = whole.submit(p, 7)
    want = whole.run()[rid]
    b = ContinuousBatcher(cfg, params, max_batch=2, prefill_chunk=4,
                          prefix_cache=False)
    other = b.submit(_prompt(41, 6), 12)
    rid = b.submit(p, 7)
    out = b.run()
    assert out[rid].tolist() == want.tolist() and other in out
    assert b.carried_prefills == 2


# ----------------------------------------------------------- refusals

def _refused(made, what):
    cfg, params = made
    if what == "prefix-cache":
        ContinuousBatcher(cfg, params, max_batch=2, prefix_cache=True)
    elif what == "speculative_k":
        ContinuousBatcher(cfg, params, max_batch=2, speculative_k=2)
    elif what == "prefill_only":
        ContinuousBatcher(cfg, params, max_batch=2, prefill_only=True)
    elif what == "set_draft":
        small = GPTConfig(vocab_size=211, hidden_size=48, num_layers=1,
                          num_heads=4, max_position_embeddings=64)
        ContinuousBatcher(cfg, params, max_batch=2).set_draft(
            DraftModel(small, None, window=8))
    elif what == "adopt_session":
        ContinuousBatcher(cfg, params, max_batch=2).adopt_session({"v": 1})
    elif what == "set_role":
        ContinuousBatcher(cfg, params, max_batch=2).set_role("prefill")
    elif what == "lookup_generate":
        lookup_generate(cfg, params, jnp.zeros((1, 4), jnp.int32), 4)
    elif what == "rewind_cache":
        gpt.rewind_cache(init_cache(cfg, params, 1), 0)
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
def test_what_the_ssm_state_cannot_follow_refuses_loudly(made, what):
    """The same refusals, by the same messages, as for a conv or a
    retention state: each names the state that caused it."""
    with pytest.raises(ValueError) as e:
        _refused(made, what)
    assert "ssm" in str(e.value) or "mamba2" in str(e.value)


def test_errors_state_the_cache_kinds(made):
    cfg, _ = made
    assert cfg.cache_kinds == (
        "K/V of 1 full_attention layer(s) (positional, rewindable); "
        "ssm_state and ssm_conv of 2 mamba2 layer(s) (the decayed sum of "
        "every token so far and the last 3 convolution inputs per row: "
        "fixed size, no snapshot to rewind to or share)")
    from tensorflowonspark_tpu.serving.sharded import default_shard_params
    with pytest.raises(ValueError) as e:
        default_shard_params(cfg, None, None)
    assert cfg.cache_kinds in str(e.value)
    assert "4 of 16 experts" in str(e.value)
