"""Power-retention layers THROUGH THE MODEL AND THE BATCHER, against the
plain float32 reference ``benchmark/reference/brumby.py`` (the attention
form: no state, no feature map) at a small size with seeded random weights:
hidden 64, 4 query / 2 key/value heads of 16, 3 layers, an untied head.
Comparisons are of LOGITS, float32 at highest precision on both sides."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import brumby as ref
from tensorflowonspark_tpu.models import (GPT, ContinuousBatcher, DraftModel,
                                          GPTConfig, greedy_generate,
                                          init_cache, lookup_generate)
from tensorflowonspark_tpu.models import gpt
from tensorflowonspark_tpu.models.kv_pages import NoPages

adapter = harness.load_module("models", "brumby")

CFG = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
       "layer_types": ["retention"] * 3, "head_dim": 16,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "rms_norm_eps": 1e-6, "rope_theta": 1e6, "vocab_size": 211,
       "max_position_embeddings": 64, "tie_word_embeddings": False,
       "power": 2, "retention_eps": 1e-6, "retention_chunk": 8,
       "dtype": "float32", "init_std": 0.3}
#: float32 everywhere: what differs from the reference is the order of
#: sums.  The attention form squares one product q . k; the state holds
#: the same number as 192 signed products of the feature map, which cancel
#: where q . k is near zero, so a weight of true size ~1e-3 carries ~1e-6
#: of rounding, and a position whose live keys are all nearly orthogonal
#: to its query divides two such sums: 1e-3 of a logit of size ~10 at the
#: worst position seen (1e-4 of its size), a few 1e-6 elsewhere
TOL = 2e-3


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def made():
    with jax.default_matmul_precision("highest"):
        return adapter.gpt_config(CFG), ref.make_weights(3, CFG)


def _ref_logits(params, seq):
    return np.asarray(ref.forward(
        params, jnp.asarray(seq, jnp.int32)[None], CFG)[0])


def _prompt(i, n):
    return np.random.default_rng([11, i]).integers(0, 211, n).astype(np.int32)


def _probe(b, params):
    """Logits of the NEXT position of every active slot: the batcher's
    cache, fed what the next step would feed it, cache not kept."""
    b.settle()
    toks = jnp.asarray([s.tokens[-1] if s else 0 for s in b.slots],
                       jnp.int32)
    logits, _ = b.model.apply({"params": params, "cache": b.cache},
                              toks[:, None], mutable=["cache"])
    return np.asarray(logits[:, 0])


# ------------------------------------------------------------ the model

def test_full_forward_is_the_reference_attention_form(made):
    """``decode=False`` runs the chunked form from an empty state (chunks
    of 8 over 29 tokens) and the untied head."""
    cfg, params = made
    seq = _prompt(0, 29)
    got = GPT(cfg).apply({"params": params}, jnp.asarray(seq)[None])[0]
    np.testing.assert_allclose(got, _ref_logits(params, seq), atol=TOL)


def test_recurrent_decode_is_the_chunked_prefill_is_the_reference(made):
    """One sequence three ways: token by token through the cache (the
    recurrent step), one block through the cache (chunked), and the
    reference's full forward: the same logits at every position."""
    cfg, params = made
    seq = _prompt(1, 21)
    want = _ref_logits(params, seq)
    model = GPT(cfg, decode=True)
    cache = init_cache(cfg, params, 1)
    block, _ = model.apply({"params": params, "cache": cache},
                           jnp.asarray(seq)[None], mutable=["cache"])
    np.testing.assert_allclose(block[0], want, atol=TOL)
    steps = []
    for t in seq:
        logits, upd = model.apply({"params": params, "cache": cache},
                                  jnp.asarray([[t]]), mutable=["cache"])
        cache = upd["cache"]
        steps.append(np.asarray(logits[0, 0]))
    np.testing.assert_allclose(np.stack(steps), want, atol=TOL)


def test_untied_head_is_its_own_matrix(made):
    cfg, params = made
    assert params["lm_head"].shape == (64, 211)
    tied = dataclasses.replace(cfg, tie_word_embeddings=True)
    seq = jnp.asarray(_prompt(2, 9))[None]
    a = GPT(cfg).apply({"params": params}, seq)
    b = GPT(tied).apply({"params": params}, seq)
    assert float(jnp.abs(a - b).max()) > 1.0


def test_a_cut_in_depth_drops_nothing_from_the_layers_kept(made):
    """The 3-layer model's first 2 layers, run as the cut configuration
    (``num_hidden_layers`` 2), give the hidden state the uncut reference
    has after 2 layers: the test that ties the benchmark's cut (layers 0-7
    of 40) to the model."""
    _, params = made
    cut = dict(CFG, num_hidden_layers=2, layer_types=["retention"] * 2)
    seq = _prompt(3, 17)
    want = ref.hidden_after(params, jnp.asarray(seq)[None], CFG, 2)
    # the program's trunk applies the final norm: undo nothing, compare
    # after the same norm
    want = ref._base._rms(want, params["ln_f"]["scale"], CFG["rms_norm_eps"])
    kept = {k: v for k, v in params.items() if k != "layer_2"}
    got = GPT(adapter.gpt_config(cut)).apply(
        {"params": kept}, jnp.asarray(seq)[None], method=GPT.hidden)
    np.testing.assert_allclose(got, want, atol=TOL)


# --------------------------------------------------------- the batcher

def test_batcher_builds_no_kv_pool_and_admits_by_slots(made):
    cfg, params = made
    b = ContinuousBatcher(cfg, params, max_batch=2, prefill_rows_max=2)
    names = {p[-1].key for p, _ in
             jax.tree_util.tree_flatten_with_path(b.cache)[0]}
    assert names == {"index", "ret_state", "ret_norm"}
    assert isinstance(b._pages, NoPages) and b.cfg.kv_page_tokens is None
    assert b.load()["total_pages"] == 0 and not b._attends_in_place
    ids = [b.submit(_prompt(i, 9 + i), 6) for i in range(5)]
    assert not b.has_free_slot()
    out = b.run()
    assert sorted(out) == ids and b.kv_pages_read == 0
    assert b.prefix_stats()["total_pages"] == 0


def test_prefill_then_decode_is_the_reference_full_forward(made):
    """Rows of UNEQUAL prompt length in one padded prefill (bucket 16,
    lengths 9, 14, 11), then decode steps through the state: at every
    step each row's next-position logits are the reference's over the
    whole sequence so far.  Padding in neither state nor decay."""
    cfg, params = made
    b = ContinuousBatcher(cfg, params, max_batch=3)
    prompts = [_prompt(10 + i, n) for i, n in enumerate((9, 14, 11))]
    for p in prompts:
        b.submit(p, 12)
    b.step()
    assert b.prefill_dispatches == 1 and b.state_rows_seated == 3
    for _ in range(4):
        got = _probe(b, params)
        for i, s in enumerate(b.slots):
            seq = np.concatenate([prompts[s.request_id], s.tokens])
            np.testing.assert_allclose(got[i], _ref_logits(params, seq)[-1],
                                       atol=TOL)
        b.step()


def test_served_tokens_are_the_solo_greedy_tokens(made):
    cfg, params = made
    b = ContinuousBatcher(cfg, params, max_batch=2, prefill_rows_max=1)
    prompts = [_prompt(20 + i, n) for i, n in enumerate((7, 12, 17, 10))]
    ids = [b.submit(p, 9) for p in prompts]
    out = b.run()
    for rid, p in zip(ids, prompts):
        want = np.asarray(greedy_generate(cfg, params, jnp.asarray(p)[None],
                                          9))[0, p.size:]
        assert out[rid].tolist() == want.tolist()
    # the steps between two admissions were queued ahead, as in every cell
    assert b.decode_ahead_dispatches > 0
    assert b.state_bytes_moved == b.decode_steps * gpt.state_step_bytes(
        b.cfg, 2)


def test_a_parked_row_is_cleared_and_its_slot_reseated(made):
    """A finished row's state is zeroed at park, and the next request
    seated in that slot starts from ITS prefill, not from what was left."""
    cfg, params = made
    b = ContinuousBatcher(cfg, params, max_batch=1)
    first = b.submit(_prompt(30, 13), 5)
    b.run()
    state = b.cache["layer_0"]["ret"]["ret_state"]
    assert float(jnp.abs(state).max()) == 0.0
    p = _prompt(31, 10)
    b.submit(p, 6)
    b.step()
    got = _probe(b, params)[0]
    seq = np.concatenate([p, b.slots[0].tokens])
    np.testing.assert_allclose(got, _ref_logits(params, seq)[-1], atol=TOL)
    assert first in b._results


def test_a_row_parked_ahead_is_cleared_before_the_queued_step(made):
    """A row that ends by budget is parked, its retention state zeroed,
    BEHIND its last step and ahead of the step queued next, before either
    is fetched; the request then prefilled behind that queued step starts
    from its own state, and the row beside it, which the queued step ran,
    stays where the reference is."""
    cfg, params = made
    b = ContinuousBatcher(cfg, params, max_batch=2)
    p0, p1, p2 = _prompt(32, 9), _prompt(33, 6), _prompt(34, 11)
    first = b.submit(p0, 3)
    b.submit(p1, 14)
    b.step()
    cleared = []
    park = b._park_slot
    b._park_slot = lambda i: (park(i), cleared.append(float(jnp.abs(
        b.cache["layer_0"]["ret"]["ret_state"][i]).max())))[0]
    assert b.step() == [first]
    # parked once, ahead of the fetch: zero there, with the next step queued
    assert cleared == [0.0] and b.step_queued and b.slots[0] is None
    third = b.submit(p2, 8)
    b.step()
    # ... which the prefill consumed; the next step, the new row in it,
    # was dispatched as soon as its first token was on the host
    assert b.slots[0].request_id == third and b.step_queued
    assert b.decode_ahead_standdowns["admission"] == 1
    assert b.state_rows_seated == 3
    for _ in range(2):
        got = _probe(b, params)
        for i, p in ((0, p2), (1, p1)):
            seq = np.concatenate([p, b.slots[i].tokens])
            np.testing.assert_allclose(got[i], _ref_logits(params, seq)[-1],
                                       atol=TOL)
        b.step()
    assert cleared == [0.0]
    assert sum(b.decode_ahead_standdowns.values()) \
        == b.decode_dispatches - b.decode_ahead_dispatches


def test_chunked_admission_carries_the_state_beside_the_cache(made):
    """``carried_prefills`` counts the final dispatches that brought
    ``inf["state"]``: none without ``prefill_chunk``, one per chunked
    request with it (both prompts here are longer than a chunk).  A
    chunked request's FIRST slice is handed zeros as an INPUT
    (``_advance_inflight``: ``self._state_rows(1)``), not made inside the
    program, so it skips the state's query only because
    ``retention_chunked`` decides that from the normaliser it is given;
    a flag from the caller that made the zeros would miss it."""
    cfg, params = made
    p = _prompt(40, 23)
    whole = ContinuousBatcher(cfg, params, max_batch=2)
    rid = whole.submit(p, 7)
    want = whole.run()[rid]
    assert (whole.prefill_dispatches, whole.carried_prefills) == (1, 0)
    b = ContinuousBatcher(cfg, params, max_batch=2, prefill_chunk=4)
    other = b.submit(_prompt(41, 6), 12)
    rid = b.submit(p, 7)
    out = b.run()
    assert out[rid].tolist() == want.tolist() and other in out
    assert (b.prefill_dispatches, b.carried_prefills) == (2, 2)


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
        small = dataclasses.replace(cfg, layer_types=None, num_layers=1)
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
def test_what_the_retention_state_cannot_follow_refuses_loudly(made, what):
    """The same refusals, by the same messages, as for a conv state
    (``tests/test_lfm2.py``): each names the state that caused it."""
    with pytest.raises(ValueError) as e:
        _refused(made, what)
    assert "ret" in str(e.value)


def test_errors_state_the_cache_kinds(made):
    cfg, _ = made
    assert cfg.cache_kinds == (
        "ret_state of 3 retention layer(s) (the decayed sum of every token "
        "so far per row: fixed size, no snapshot to rewind to or share)")
    assert cfg.has_state and cfg.num_attention_layers == 0
    mixed = GPTConfig(num_layers=3, hidden_size=32, num_heads=2,
                      vocab_size=50, pos_encoding="rope",
                      layer_types=("conv", "full_attention", "retention"))
    assert mixed.num_attention_layers == 1 and mixed.has_state
    assert "K/V of 1 full_attention" in mixed.cache_kinds
    assert "conv_state of 1 conv" in mixed.cache_kinds
    with pytest.raises(ValueError, match="unknown \\['window'\\]"):
        GPTConfig(num_layers=1, layer_types=("window",))


def test_retention_beside_attention_keeps_the_pool(made):
    """A pattern with both kinds keeps pages for the attention layer and
    state rows for the retention layers: one batcher, both mechanisms."""
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                    intermediate_size=48, max_position_embeddings=32,
                    dtype=jnp.float32, pos_encoding="rope", norm="rmsnorm",
                    mlp="swiglu", use_bias=False, qk_norm=True,
                    layer_types=("retention", "full_attention"),
                    retention_chunk=4)
    import flax.linen as nn
    params = nn.unbox(GPT(cfg).init(jax.random.key(0),
                                    jnp.zeros((1, 4), jnp.int32))["params"])
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=4)
    assert b.load()["total_pages"] == 16
    prompts = [np.arange(3, 3 + n, dtype=np.int32) for n in (5, 9, 6)]
    ids = [b.submit(p, 5) for p in prompts]
    out = b.run()
    for rid, p in zip(ids, prompts):
        want = np.asarray(greedy_generate(cfg, params, jnp.asarray(p)[None],
                                          5))[0, p.size:]
        assert out[rid].tolist() == want.tolist()
    assert b.kv_pages_read > 0 and b.state_rows_seated == 3
