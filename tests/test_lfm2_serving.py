"""The conv layers' per-slot state and the expert layer THROUGH THE BATCHER
(the serving half of ``tests/test_lfm2.py``; a file of its own so that the
test runner spreads the two over workers), against the plain float32
reference ``benchmark/reference/lfm2.py`` at a small size with every feature
on and seeded random weights.  Comparisons are of LOGITS: the batcher's
cache is probed with the tokens it would feed next, and what comes back is
held against the reference's full forward over the whole sequence so far."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import lfm2 as ref
from tensorflowonspark_tpu.models import (ContinuousBatcher, greedy_generate,
                                          moe)

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


def _probe(b, params):
    """Logits of the NEXT position of every active slot: the batcher's
    cache, fed what the next step would feed it, cache not kept."""
    b.settle()      # a step queued ahead has its tokens emitted first
    toks = jnp.asarray([s.tokens[-1] if s else 0 for s in b.slots],
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = b.model.apply({"params": params, "cache": b.cache},
                                  toks[:, None],
                                  mutable=["cache", moe.STATS])
    return np.asarray(logits[:, 0])


def _check_slots(b, params, prompts):
    """Every active slot's next-position logits against the reference's
    full forward over its prompt and the tokens served so far; returns
    how many slots were checked."""
    got = _probe(b, params)
    n = 0
    for i, s in enumerate(b.slots):
        if s is None:
            continue
        seq = np.concatenate([prompts[s.request_id], s.tokens])
        np.testing.assert_allclose(got[i], _ref_last(params, seq), atol=TOL,
                                   err_msg=f"slot {i} after {len(seq)}")
        n += 1
    return n


MODES = {"paged": dict(kv_page_tokens=4, kv_pool_pages=64,
                       prefix_cache=False),
         "default": {},
         "paged-chunked": dict(kv_page_tokens=4, kv_pool_pages=64,
                               prefix_cache=False, prefill_chunk=4),
         "default-chunked": dict(prefill_chunk=4),
         "paged-rows-max": dict(kv_page_tokens=4, kv_pool_pages=64,
                                prefix_cache=False, prefill_rows_max=1)}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_batcher_logits_match_reference_at_unequal_lengths_and_steps(
        made, mode):
    """Prompt lengths that are no bucket sizes (the state is taken at the
    true length, not at the padded bucket's end), rows admitted at
    different steps (the state is per row), with and without chunked
    prefill (the state is carried across chunks)."""
    cfg, params = made
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(cfg, params, max_batch=4, **MODES[mode])
        prompts = {}
        schedule = {0: [(0, 11), (1, 5)], 2: [(2, 13)], 5: [(3, 7)]}
        checked = 0
        for step in range(9):
            for i, n in schedule.get(step, []):
                prompts[b.submit(_prompt(i, n), 12)] = _prompt(i, n)
            b.step()
            checked += _check_slots(b, params, prompts)
    assert checked >= 15
    assert b.state_rows_seated == 4
    # three small integers per expert layer per dispatch rode the fetches
    layers = cfg.num_expert_layers
    assert b.expert_assignments % (2 * layers) == 0
    assert 0 < b.experts_touched <= b.expert_assignments
    assert b.expert_peak_assignments * 8 >= b.expert_assignments


def test_chunked_prefill_on_equals_off(made):
    """The state carried across chunks is the state of the whole prompt:
    same first tokens, same logits at the next position."""
    cfg, params = made
    kw = dict(kv_page_tokens=4, kv_pool_pages=64, prefix_cache=False)
    b1 = ContinuousBatcher(cfg, params, max_batch=2, **kw)
    b2 = ContinuousBatcher(cfg, params, max_batch=2, prefill_chunk=4, **kw)
    with jax.default_matmul_precision("highest"):
        for b in (b1, b2):
            b.submit(_prompt(0, 14), 6)
            while not any(b.slots):
                b.step()
        assert [s.tokens for s in b1.slots if s] \
            == [s.tokens for s in b2.slots if s]
        p1 = _probe(b1, params)[[i for i, s in enumerate(b1.slots) if s][0]]
        p2 = _probe(b2, params)[[i for i, s in enumerate(b2.slots) if s][0]]
    np.testing.assert_allclose(p1, p2, atol=TOL)


@pytest.mark.parametrize("mode", ["paged", "default"])
def test_a_reused_slot_starts_from_zero_state(made, mode):
    cfg, params = made
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(cfg, params, max_batch=1, **MODES[mode])
        first = b.submit(_prompt(0, 9), 3)
        while b.result(first) is None:
            b.step()
        assert not any(b.slots)
        # a budget that outlasts both probes: each settles a queued step
        prompts = {b.submit(_prompt(1, 6), 8): _prompt(1, 6)}
        b.step()
        assert _check_slots(b, params, prompts) == 1
        b.step()
        assert _check_slots(b, params, prompts) == 1


def test_greedy_generate_matches_the_batcher(made):
    """The plain compiled decoder carries the conv state too."""
    cfg, params = made
    prompt = _prompt(4, 10)
    with jax.default_matmul_precision("highest"):
        solo = np.asarray(greedy_generate(cfg, params, prompt[None], 6))[0]
        b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=4,
                              prefix_cache=False)
        rid = b.submit(prompt, 6)
        got = b.run()[rid]
    assert solo[10:].tolist() == got.tolist()


# -- run-ahead: the next plain step queued behind the running one ----------

def _dense(**kw):
    from tensorflowonspark_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(num_layers=2, hidden_size=32, num_heads=2, vocab_size=50,
                    max_position_embeddings=64, **kw)
    return cfg, GPT(cfg).init(jax.random.key(0),
                              jnp.zeros((1, 2), jnp.int32))["params"]


def _toy(made, model):
    return made if model == "lfm2" else _dense()


#: an ``eos_id`` no row ever emits: the batcher it is given to serves the
#: same tokens and stands down at every step (a row MAY end at any step),
#: which makes it the step-by-step twin of a default-built batcher
NEVER = dict(eos_id=-1)


def _prompt_of(i, n, vocab):
    return np.random.default_rng([9, i]).integers(0, vocab, n).astype(
        np.int32)


def _serve(cfg, params, vocab, kwargs, schedule, steps, between=None,
           **submit):
    """Drive a batcher of 3 slots through ``schedule`` (step -> [(prompt
    id, prompt length, budget)]), calling ``between(b, step)`` after each
    ``step()``; returns it, the finished streams and the tokens each turn
    emitted, in order."""
    b = ContinuousBatcher(cfg, params, max_batch=3, **kwargs)
    events, rids = [], {}
    for step in range(steps):
        for i, n, budget in schedule.get(step, []):
            rids[i] = b.submit(
                _prompt_of(i, n, vocab), budget, **submit,
                on_token=lambda rid, tok, step=step: events.append(
                    (step, rid, tok)))
        b.step()
        if between is not None:
            between(b, step)
    return b, {i: b.result(r) for i, r in rids.items()}, events


#: three slots filled at once with unequal budgets, a fourth request that
#: waits for the first to finish, so that steps with every slot seated
#: (the only ones that may run ahead) alternate with finishes, a free
#: slot, and an admission
AHEAD_SCHEDULE = {0: [(0, 11, 9), (1, 5, 14), (2, 7, 20)], 3: [(3, 6, 8)]}


@pytest.mark.parametrize("model,mode", [("lfm2", "paged"), ("lfm2", "default"),
                                        ("dense-gpt", "paged")])
def test_decode_ahead_serves_the_same_tokens_at_the_same_steps(
        made, model, mode):
    """A default-built batcher runs ahead; its twin never does."""
    cfg, params = _toy(made, model)
    vocab = cfg.vocab_size
    with jax.default_matmul_precision("highest"):
        off, want, ev_off = _serve(cfg, params, vocab,
                                   dict(MODES[mode], **NEVER),
                                   AHEAD_SCHEDULE, 30)
        on, got, ev_on = _serve(cfg, params, vocab, MODES[mode],
                                AHEAD_SCHEDULE, 30)
    assert all(v is not None for v in want.values())
    assert {i: v.tolist() for i, v in got.items()} \
        == {i: v.tolist() for i, v in want.items()}
    assert ev_on == ev_off      # token for token, step for step
    assert off.decode_ahead_dispatches == 0
    # every slot was seated over steps 1..8 and 10..13 or so: most of
    # those steps were queued ahead, and each was consumed
    assert on.decode_ahead_dispatches >= 6
    assert on._ahead is None
    assert on.decode_dispatches == off.decode_dispatches
    assert (on.expert_assignments, on.experts_touched) \
        == (off.expert_assignments, off.experts_touched)


@pytest.mark.parametrize("why,kwargs,schedule,submit", [
    ("an eos_id can end a row at any step", dict(eos_id=1),
     AHEAD_SCHEDULE, {}),
    ("a sampled row's step needs its host-side sampler state", {},
     AHEAD_SCHEDULE, dict(temperature=0.7, seed=3)),
    ("a chunked admission in flight takes its slot at an unknown step",
     dict(prefill_chunk=4), {0: [(0, 11, 20), (1, 5, 20)],
                             2: [(2, 30, 4)]}, {}),
    ("a row at its last token leaves at this step", {},
     {0: [(0, 11, 2), (1, 5, 2), (2, 7, 2)]}, {}),
])
def test_decode_ahead_stands_down(made, why, kwargs, schedule, submit):
    cfg, params = made
    seen = []

    def watch(b, step):
        # the chunked case: no step is queued while the admission streams
        seen.append((b._inflight is not None, b._ahead is not None))

    b, got, _ = _serve(cfg, params, 211, dict(MODES["paged"], **kwargs),
                       schedule, 30, between=watch, **submit)
    assert not any(inflight and ahead for inflight, ahead in seen), why
    if "prefill_chunk" in kwargs:
        assert any(inflight for inflight, _ in seen)
        ran = [ahead for inflight, ahead in seen if not inflight]
        assert b.decode_ahead_dispatches == sum(ran) > 0
    else:
        assert b.decode_ahead_dispatches == 0, why
    assert all(v is not None for v in got.values())


def test_decode_ahead_waits_for_every_slot_to_be_seated(made):
    """With a slot free a request may be admitted at the next step, so
    that step is not dispatched before it is known."""
    cfg, params = made
    b, got, _ = _serve(cfg, params, 211, MODES["paged"],
                       {0: [(0, 11, 9), (1, 5, 14)]}, 16)
    assert b.decode_ahead_dispatches == 0
    assert all(v is not None for v in got.values())


@pytest.mark.parametrize("other", [dict(speculative_k=2),
                                   dict(decode_block_steps=4)])
def test_decode_ahead_stands_down_for_its_alternatives(other):
    """Speculation and blocks decide each dispatch from the last one's
    tokens: such a batcher builds (keyword and all), serves the plain
    generator's tokens, and queues nothing ahead, not even on the plain
    steps it falls back to."""
    cfg, params = _dense(dtype=jnp.float32)     # no bfloat16 near-ties
    with jax.default_matmul_precision("highest"):
        b, got, _ = _serve(cfg, params, 50, dict(decode_ahead=True, **other),
                           AHEAD_SCHEDULE, 30)
        for i, n, budget in sum(AHEAD_SCHEDULE.values(), []):
            prompt = _prompt_of(i, n, 50)
            solo = np.asarray(greedy_generate(cfg, params, prompt[None],
                                              budget))[0]
            assert got[i].tolist() == solo[n:].tolist(), (other, i)
    assert b.decode_ahead_dispatches == 0 and b._ahead is None


# -- settle(), and who may meet a queued step -------------------------------

def _queued(cfg, params, kwargs):
    """A batcher stopped between two turns with a step queued ahead."""
    b = ContinuousBatcher(cfg, params, max_batch=3, **kwargs)
    prompts = {}
    for i, n, budget in AHEAD_SCHEDULE[0]:
        prompts[b.submit(_prompt_of(i, n, cfg.vocab_size), budget)] \
            = _prompt_of(i, n, cfg.vocab_size)
    for _ in range(3):
        b.step()
    assert b._ahead is not None
    return b, prompts


def test_settle_makes_cache_and_slots_agree(made):
    """After it the probe's logits match the reference at every seated
    slot; it emits the queued step's tokens, dispatches nothing, and a
    second call does nothing."""
    cfg, params = made
    with jax.default_matmul_precision("highest"):
        b, prompts = _queued(cfg, params, MODES["paged"])
        before = [len(s.tokens) for s in b.slots]
        counters = (b.decode_dispatches, b.decode_ahead_dispatches)
        assert b.settle() == []
        assert b._ahead is None
        assert [len(s.tokens) for s in b.slots] == [n + 1 for n in before]
        assert (b.decode_dispatches, b.decode_ahead_dispatches) \
            == (counters[0] + 1, counters[1])
        assert _check_slots(b, params, prompts) == 3
        assert b.settle() == []
        assert [len(s.tokens) for s in b.slots] == [n + 1 for n in before]
        assert b.decode_dispatches == counters[0] + 1
        # and the stream goes on where a twin that never settled is
        twin, _ = _queued(cfg, params, MODES["paged"])
        assert {r: v.tolist() for r, v in b.run().items()} \
            == {r: v.tolist() for r, v in twin.run().items()}


def test_settle_returns_what_the_queued_step_finished():
    cfg, params = _dense()
    b = ContinuousBatcher(cfg, params, max_batch=2)
    short = b.submit(_prompt_of(0, 6, 50), 2)
    b.submit(_prompt_of(1, 9, 50), 9)
    # both seated with their first tokens; short's second is its last, so
    # the step that makes it had none queued behind it
    assert b.step() == [short]
    assert b._ahead is None and b.settle() == []
    b = ContinuousBatcher(cfg, params, max_batch=2)
    short = b.submit(_prompt_of(0, 6, 50), 3)
    b.submit(_prompt_of(1, 9, 50), 9)
    b.step()            # the queued step is short's last
    assert b._ahead is not None
    assert b.settle() == [short]
    assert b.result(short) is not None and len(b.result(short)) == 3
    assert b.slots[0] is None or b.slots[1] is None


@pytest.mark.parametrize("reader", ["load_params", "park", "run",
                                    "export_prefix_cache"])
def test_a_queued_step_survives_its_readers(reader):
    """Each path that reads or replaces the cache, the parameters or a
    slot between two turns, where it can meet a queued step."""
    cfg, params = _dense()
    # load_params rebuilds the page index (it is for an idle batcher, which
    # has no step queued; unload_params refuses a busy one): called on a
    # busy one all the same, only a pool without an index survives it
    kwargs = dict(kv_page_tokens=4, prefix_cache=reader != "load_params")
    with jax.default_matmul_precision("highest"):
        b, _ = _queued(cfg, params, kwargs)
        twin, _ = _queued(cfg, params, kwargs)
        queued = b._ahead
        if reader == "load_params":
            # the step in flight keeps the parameters it was dispatched
            # with and is not lost: consumed by the next turn as it lies
            b.load_params(jax.tree.map(np.asarray, params))
            assert b._ahead is queued
        elif reader == "park":
            # a slot is only ever parked by the turn that consumed the
            # queued step: none is queued behind a row that ends
            parked = []
            park = b._park_slot
            b._park_slot = lambda i: (parked.append(b._ahead), park(i))[1]
            b.run()
            assert len(parked) == 3 and all(a is None for a in parked)
        elif reader == "export_prefix_cache":
            # indexed prompt pages are written by no decode step: the
            # snapshot is the same with the step queued or settled
            got = b.export_prefix_cache()
            twin.settle()
            want = twin.export_prefix_cache()
            assert got["page_hashes"] == want["page_hashes"] != []
            assert b._ahead is queued
        out = b.run()
        assert b._ahead is None and not any(b.slots)
        assert {r: v.tolist() for r, v in out.items()} \
            == {r: v.tolist() for r, v in twin.run().items()}


# -- the expert layer's kernel: the same requests, kernel against ragged_dot

@pytest.fixture
def as_on_tpu(monkeypatch):
    """The expert layer's rule sees a TPU backend (``models.moe.
    streams_experts_once``); the kernel itself still sees the CPU and runs
    under the interpreter."""
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)


def _calls_per_dispatch(b):
    return b.grouped_matmul_calls / (b.decode_dispatches
                                     + b.prefill_dispatches)


#: "greedy" is the step-by-step twin (NEVER), "decode_ahead" what a
#: default-built batcher does
@pytest.mark.parametrize("mode,kwargs", [("greedy", NEVER),
                                         ("decode_ahead", {})])
def test_batcher_streams_through_the_kernel_as_through_ragged_dot(
        made, monkeypatch, mode, kwargs):
    cfg, params = made
    kw = dict(MODES["paged"], **kwargs)
    with jax.default_matmul_precision("highest"):
        plain, want, ev_plain = _serve(cfg, params, cfg.vocab_size, kw,
                                       AHEAD_SCHEDULE, 30)
        with monkeypatch.context() as m:
            m.setattr(moe, "_on_tpu", lambda: True)
            kern, got, ev_kern = _serve(cfg, params, cfg.vocab_size, kw,
                                        AHEAD_SCHEDULE, 30)
            text = kern._step.lower(
                params, kern.cache, jnp.zeros(
                    (3 + 3 * cfg.num_expert_layers,), jnp.int32)).as_text()
    assert all(v is not None for v in want.values())
    assert {i: v.tolist() for i, v in got.items()} \
        == {i: v.tolist() for i, v in want.items()}
    assert ev_kern == ev_plain      # token for token, step for step
    assert "ragged" not in text
    assert (kern.decode_ahead_dispatches > 0) == (mode == "decode_ahead")
    # the router's counts ride with the tokens on both paths
    assert (kern.expert_assignments, kern.expert_peak_assignments,
            kern.experts_touched) \
        == (plain.expert_assignments, plain.expert_peak_assignments,
            plain.experts_touched)
    assert kern.expert_assignments > 0
    # a fused gate-and-up call and a down call per expert layer, in every
    # decode and prefill dispatch; none where ragged_dot ran
    assert _calls_per_dispatch(kern) == 2 * cfg.num_expert_layers == 6
    assert plain.grouped_matmul_calls == 0 and plain.decode_dispatches > 0


@pytest.mark.parametrize("model,kw", [
    ("dense-gpt", {}),
    ("retention", dict(layer_types=("retention",) * 2,
                       tie_word_embeddings=False))])
def test_a_model_without_experts_counts_no_kernel_call(as_on_tpu, model, kw):
    cfg, params = _dense(**kw)
    b = ContinuousBatcher(cfg, params, max_batch=2)
    b.submit(_prompt_of(0, 5, cfg.vocab_size), 4)
    b.run()
    assert b.decode_dispatches > 0 and b.grouped_matmul_calls == 0
