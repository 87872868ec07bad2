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
    emitted, in order: (turn, request id, token)."""
    b = ContinuousBatcher(cfg, params, max_batch=3, **kwargs)
    events, rids, turn = [], {}, [0]
    for step in range(steps):
        turn[0] = step
        for i, n, budget in schedule.get(step, []):
            rids[i] = b.submit(
                _prompt_of(i, n, vocab), budget, **submit,
                on_token=lambda rid, tok: events.append(
                    (turn[0], rid, tok)))
        b.step()
        if between is not None:
            between(b, step)
    return b, {i: b.result(r) for i, r in rids.items()}, events


#: three slots filled at once with unequal budgets, a fourth request that
#: waits for the first to finish, so that steps with every slot seated
#: alternate with finishes by budget, a free slot, and an admission behind
#: a queued step
AHEAD_SCHEDULE = {0: [(0, 11, 9), (1, 5, 14), (2, 7, 20)], 3: [(3, 6, 8)]}


def _streams(events):
    """request id -> [(step of the ``step()`` that emitted it, token)]."""
    out = {}
    for step, rid, tok in events:
        out.setdefault(rid, []).append((step, tok))
    return out


@pytest.mark.parametrize("model,mode", [("lfm2", "paged"), ("lfm2", "default"),
                                        ("dense-gpt", "paged")])
def test_decode_ahead_serves_the_same_tokens_at_the_same_steps(
        made, model, mode):
    """A default-built batcher runs ahead; its twin never does.  Every
    request gets its twin's tokens; a row admitted behind a queued step
    gets its first token in the ``step()`` of its admission, as its twin
    does, and joins the decode at the next dispatch: none of its tokens
    comes more than one ``step()`` after the twin's."""
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
    s_on, s_off = _streams(ev_on), _streams(ev_off)
    assert s_on.keys() == s_off.keys()
    late = 0
    for rid, twin in s_off.items():
        mine = s_on[rid]
        assert [t for _, t in mine] == [t for _, t in twin]
        assert mine[0][0] == twin[0][0], "first token: step of admission"
        lags = [a - b for (a, _), (b, _) in zip(mine, twin)]
        assert set(lags) <= {0, 1}, (rid, lags)
        late += any(lags)
    assert late == 1        # the one row admitted behind a queued step
    assert off.decode_ahead_dispatches == 0
    # all but the admission's step, the one after it and the last were
    # queued ahead, and each was consumed
    assert on.decode_ahead_dispatches >= on.decode_dispatches - 3
    assert on._ahead is None
    assert on.decode_dispatches == off.decode_dispatches
    for b in (on, off):
        assert sum(b.decode_ahead_standdowns.values()) \
            == b.decode_dispatches - b.decode_ahead_dispatches
    assert off.decode_ahead_standdowns["eos"] == off.decode_dispatches
    assert on.decode_ahead_standdowns["admission"] == 1
    assert on.decode_ahead_standdowns["idle"] == 1
    # a step runs every row, seated or parked (what a parked row is fed,
    # and so which experts it touches, is not the twin's)
    assert on.expert_assignments == off.expert_assignments


@pytest.mark.parametrize("why,kwargs,schedule,submit", [
    ("eos", dict(eos_id=1), AHEAD_SCHEDULE, {}),
    ("sampled", {}, AHEAD_SCHEDULE, dict(temperature=0.7, seed=3)),
    ("chunked", dict(prefill_chunk=4), {0: [(0, 11, 20), (1, 5, 20)],
                                        2: [(2, 30, 4)]}, {}),
    ("idle", {}, {0: [(0, 11, 2), (1, 5, 2), (2, 7, 2)]}, {}),
])
def test_decode_ahead_stands_down(made, why, kwargs, schedule, submit):
    """An ``eos_id`` can end a row at any step; a sampled row's step needs
    its host-side sampler state; a chunked admission in flight takes its
    slot at a step of its own; and where every row is at its last token
    there is no next step."""
    cfg, params = made
    seen = []

    def watch(b, step):
        # the chunked case: no step is queued while the admission streams
        seen.append((b._inflight is not None and any(b.slots),
                     b.step_queued))

    b, got, _ = _serve(cfg, params, 211, dict(MODES["paged"], **kwargs),
                       schedule, 30, between=watch, **submit)
    assert not any(inflight and ahead for inflight, ahead in seen), why
    if why == "chunked":
        assert any(inflight for inflight, _ in seen)
        ran = [ahead for inflight, ahead in seen if not inflight]
        assert b.decode_ahead_dispatches == sum(ran) > 0
        assert b.decode_ahead_standdowns[why] \
            == sum(inflight for inflight, _ in seen)
    else:
        assert b.decode_ahead_dispatches == 0, why
        assert b.decode_ahead_standdowns[why] == b.decode_dispatches > 0
    assert sum(b.decode_ahead_standdowns.values()) \
        == b.decode_dispatches - b.decode_ahead_dispatches
    assert all(v is not None for v in got.values())


def _same_tokens_as_the_twin(cfg, params, kwargs, schedule, steps=24,
                             between=None):
    """Serve ``schedule`` by a default-built batcher (``between`` as in
    ``_serve``) and by its twin that never runs ahead; returns the first,
    its tokens held to the twin's."""
    vocab = cfg.vocab_size
    with jax.default_matmul_precision("highest"):
        _, want, _ = _serve(cfg, params, vocab, dict(kwargs, **NEVER),
                            schedule, steps)
        b, got, _ = _serve(cfg, params, vocab, kwargs, schedule, steps,
                           between=between)
    assert all(v is not None for v in want.values())
    assert {i: v.tolist() for i, v in got.items()} \
        == {i: v.tolist() for i, v in want.items()}
    return b


def test_decode_ahead_runs_with_a_slot_free(made):
    """A step runs every row, seated or parked: with a slot free and
    nothing admitted the next step's rows are decided all the same."""
    cfg, params = made
    b = _same_tokens_as_the_twin(cfg, params, MODES["paged"],
                                 {0: [(0, 11, 9), (1, 5, 14)]}, 16)
    # every step but the last had the next one queued behind it
    assert b.decode_ahead_dispatches == b.decode_dispatches - 1 == 12
    assert b.decode_ahead_standdowns["idle"] == 1


@pytest.mark.parametrize("model", ["lfm2", "dense-gpt"])
def test_a_row_that_ends_by_budget_is_parked_ahead_and_once(made, model):
    """Rows at their last token end at the step about to be fetched: they
    are parked behind it and the next step is queued behind that, both
    before the fetch; the finish does not park them again.  The last row
    to end has no step to park ahead of."""
    cfg, params = _toy(made, model)
    order = []

    def spy(b, _step):
        if order:
            return
        park, fetch = b._park_slot, b._fetch
        b._park_slot = lambda i: (order.append(("park", i)), park(i))[1]
        b._fetch = lambda *a, **k: (order.append(("fetch",)),
                                    fetch(*a, **k))[1]

    b = _same_tokens_as_the_twin(
        cfg, params, MODES["paged"],
        {0: [(0, 11, 3), (1, 5, 3), (2, 7, 6)]}, 8, between=spy)
    # step 1 makes the third tokens of rows 0 and 1: both parked, and the
    # next step dispatched, before its fetch; row 2 parks at its finish
    assert order[:3] == [("park", 0), ("park", 1), ("fetch",)]
    assert [e for e in order if e[0] == "park"] \
        == [("park", 0), ("park", 1), ("park", 2)]
    assert order[-2:] == [("fetch",), ("park", 2)]
    assert b.decode_ahead_dispatches == b.decode_dispatches - 1


def _row_pages(b, request_id):
    """The leased pages of the seated request, and their contents."""
    s = next(s for s in b.slots if s and s.request_id == request_id)
    return list(s.lease.page_ids), b._gather_pages(s.lease.page_ids)


def test_pages_released_at_a_finish_hold_what_the_next_row_wrote():
    """A pool of exactly three rows' pages: the request that waits is
    leased the pages its predecessor released, while a step is queued
    that ran the predecessor's row parked.  That step wrote none of them:
    page for page they hold what the twin's hold."""
    cfg, params = _dense(dtype=jnp.float32)
    kwargs = dict(kv_page_tokens=4, kv_pool_pages=21, prefix_cache=False)
    found = {}
    with jax.default_matmul_precision("highest"):
        for name, kw in (("on", kwargs), ("off", dict(kwargs, **NEVER))):
            b = ContinuousBatcher(cfg, params, max_batch=3, **kw)
            for i, n, budget in ((0, 11, 5), (1, 5, 20), (2, 7, 20)):
                b.submit(_prompt_of(i, n, 50), budget)
            last = b.submit(_prompt_of(3, 9, 50), 12)
            while b.result(0) is None:
                b.step()
            assert b.step_queued == (name == "on")
            b.step()        # leased what request 0 released
            assert any(s and s.request_id == last for s in b.slots)
            while len(next(s for s in b.slots if s
                           and s.request_id == last).tokens) < 4:
                b.step()
            b.settle()
            found[name] = b
        on, off = found["on"], found["off"]
        n = len(next(s for s in on.slots
                     if s and s.request_id == last).tokens)
        while len(next(s for s in off.slots
                       if s and s.request_id == last).tokens) < n:
            off.step()
        ids_on, kv_on = _row_pages(on, last)
        ids_off, kv_off = _row_pages(off, last)
    assert ids_on == ids_off and len(ids_on) == 6
    for a, b in zip(kv_on, kv_off):
        np.testing.assert_array_equal(a, b)


def test_a_slot_parked_ahead_is_reseated_from_zero_state(made):
    """The conv state of a row that ended by budget is cleared between its
    last step and the step queued behind it; the next request seated there
    starts from ITS prefill, and the row beside it, which the queued step
    ran, is where the reference is."""
    cfg, params = made
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(cfg, params, max_batch=2, **MODES["paged"])
        prompts = {b.submit(_prompt(0, 9), 3): _prompt(0, 9),
                   b.submit(_prompt(1, 6), 14): _prompt(1, 6)}
        b.step()
        assert b.step() == [0]       # parked ahead of this fetch
        assert b.step_queued and b.slots[0] is None
        assert b.decode_ahead_standdowns == dict.fromkeys(
            b.decode_ahead_standdowns, 0)
        prompts[b.submit(_prompt(2, 7), 8)] = _prompt(2, 7)
        b.step()                        # prefilled behind the queued step
        assert b.slots[0] is not None and b.state_rows_seated == 3
        assert _check_slots(b, params, prompts) == 2
        b.step()
        assert _check_slots(b, params, prompts) == 2


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
    assert b.decode_ahead_standdowns["alternative"] \
        == b.decode_dispatches > 0


# -- settle(), and who may meet a queued step -------------------------------

def _queued(cfg, params, kwargs, seated=3):
    """A batcher stopped between two turns with a step queued ahead,
    ``seated`` of its three slots taken."""
    b = ContinuousBatcher(cfg, params, max_batch=3, **kwargs)
    prompts = {}
    for i, n, budget in AHEAD_SCHEDULE[0][:seated]:
        prompts[b.submit(_prompt_of(i, n, cfg.vocab_size), budget)] \
            = _prompt_of(i, n, cfg.vocab_size)
    for _ in range(3):
        b.step()
    assert b.step_queued and sum(s is not None for s in b.slots) == seated
    return b, prompts


@pytest.mark.parametrize("seated", [3, 2])
def test_settle_makes_cache_and_slots_agree(made, seated):
    """After it the probe's logits match the reference at every seated
    slot, a slot free or not; it emits the queued step's tokens,
    dispatches nothing, and a second call does nothing."""
    cfg, params = made
    with jax.default_matmul_precision("highest"):
        b, prompts = _queued(cfg, params, MODES["paged"], seated)
        before = [len(s.tokens) for s in b.slots if s]
        counters = (b.decode_dispatches, b.decode_ahead_dispatches)
        assert b.settle() == []
        assert b._ahead is None and b.decode_ahead_standdowns["settle"] == 1
        assert [len(s.tokens) for s in b.slots if s] \
            == [n + 1 for n in before]
        assert (b.decode_dispatches, b.decode_ahead_dispatches) \
            == (counters[0] + 1, counters[1])
        assert _check_slots(b, params, prompts) == seated
        assert b.settle() == []
        assert [len(s.tokens) for s in b.slots if s] \
            == [n + 1 for n in before]
        assert b.decode_dispatches == counters[0] + 1
        # and the stream goes on where a twin that never settled is
        twin, _ = _queued(cfg, params, MODES["paged"], seated)
        assert {r: v.tolist() for r, v in b.run().items()} \
            == {r: v.tolist() for r, v in twin.run().items()}


def test_settle_returns_what_the_queued_step_finished():
    cfg, params = _dense()
    b = ContinuousBatcher(cfg, params, max_batch=2)
    short = b.submit(_prompt_of(0, 6, 50), 2)
    b.submit(_prompt_of(1, 9, 50), 9)
    # both seated with their first tokens; short's second is its last: it
    # is parked behind the step that makes it, the next queued behind that
    assert b.step() == [short]
    assert b.step_queued and b.settle() == []
    b = ContinuousBatcher(cfg, params, max_batch=2)
    short = b.submit(_prompt_of(0, 6, 50), 3)
    b.submit(_prompt_of(1, 9, 50), 9)
    b.step()            # the queued step is short's last
    assert b._ahead is not None
    assert b.settle() == [short]
    assert b.result(short) is not None and len(b.result(short)) == 3
    assert b.slots[0] is None or b.slots[1] is None


def _session(cfg, params, kwargs, prompt, budget):
    """A handoff session of ``prompt``, from a prefill-only batcher."""
    p = ContinuousBatcher(cfg, params, max_batch=1, prefill_only=True,
                          **kwargs)
    p.submit(prompt, budget)
    p.step()
    return p.take_sessions()[0][1]


@pytest.mark.parametrize("reader,seated", [
    ("load_params", 3), ("run", 3), ("run", 2),
    ("export_prefix_cache", 3), ("export_prefix_cache", 2),
    ("import_prefix_cache", 2), ("adopt_session", 2), ("submit", 2),
    ("unload_params", 2)])
def test_a_queued_step_survives_its_readers(reader, seated):
    """Each path that reads or replaces the cache, the parameters or a
    slot between two turns, where it can meet a queued step, with every
    slot seated or one free."""
    cfg, params = _dense()
    # load_params rebuilds the page index (it is for an idle batcher, which
    # has no step queued; unload_params refuses a busy one): called on a
    # busy one all the same, only a pool without an index survives it
    kwargs = dict(kv_page_tokens=4, prefix_cache=reader != "load_params")
    with jax.default_matmul_precision("highest"):
        b, _ = _queued(cfg, params, kwargs, seated)
        # the twin meets the same reader with its queued step consumed
        twin, _ = _queued(cfg, params, kwargs, seated)
        twin.settle()
        queued = b._ahead
        if reader == "load_params":
            # the step in flight keeps the parameters it was dispatched
            # with and is not lost: consumed by the next turn as it lies
            b.load_params(jax.tree.map(np.asarray, params))
            assert b._ahead is queued
        elif reader == "unload_params":
            # none is queued without a seated row that goes on, and a
            # busy batcher refuses
            with pytest.raises(RuntimeError, match="live requests"):
                b.unload_params()
        elif reader == "export_prefix_cache":
            # indexed prompt pages are written by no decode step: the
            # snapshot is the same with the step queued or settled
            got = b.export_prefix_cache()
            want = twin.export_prefix_cache()
            assert got["page_hashes"] == want["page_hashes"] != []
            assert b._ahead is queued
        elif reader == "import_prefix_cache":
            # a donated page set lands in free pages, behind the step
            donor = ContinuousBatcher(cfg, params, max_batch=1, **kwargs)
            donor.submit(_prompt_of(7, 13, 50), 2)
            donor.run()
            export = donor.export_prefix_cache()
            assert b.import_prefix_cache(export) \
                == twin.import_prefix_cache(export) == 3
            assert b._ahead is queued
            for x in (b, twin):     # and the next admission matches them
                x.submit(_prompt_of(7, 13, 50), 5)
        elif reader == "adopt_session":
            # seated behind the queued step, into the free slot's row and
            # freshly leased pages; it joins the decode at the next dispatch
            session = _session(cfg, params, kwargs, _prompt_of(5, 10, 50), 7)
            for x in (b, twin):
                x.adopt_session(session)
            b.step()
            assert sum(s is not None for s in b.slots) == 3
            assert b.decode_ahead_standdowns["admission"] == 1
            assert b.step_queued        # dispatched from the host's tokens
        elif reader == "submit":
            # prefilled behind the queued step, which is consumed first
            for x in (b, twin):
                x.submit(_prompt_of(5, 10, 50), 7)
            ahead = b.decode_ahead_dispatches
            b.step()
            assert sum(s is not None for s in b.slots) == 3
            assert b.decode_ahead_standdowns["admission"] == 1
            # the next step went out as soon as the first token was on
            # the host: queued, though behind no running step
            assert b.step_queued and b.decode_ahead_dispatches == ahead
        out = b.run()
        assert b._ahead is None and not any(b.slots)
        assert {r: v.tolist() for r, v in out.items()} \
            == {r: v.tolist() for r, v in twin.run().items()}
        assert sum(b.decode_ahead_standdowns.values()) \
            == b.decode_dispatches - b.decode_ahead_dispatches


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
