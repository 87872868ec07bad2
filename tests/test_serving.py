"""Continuous batching (``models/serving.py``): greedy-exact output per
request regardless of admission order, slot reuse, or batch company.

The oracle for every request is a SOLO ``greedy_generate`` run on its
prompt (the scalar-index decode path) — so these tests also lock the
per-row-position substrate (``GPTConfig.per_row_positions``) against the
reference implementation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models import (GPT, GPTConfig, ContinuousBatcher,
                                          greedy_generate)


def _make(pos_encoding="rope", **kw):
    kw.setdefault("max_position_embeddings", 48)
    cfg = GPTConfig(vocab_size=61, hidden_size=32, num_layers=2, num_heads=4,
                    intermediate_size=64,
                    dtype=jnp.float32, pos_encoding=pos_encoding, **kw)
    params = GPT(cfg).init(jax.random.key(0),
                           jnp.ones((1, 4), jnp.int32))["params"]
    return cfg, params


def _oracle(cfg, params, prompt, n):
    out = greedy_generate(cfg, params, jnp.asarray(prompt)[None, :], n)
    return np.asarray(out)[0, len(prompt):]


@pytest.mark.parametrize("pos_encoding", ["rope", "learned"])
def test_staggered_requests_match_solo_greedy(pos_encoding):
    """More requests than slots, different prompt lengths and budgets:
    every request's tokens equal its solo greedy run."""
    cfg, params = _make(pos_encoding)
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32), n)
            for t, n in ((5, 7), (3, 12), (8, 4), (5, 9), (2, 6), (6, 1))]

    b = ContinuousBatcher(cfg, params, max_batch=2)
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()

    assert sorted(results) == sorted(rids)
    for rid, (prompt, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, prompt, n))


def test_unload_load_params_keeps_compiled_exactness():
    """The warm-standby posture: unload drops the weights but keeps the
    compiled executables; a reloaded (host-numpy, peer-cloned-shaped)
    tree decodes token-identically with no live-state carryover.
    Guards: submit while weightless raises; unload with live work
    refuses."""
    cfg, params = _make()
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    b = ContinuousBatcher(cfg, params, max_batch=2)
    rid = b.submit(prompt, 6)
    with pytest.raises(RuntimeError, match="live requests"):
        b.unload_params()                 # in-flight work: refuse
    want = b.run()[rid]
    b.unload_params()
    assert b.params is None
    with pytest.raises(RuntimeError, match="no parameters"):
        b.submit(prompt, 2)
    with pytest.raises(ValueError):
        b.load_params(None)
    # reload a HOST tree (what a peer clone delivers) — same executables
    b.load_params(jax.tree.map(lambda x: np.asarray(x), params))
    rid2 = b.submit(prompt, 6)
    np.testing.assert_array_equal(b.run()[rid2], want)
    np.testing.assert_array_equal(want, _oracle(cfg, params, prompt, 6))


def test_load_params_drops_stale_prefix_cache():
    """Paged mode: a parameter swap must rebuild the prefix index empty —
    cached pages hold KV computed under the OLD weights, and a post-swap
    hit against them would decode wrong tokens when the trees differ."""
    cfg, params = _make()
    prompt = np.arange(1, 25, dtype=np.int32)      # spans whole pages
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    rid = b.submit(prompt, 4)
    b.run()
    b.result(rid, pop=True)
    assert b.prefix_stats()["cached_pages"] > 0    # index is warm
    b.unload_params()
    # a DIFFERENT tree (fresh seed): the old pages are poison now
    params2 = GPT(cfg).init(jax.random.key(7),
                            jnp.ones((1, 4), jnp.int32))["params"]
    b.load_params(jax.device_put(params2))
    assert b.prefix_stats()["cached_pages"] == 0   # index flushed
    rid2 = b.submit(prompt, 4)
    out = b.run()[rid2]
    assert b.prefix_stats()["hit"] == 0, "stale prefix page was reused"
    np.testing.assert_array_equal(out, _oracle(cfg, params2, prompt, 4))


def test_mid_flight_admission_does_not_disturb_running_slots():
    """Submit while another request is mid-decode; both stay exact."""
    cfg, params = _make()
    rng = np.random.default_rng(1)
    p1 = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)

    b = ContinuousBatcher(cfg, params, max_batch=2)
    r1 = b.submit(p1, 10)
    for _ in range(4):           # r1 alone for a few steps
        b.step()
    r2 = b.submit(p2, 5)         # admitted mid-flight of r1
    results = b.run()

    np.testing.assert_array_equal(results[r1], _oracle(cfg, params, p1, 10))
    np.testing.assert_array_equal(results[r2], _oracle(cfg, params, p2, 5))


def test_eos_frees_slot_early_and_slot_reuse_is_clean():
    """A request stopping at eos releases its slot; the slot's next
    tenant is unaffected by the leftover cache rows."""
    cfg, params = _make()
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
    # pick the eos id as the 3rd token the oracle would emit, so the
    # request genuinely stops early
    oracle1 = _oracle(cfg, params, p1, 10)
    eos = int(oracle1[2])
    p2 = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)

    b = ContinuousBatcher(cfg, params, max_batch=1, eos_id=eos)
    r1 = b.submit(p1, 10)
    r2 = b.submit(p2, 6)         # waits for the only slot
    results = b.run()

    # r1: truncated at (and including) the FIRST eos occurrence
    first = list(oracle1).index(eos)
    np.testing.assert_array_equal(results[r1], oracle1[:first + 1])
    assert len(results[r1]) < len(oracle1), "eos did not stop early"
    # r2 reused r1's slot; exactness = prefix-up-to-eos of its solo run
    want2 = _oracle(cfg, params, p2, 6)
    got2 = results[r2]
    if eos in want2:
        want2 = want2[:list(want2).index(eos) + 1]
    np.testing.assert_array_equal(got2, want2)


def test_single_step_budget_and_validation():
    cfg, params = _make()
    with pytest.raises(ValueError, match="max_batch"):
        ContinuousBatcher(cfg, params, max_batch=0)
    b = ContinuousBatcher(cfg, params, max_batch=2)
    with pytest.raises(ValueError, match="empty prompt"):
        b.submit(np.array([], np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        b.submit(np.array([1, 2], np.int32), 0)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        b.submit(np.arange(40, dtype=np.int32), 20)
    rid = b.submit(np.array([1, 2, 3], np.int32), 1)  # 1-token budget
    # finishing AT admission must still be reported by step()
    assert b.step() == [rid]
    results = b.run()
    np.testing.assert_array_equal(results[rid],
                                  _oracle(cfg, params, [1, 2, 3], 1))


def test_has_free_slot_counts_pending():
    """The documented drive loop 'submit while has_free_slot()' must
    terminate: queued requests count against free slots."""
    cfg, params = _make()
    b = ContinuousBatcher(cfg, params, max_batch=2)
    n = 0
    while b.has_free_slot():
        b.submit(np.array([1, 2], np.int32), 3)
        n += 1
        assert n <= 2, "has_free_slot ignored the pending queue"
    assert n == 2


def test_one_decode_executable_for_the_lifetime():
    """The decode step never recompiles across admissions/retirements."""
    cfg, params = _make()
    b = ContinuousBatcher(cfg, params, max_batch=2)
    b.submit(np.array([1, 2], np.int32), 3)
    b.submit(np.array([3, 4, 5], np.int32), 8)
    b.submit(np.array([6], np.int32), 4)
    b.run()
    assert b._step._cache_size() == 1, "decode step recompiled"


def test_rolling_cache_rejected():
    cfg, params = _make(sliding_window=8, rolling_kv_cache=True)
    with pytest.raises(ValueError, match="rolling_kv_cache"):
        ContinuousBatcher(cfg, params, max_batch=2)


def _variant_setup(variant):
    """(cfg, params) for one decode-feature variant — shared by the plain
    and speculative composition matrices so the two cannot drift."""
    kw = {}
    if variant == "gqa":
        kw["num_kv_heads"] = 2
    if variant == "window":
        kw["sliding_window"] = 8
    cfg, params = _make("rope", **kw)
    if variant in ("int8", "int4"):
        from tensorflowonspark_tpu.ops import quantize_params

        params = quantize_params(params,
                                 bits=4 if variant == "int4" else 8)
    return cfg, params


@pytest.mark.parametrize("variant", ["int8", "int4", "gqa", "window"])
def test_serving_composes_with_decode_features(variant):
    """Continuous batching must stay greedy-exact under the decode
    stack's other features: int8/int4 weight-only quantization,
    grouped-query attention, sliding-window attention (full cache)."""
    cfg, params = _variant_setup(variant)

    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32), n)
            for t, n in ((4, 6), (7, 9), (3, 5))]
    b = ContinuousBatcher(cfg, params, max_batch=2)
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid], _oracle(cfg, params, p, n))


def test_serving_with_tp_sharded_params_under_mesh():
    """Distributed inference: ContinuousBatcher over Megatron-tp-sharded
    parameters on a 2-device mesh — greedy-exact against a solo sharded
    greedy run (same reduction order), with params verified actually
    sharded over tp."""
    from tensorflowonspark_tpu.parallel import MeshSpec, make_mesh
    from tensorflowonspark_tpu.parallel.sharding import flax_shardings

    # vocab divisible by tp (tok_emb shards its rows over tp)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                    intermediate_size=64, max_position_embeddings=48,
                    dtype=jnp.float32, pos_encoding="rope")
    params = GPT(cfg).init(jax.random.key(0),
                           jnp.ones((1, 4), jnp.int32))["params"]
    mesh = make_mesh(MeshSpec(tp=2, dp=1), devices=jax.devices()[:2])

    model = GPT(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 4), jnp.int32)))
    shardings = flax_shardings(mesh, abstract)["params"]
    sharded = jax.device_put(params, shardings)
    n_tp = sum("tp" in str(s.spec) for s in jax.tree.leaves(shardings))
    assert n_tp > 0, "no parameter actually sharded over tp"

    rng = np.random.default_rng(4)
    reqs = [(rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32), n)
            for t, n in ((5, 8), (3, 11), (7, 5))]
    with mesh:
        b = ContinuousBatcher(cfg, sharded, max_batch=2)
        rids = [b.submit(p, n) for p, n in reqs]
        results = b.run()
        for rid, (p, n) in zip(rids, reqs):
            want = np.asarray(greedy_generate(
                cfg, sharded, jnp.asarray(p)[None, :], n))[0, len(p):]
            np.testing.assert_array_equal(results[rid], want)


def test_sampling_deterministic_and_company_independent():
    """A sampled request's tokens are a pure function of (seed, temp,
    top_p) — identical alone, batched with greedy neighbors, or after
    slot churn; and greedy neighbors stay greedy-exact next to it."""
    cfg, params = _make()
    rng = np.random.default_rng(5)
    p = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
    pg = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)

    def sampled_run(extra_greedy):
        b = ContinuousBatcher(cfg, params, max_batch=2)
        rid = b.submit(p, 9, temperature=0.8, top_p=0.9, seed=123)
        gids = [b.submit(pg, n) for n in extra_greedy]
        res = b.run()
        return res[rid], [res[g] for g in gids]

    alone, _ = sampled_run([])
    with_company, greedy_outs = sampled_run([6, 3, 7])
    np.testing.assert_array_equal(alone, with_company)
    for g in greedy_outs:
        np.testing.assert_array_equal(
            g, _oracle(cfg, params, pg, len(g)))

    # a different seed must (overwhelmingly) change the trajectory
    b = ContinuousBatcher(cfg, params, max_batch=1)
    rid = b.submit(p, 9, temperature=0.8, top_p=0.9, seed=124)
    other = b.run()[rid]
    assert not np.array_equal(alone, other)


def test_tiny_top_p_equals_greedy():
    """top_p -> 0 keeps only the argmax token: sampling must reduce to
    the greedy trajectory at any temperature."""
    cfg, params = _make()
    rng = np.random.default_rng(6)
    p = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    b = ContinuousBatcher(cfg, params, max_batch=1)
    rid = b.submit(p, 8, temperature=1.3, top_p=1e-6, seed=7)
    np.testing.assert_array_equal(b.run()[rid], _oracle(cfg, params, p, 8))


def test_sampling_validation():
    cfg, params = _make()
    b = ContinuousBatcher(cfg, params, max_batch=1)
    with pytest.raises(ValueError, match="temperature"):
        b.submit(np.array([1], np.int32), 2, temperature=-0.1)
    with pytest.raises(ValueError, match="top_p"):
        b.submit(np.array([1], np.int32), 2, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        b.submit(np.array([1], np.int32), 2, top_p=1.5)


def test_seed_must_fit_int32():
    cfg, params = _make()
    b = ContinuousBatcher(cfg, params, max_batch=1)
    with pytest.raises(ValueError, match="seed"):
        b.submit(np.array([1], np.int32), 2, temperature=0.5, seed=2**35)


def test_batcher_nucleus_matches_sample_generate_filter():
    """Serving and sample_generate share nucleus_filter — same kept set
    (ties included) on a crafted tied distribution."""
    from tensorflowonspark_tpu.models.gpt import nucleus_filter

    logits = jnp.asarray([3.0, 2.0, 2.0, 0.0, -1.0])
    out = nucleus_filter(logits, 0.75)
    # top token (p~0.58) kept; both TIED 2.0 tokens kept (threshold
    # semantics), tail masked
    assert np.isfinite(np.asarray(out[:3])).all()
    assert np.isneginf(np.asarray(out[3:])).all()


def test_prefill_bucketing_is_exact_and_bounds_compiles():
    """Right-padded power-of-two prefill buckets: every prompt length in
    3..9 stays greedy-exact, and the prefill compile count is the
    (bucket, group-size) count, not the length count.  Equal budgets make
    slots free in pairs, so same-bucket pairs share batched executables:
    (3,4)->bucket4 group2, (5,6) and (7,8)->bucket8 group2 (reused),
    9->bucket16 solo."""
    cfg, params = _make()
    rng = np.random.default_rng(8)
    b = ContinuousBatcher(cfg, params, max_batch=2)
    reqs = [(rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32), 6)
            for t in range(3, 10)]
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, p, n))
    assert {k for k in b._prefill_jit if k[0] == "final"} \
        == {("final", 4, 2), ("final", 8, 2), ("final", 16, 1)}, \
        sorted(map(str, b._prefill_jit))


@pytest.mark.parametrize("pos_encoding", ["rope", "learned"])
def test_chunked_prefill_matches_whole(pos_encoding):
    """Long-context admission: prompts prefilled in fixed chunks through
    the cached decode path are greedy-exact vs the whole-prompt oracle,
    and the chunk loop adds only (chunk + final-bucket) executables."""
    cfg, params = _make(pos_encoding)
    rng = np.random.default_rng(9)
    b = ContinuousBatcher(cfg, params, max_batch=2, prefill_chunk=6)
    reqs = [(rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32), 5)
            for t in (20, 23, 4)]   # 4 <= chunk -> whole-prompt path
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, p, n))
    keys = set(b._prefill_jit)
    assert ("chunk", 6) in keys
    # chunked finals run solo (rest 2, 5 -> buckets 2, 8 at group 1) +
    # the short whole prompt (bucket 4, admitted alone once slots free)
    assert {k for k in keys if k[0] == "final"} \
        == {("final", 2, 1), ("final", 8, 1), ("final", 4, 1)}


def test_failed_step_poisons_the_batcher():
    """A device failure mid-step leaves the donated cache unrecoverable:
    the batcher must refuse further use with an error naming the original
    failure, instead of silently decoding from a poisoned cache."""
    cfg, params = _make()
    b = ContinuousBatcher(cfg, params, max_batch=2)
    b.submit(np.asarray([1, 2, 3], np.int32), 5)
    b.step()
    boom = RuntimeError("RESOURCE_EXHAUSTED: synthetic device OOM")

    def raising_step(params, cache, tokens):
        raise boom
    b._step = raising_step
    with pytest.raises(RuntimeError, match="synthetic device OOM"):
        b.step()
    for call in (b.step, b.run, lambda: b.submit([1], 1)):
        with pytest.raises(RuntimeError, match="unusable(.|\n)*synthetic"):
            call()


def test_burst_admission_shares_one_prefill_dispatch():
    """A burst of same-bucket arrivals is admitted with ONE batched
    prefill call, which seats the rows too — and every request stays
    greedy-exact vs its solo oracle (batching must not change
    numerics)."""
    cfg, params = _make()
    rng = np.random.default_rng(11)
    b = ContinuousBatcher(cfg, params, max_batch=8)
    calls = []
    orig = b._prefill
    b._prefill = lambda *a: calls.append(1) or orig(*a)
    reqs = [(rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32), n)
            for n in (4, 6, 3, 5, 7, 4, 6, 5)]
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()
    assert len(calls) == 1, f"expected one batched prefill, got {len(calls)}"
    assert ("final", 8, 8) in b._prefill_jit
    assert b.prefill_dispatches == 1
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, p, n))


def test_group_padding_rows_never_land():
    """A group of 3 pads to 4 prefill rows; the pad row's writes drop
    (an all-sentinel block table) and so does its seat (out-of-bounds
    slot), and running slots are untouched: all requests remain
    greedy-exact."""
    cfg, params = _make()
    rng = np.random.default_rng(12)
    b = ContinuousBatcher(cfg, params, max_batch=4)
    # occupy one slot first so the burst of 3 lands beside a live row
    early_p = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    early = b.submit(early_p, 10)
    b.step()
    reqs = [(rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32), n)
            for n in (4, 5, 6)]
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()
    assert ("final", 8, 4) in b._prefill_jit   # group of 3 padded to 4
    np.testing.assert_array_equal(results[early],
                                  _oracle(cfg, params, early_p, 10))
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, p, n))


def test_chunked_admission_is_time_sliced():
    """Admitting a long (chunked) prompt must NOT stall running slots:
    each step advances the in-flight prefill by one chunk while active
    requests keep decoding, the target slot stays reserved until the
    final chunk lands, and both outputs remain greedy-exact."""
    cfg, params = _make()
    rng = np.random.default_rng(13)
    b = ContinuousBatcher(cfg, params, max_batch=2, prefill_chunk=4)
    short = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
    r1 = b.submit(short, 20)
    b.step()                                  # r1 active
    slot1 = next(i for i, s in enumerate(b.slots) if s is not None)

    long_p = rng.integers(0, cfg.vocab_size, (18,)).astype(np.int32)
    r2 = b.submit(long_p, 5)                  # 18 > 4: chunked, 4+final
    for _ in range(4):                        # chunk slices 1..4
        n_before = len(b.slots[slot1].tokens)
        b.step()
        assert b._inflight is not None, "inflight finished too early"
        assert b._reserved, "target slot not reserved during chunking"
        assert len(b.slots[slot1].tokens) == n_before + 1, \
            "running slot stalled during chunked admission"
    b.step()                                  # final chunk: scatter+admit
    assert b._inflight is None and not b._reserved
    results = b.run()
    np.testing.assert_array_equal(results[r1],
                                  _oracle(cfg, params, short, 20))
    np.testing.assert_array_equal(results[r2],
                                  _oracle(cfg, params, long_p, 5))


def test_short_requests_bypass_blocked_chunked_head():
    """A second long prompt queued behind an active chunked admission
    must not stall short requests: they admit into free slots while the
    first long prompt streams; all outputs stay greedy-exact."""
    cfg, params = _make()
    rng = np.random.default_rng(14)
    b = ContinuousBatcher(cfg, params, max_batch=3, prefill_chunk=4)
    longs = [rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32)
             for t in (18, 14)]
    shorts = [rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
              for _ in range(2)]
    r_l1 = b.submit(longs[0], 5)
    r_l2 = b.submit(longs[1], 5)
    r_s = [b.submit(p, 8) for p in shorts]
    b.step()
    # long-1 is streaming; long-2 blocked; both shorts must be in slots
    assert b._inflight is not None
    active = {s.request_id for s in b.slots if s is not None}
    assert set(r_s) <= active, (active, r_s)
    results = b.run()
    for rid, (p, n) in zip([r_l1, r_l2] + r_s,
                           [(longs[0], 5), (longs[1], 5)]
                           + [(p, 8) for p in shorts]):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, p, n))


def test_speculative_batcher_greedy_exact_and_accepts():
    """Speculative continuous batching: repetitive prompts (lookup hits)
    and novel prompts stay greedy-exact vs solo oracles, per-row
    acceptance actually fires, and each slot commits its OWN accepted
    length (not the batch minimum)."""
    cfg, params = _make()
    rng = np.random.default_rng(15)
    # highly repetitive prompt -> the n-gram lookup drafts well
    rep = np.tile(np.asarray([7, 11, 23], np.int32), 5)
    novel = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    b = ContinuousBatcher(cfg, params, max_batch=2, speculative_k=4)
    r1 = b.submit(rep, 12)
    r2 = b.submit(novel, 9)
    results = b.run()
    np.testing.assert_array_equal(results[r1],
                                  _oracle(cfg, params, rep, 12))
    np.testing.assert_array_equal(results[r2],
                                  _oracle(cfg, params, novel, 9))
    assert b.spec_proposed > 0
    # the repetitive prompt makes acceptance deterministic under a
    # correct verify: drafts MUST be accepted, and committed tokens must
    # then exceed what one-per-dispatch decoding could produce
    assert b.spec_accepted > 0
    assert b.decode_dispatches < 21


def test_speculative_matches_plain_batcher_and_solo():
    """Staggered mixed-length requests through a speculative batcher
    equal the plain batcher AND the solo oracle token-for-token."""
    cfg, params = _make()
    rng = np.random.default_rng(16)
    reqs = [(np.tile(rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32),
                     3), n) for n in (10, 7, 5, 8)]
    bs = ContinuousBatcher(cfg, params, max_batch=2, speculative_k=3)
    rids = [bs.submit(p, n) for p, n in reqs]
    res_s = bs.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(res_s[rid], _oracle(cfg, params, p, n))


def test_speculative_eos_truncation():
    """An accepted draft containing eos must truncate exactly where solo
    greedy would stop."""
    cfg, params = _make()
    rng = np.random.default_rng(17)
    p = np.tile(rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32), 4)
    oracle = _oracle(cfg, params, p, 12)
    eos = int(oracle[4])
    b = ContinuousBatcher(cfg, params, max_batch=1, eos_id=eos,
                          speculative_k=4)
    rid = b.submit(p, 12)
    results = b.run()
    first = list(oracle).index(eos)
    np.testing.assert_array_equal(results[rid], oracle[:first + 1])


def test_speculative_composes_with_sampling():
    """Sampled slots inside a speculative batcher draft nothing and
    produce the exact tokens the plain sampling batcher produces (pure
    function of request parameters, regardless of speculation around
    them)."""
    cfg, params = _make()
    rng = np.random.default_rng(18)
    rep = np.tile(np.asarray([5, 9], np.int32), 6)
    nov = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)

    def run(spec):
        b = ContinuousBatcher(cfg, params, max_batch=2,
                              speculative_k=4 if spec else None)
        r_greedy = b.submit(rep, 10)
        r_samp = b.submit(nov, 8, temperature=0.9, top_p=0.8, seed=42)
        res = b.run()
        return res[r_greedy], res[r_samp]

    g_spec, s_spec = run(True)
    g_plain, s_plain = run(False)
    np.testing.assert_array_equal(g_spec, g_plain)
    np.testing.assert_array_equal(s_spec, s_plain)


def test_speculative_with_tp_sharded_params_under_mesh():
    """Speculation composes with distributed inference: the fused verify
    runs over Megatron-tp-sharded params on a 2-device mesh, per-row
    acceptance fires, and outputs equal the solo sharded greedy run."""
    from tensorflowonspark_tpu.parallel import MeshSpec, make_mesh
    from tensorflowonspark_tpu.parallel.sharding import flax_shardings

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                    intermediate_size=64, max_position_embeddings=64,
                    dtype=jnp.float32, pos_encoding="rope")
    params = GPT(cfg).init(jax.random.key(0),
                           jnp.ones((1, 4), jnp.int32))["params"]
    mesh = make_mesh(MeshSpec(tp=2, dp=1), devices=jax.devices()[:2])
    abstract = jax.eval_shape(
        lambda: GPT(cfg).init(jax.random.key(0),
                              jnp.ones((1, 4), jnp.int32)))
    sharded = jax.device_put(params, flax_shardings(mesh, abstract)["params"])

    rep = np.tile(np.asarray([3, 8, 13], np.int32), 4)
    with mesh:
        b = ContinuousBatcher(cfg, sharded, max_batch=2, speculative_k=4)
        rid = b.submit(rep, 12)
        results = b.run()
        want = np.asarray(greedy_generate(
            cfg, sharded, jnp.asarray(rep)[None, :], 12))[0, len(rep):]
    np.testing.assert_array_equal(results[rid], want)
    assert b.spec_accepted > 0


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_fuzz_random_schedules_stay_greedy_exact(seed):
    """Randomized drive: arbitrary submit/step interleavings, mixed
    prompt lengths (short, bucketed, chunked), mixed budgets, random
    slot counts, speculation on/off — every request must equal its solo
    greedy oracle regardless of schedule."""
    cfg, params = _make()
    rng = np.random.default_rng(seed)
    spec = int(rng.integers(0, 2))
    block = None if spec else [None, 4, 8][int(rng.integers(0, 3))]
    b = ContinuousBatcher(
        cfg, params, max_batch=int(rng.integers(1, 5)),
        prefill_chunk=int(rng.integers(4, 9)),
        speculative_k=(3 if spec else None),
        decode_block_steps=block)
    reqs, rids = [], []
    n_req = int(rng.integers(4, 9))
    submitted = 0
    while submitted < n_req:       # run() drains whatever remains after
        if rng.random() < 0.5:
            t = int(rng.integers(2, 20))
            if rng.random() < 0.4:      # repetitive: speculation bites
                p = np.tile(rng.integers(0, cfg.vocab_size,
                                         (2,)).astype(np.int32),
                            (t + 1) // 2)[:t]
            else:
                p = rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32)
            n = int(rng.integers(1, 9))
            reqs.append((p, n))
            rids.append(b.submit(p, n))
            submitted += 1
        for _ in range(int(rng.integers(1, 4))):
            b.step()
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(
            results[rid], _oracle(cfg, params, p, n),
            err_msg=f"seed={seed} spec={spec} rid={rid}")


@pytest.mark.parametrize("variant", ["int8", "int4", "gqa", "window"])
def test_speculative_composes_with_decode_features(variant):
    """The fused verify path must stay greedy-exact under quantized
    weights, grouped-query attention, and sliding windows — same
    matrix the plain batcher is locked against."""
    cfg, params = _variant_setup(variant)
    rng = np.random.default_rng(24)
    reqs = [(np.tile(rng.integers(0, cfg.vocab_size,
                                  (3,)).astype(np.int32), 4), n)
            for n in (7, 9, 5)]
    b = ContinuousBatcher(cfg, params, max_batch=2, speculative_k=3)
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, p, n))
    assert b.spec_accepted > 0


# -- multi-step decode blocks ---------------------------------------------

def test_block_decode_matches_solo_greedy():
    """decode_block_steps: identical tokens to per-step decode (the scan
    body IS the plain step), across staggered budgets and eos-free
    traffic."""
    cfg, params = _make()
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32), n)
            for t, n in ((5, 16), (3, 9), (8, 4), (2, 13))]
    b = ContinuousBatcher(cfg, params, max_batch=2, decode_block_steps=8)
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, p, n))


def test_block_decode_amortizes_dispatches():
    """One request, budget 32, block 8: the decode dispatch count must
    collapse well below the step count (pow2 blocks bounded by remaining
    budget), with decode_steps still counting every step."""
    cfg, params = _make()
    p = np.arange(4, dtype=np.int32) + 1
    b = ContinuousBatcher(cfg, params, max_batch=2, decode_block_steps=8)
    rid = b.submit(p, 33)        # 1 at prefill + 32 decode steps
    res = b.run()
    assert res[rid].size == 33
    assert b.decode_steps == 32
    # 32 steps in 8-blocks: 4 dispatches (+0..2 tail singles depending on
    # pow2 flooring) — far below 32
    assert b.decode_dispatches <= 6, b.decode_dispatches
    np.testing.assert_array_equal(res[rid], _oracle(cfg, params, p, 33))


def test_block_decode_sampled_rows_match_per_step():
    """Sampled requests under blocks: output is the same pure function
    of (seed, step) as the per-step batcher — the in-scan step counter
    must line up exactly."""
    cfg, params = _make()
    rng = np.random.default_rng(9)
    p1 = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)

    def drive(block):
        b = ContinuousBatcher(cfg, params, max_batch=2,
                              decode_block_steps=block)
        r1 = b.submit(p1, 12, temperature=0.8, top_p=0.9, seed=11)
        r2 = b.submit(p2, 7)                      # greedy alongside
        out = b.run()
        return out[r1], out[r2]

    a1, a2 = drive(None)
    b1, b2 = drive(8)
    np.testing.assert_array_equal(a1, b1)
    np.testing.assert_array_equal(a2, b2)
    np.testing.assert_array_equal(b2, _oracle(cfg, params, p2, 7))


def test_block_decode_eos_truncates_and_slot_reuses():
    """A row hitting eos mid-block: later scanned tokens are discarded,
    the slot frees, and a follow-up request admitted into that slot
    stays exact."""
    cfg, params = _make()
    p = np.arange(5, dtype=np.int32) + 1
    ref = _oracle(cfg, params, p, 24)
    eos = int(ref[2])
    # the oracle-with-eos stops at the FIRST occurrence of that token
    cut = int(np.flatnonzero(ref == eos)[0])
    b = ContinuousBatcher(cfg, params, max_batch=1, eos_id=eos,
                          decode_block_steps=8)
    r1 = b.submit(p, 24)
    got = b.run()[r1]
    np.testing.assert_array_equal(got, ref[:cut + 1])
    p2 = np.arange(4, dtype=np.int32) + 2
    r2 = b.submit(p2, 6)
    out = b.run()
    ref2 = _oracle(cfg, params, p2, 6)
    cut2 = np.flatnonzero(ref2 == eos)
    if cut2.size:                 # same eos id applies to the follow-up
        ref2 = ref2[:int(cut2[0]) + 1]
    np.testing.assert_array_equal(out[r2], ref2)


def test_block_decode_admission_latency_policy():
    """Admission precedes the block decision inside one step(), so a
    queued request with a free slot admits immediately.  For a request
    that CANNOT admit yet (no free slot): with ``eos_id`` set, an eos
    could free a slot any step, so the batcher must single-step; without
    eos, no slot can free before the minimum remaining budget, so
    blocking up to that bound delays the queued request by zero steps
    and MUST be taken."""
    cfg, params = _make()
    rng = np.random.default_rng(3)
    p1 = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)

    # eos set -> conservative single steps while a request waits
    b = ContinuousBatcher(cfg, params, max_batch=1, decode_block_steps=8,
                          eos_id=cfg.vocab_size + 1)   # never fires
    r1 = b.submit(p1, 20)
    b.step()                     # admit r1; r1 owns the only slot
    steps_before = b.decode_steps
    r2 = b.submit(p2, 5)         # cannot admit: no free slot
    b.step()
    assert b.decode_steps - steps_before == 1  # single, not a block
    out = b.run()
    np.testing.assert_array_equal(out[r1], _oracle(cfg, params, p1, 20))
    np.testing.assert_array_equal(out[r2], _oracle(cfg, params, p2, 5))

    # no eos -> blocks keep running while the request waits (zero-delay
    # bound) and amortization survives a full backlog drain
    b2 = ContinuousBatcher(cfg, params, max_batch=1, decode_block_steps=8)
    q1 = b2.submit(p1, 20)
    b2.step()
    q2 = b2.submit(p2, 5)
    b2.step()
    assert b2.decode_steps > b2.decode_dispatches  # a block ran
    out2 = b2.run()
    np.testing.assert_array_equal(out2[q1], _oracle(cfg, params, p1, 20))
    np.testing.assert_array_equal(out2[q2], _oracle(cfg, params, p2, 5))
    # first tokens come from the prefills: 19 + 4 decode steps total
    assert b2.decode_steps == 23
    assert b2.decode_dispatches < 12           # ... in far fewer dispatches


# -- streaming callback + load snapshot -----------------------------------

@pytest.mark.parametrize("kw", [{}, {"decode_block_steps": 8},
                                {"speculative_k": 3},
                                {"prefill_chunk": 4}])
def test_on_token_streams_exactly_the_oracle(kw):
    """The ``submit(on_token=...)`` stream equals the solo greedy oracle
    token-for-token, in order, under every decode regime (per-step,
    scanned blocks, speculative verify, chunked prefill) — discarded
    block/draft tokens never surface."""
    cfg, params = _make()
    rng = np.random.default_rng(30)
    streamed: dict[int, list] = {}

    def on_token(rid, tok):
        streamed.setdefault(rid, []).append(tok)

    b = ContinuousBatcher(cfg, params, max_batch=2, **kw)
    # repetitive prompt so speculation drafts; a long one so chunking
    # chunks; mixed budgets so slots churn
    reqs = [(np.tile(np.asarray([7, 11, 23], np.int32), 5), 10),
            (rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32), 7),
            (rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32), 1)]
    rids = [b.submit(p, n, on_token=on_token) for p, n in reqs]
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        oracle = _oracle(cfg, params, p, n).tolist()
        assert streamed[rid] == oracle, f"stream diverged ({kw})"
        assert results[rid].tolist() == oracle
    assert not b._on_token, "finished requests must drop their callbacks"


def test_on_token_fires_before_finish_and_with_eos():
    """Tokens stream as they commit (mid-flight, not at the end): after
    the first step the stream holds exactly the first oracle token while
    the request is still running; an eos stop truncates the stream
    exactly like the result."""
    cfg, params = _make()
    rng = np.random.default_rng(32)
    p = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
    oracle = _oracle(cfg, params, p, 6)
    streamed: list = []
    b = ContinuousBatcher(cfg, params, max_batch=1)
    rid = b.submit(p, 6, on_token=lambda r, t: streamed.append((r, t)))
    b.step()   # admits (prefill commits token 1) + one decode step
    early = [t for r, t in streamed if r == rid]
    assert early == oracle[: len(early)].tolist() and 0 < len(early) < 6
    assert b.result(rid) is None, "tokens must stream BEFORE finish"
    results = b.run()
    assert [t for _, t in streamed] == results[rid].tolist() \
        == oracle.tolist()

    # eos truncation: the stream ends where the result ends (first eos),
    # not at the budget
    eos = int(oracle[0])
    streamed2: list = []
    b2 = ContinuousBatcher(cfg, params, max_batch=1, eos_id=eos)
    rid2 = b2.submit(p, 10, on_token=lambda r, t: streamed2.append(t))
    res2 = b2.run()[rid2]
    first = list(_oracle(cfg, params, p, 10)).index(eos)
    assert streamed2 == res2.tolist() \
        == _oracle(cfg, params, p, 10)[: first + 1].tolist()


def test_load_counts_every_live_request_once():
    cfg, params = _make()
    b = ContinuousBatcher(cfg, params, max_batch=2, prefill_chunk=4)
    # the default pool: max_batch requests of max_position_embeddings
    # tokens in 16-token pages, all of it allocatable
    assert b.load() == {"active": 0, "pending": 0, "reserved": 0,
                        "total": 0, "free_pages": 6, "total_pages": 6}
    rng = np.random.default_rng(31)
    b.submit(rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32), 6)
    b.submit(rng.integers(0, cfg.vocab_size, (18,)).astype(np.int32), 5)
    b.submit(rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32), 6)
    assert b.load() == {"active": 0, "pending": 3, "reserved": 0,
                        "total": 3, "free_pages": 6, "total_pages": 6}
    b.step()
    # short prompt active; the long one is the in-flight chunked
    # admission (pending, with its slot reserved); the third queued
    load = b.load()
    assert load["total"] == 3, load
    assert load["active"] >= 1 and load["reserved"] == 1, load
    assert load["free_pages"] < 6, load     # live requests hold pages
    b.run()
    # released pages are allocatable again, cached or not
    assert b.load() == {"active": 0, "pending": 0, "reserved": 0,
                        "total": 0, "free_pages": 6, "total_pages": 6}


# -- paged KV + shared prefix cache (kv_page_tokens) ----------------------

@pytest.mark.parametrize("pos_encoding", ["rope", "learned"])
def test_paged_matches_solo_greedy(pos_encoding):
    """Paged-KV decode (block-table pool instead of the dense cache) is
    token-exact vs the solo greedy oracle across staggered mixed-length
    requests — the locked contract, paged edition."""
    cfg, params = _make(pos_encoding)
    rng = np.random.default_rng(40)
    reqs = [(rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32), n)
            for t, n in ((5, 7), (3, 12), (8, 4), (9, 9), (2, 6), (6, 1))]
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, p, n))
    # pages all returned (free + still-cached prefix pages = the pool)
    st = b.prefix_stats()
    assert st["free_pages"] == st["total_pages"], st


def test_paged_prefix_hit_skips_reprefill_and_stays_exact():
    """Same-system-prompt requests: the first admission misses and
    indexes its full prompt pages; later ones match the chain, prefill
    only their tails, and stay greedy-exact.  A prompt diverging
    MID-page matches only up to the divergence page (copy-on-write: it
    prefills a private copy, the shared original is untouched — the
    original must still hit afterwards)."""
    cfg, params = _make()
    rng = np.random.default_rng(41)
    pre = rng.integers(0, cfg.vocab_size, (17,)).astype(np.int32)  # 2 pages
    A = np.concatenate([pre, rng.integers(0, cfg.vocab_size,
                                          (3,)).astype(np.int32)])
    B = A.copy()
    B[11] = (B[11] + 1) % cfg.vocab_size      # diverges inside page 2
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    ra = b.submit(A, 5)
    b.run()
    assert b.prefix_stats()["miss"] == 1
    rb = b.submit(B, 5)
    res = b.run()
    np.testing.assert_array_equal(res[rb], _oracle(cfg, params, B, 5))
    assert b.prefix_stats()["partial"] == 1   # shared page 1, private 2
    ra2 = b.submit(A, 5)
    res = b.run()
    np.testing.assert_array_equal(res[ra2], _oracle(cfg, params, A, 5))
    np.testing.assert_array_equal(b.result(ra), res[ra2])
    assert b.prefix_stats()["hit"] == 1, b.prefix_stats()


def test_paged_exhaustion_backpressures_then_drains_exact():
    """A pool too small for the queue: admission blocks on free pages
    (not free slots), requests wait their turn, every one completes
    greedy-exact, and the pool leaks nothing."""
    cfg, params = _make()
    rng = np.random.default_rng(42)
    b = ContinuousBatcher(cfg, params, max_batch=4, kv_page_tokens=8,
                          kv_pool_pages=6)
    # 30 tokens -> 4 pages each: only one fits at a time despite 4 slots
    reqs = [(rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32), 20)
            for _ in range(3)]
    rids = [b.submit(p, n) for p, n in reqs]
    b.step()
    assert sum(s is not None for s in b.slots) == 1, \
        "page exhaustion must hold admissions back"
    load = b.load()
    assert load["pending"] == 2 and load["total_pages"] == 6, load
    res = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(res[rid], _oracle(cfg, params, p, n))
    st = b.prefix_stats()
    assert st["free_pages"] == st["total_pages"] == 6, st
    assert all(s is None for s in b.slots)


def test_paged_submit_rejects_requests_larger_than_the_pool():
    cfg, params = _make()
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                          kv_pool_pages=3)   # 24 tokens max
    with pytest.raises(ValueError, match="KV pages"):
        b.submit(np.arange(20, dtype=np.int32) % cfg.vocab_size, 10)
    rid = b.submit(np.arange(10, dtype=np.int32) % cfg.vocab_size, 10)
    np.testing.assert_array_equal(
        b.run()[rid], _oracle(cfg, params,
                              np.arange(10, dtype=np.int32)
                              % cfg.vocab_size, 10))


def test_paged_eviction_under_pressure_then_reprefill_exact():
    """Cached prefix pages are evicted (LRU, refcount 0 only) when the
    pool runs dry; a later request for the evicted prefix re-prefills
    from scratch and is still exact."""
    cfg, params = _make()
    rng = np.random.default_rng(43)
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                          kv_pool_pages=8)
    A = rng.integers(0, cfg.vocab_size, (17,)).astype(np.int32)
    b.submit(A, 4)
    b.run()
    for _ in range(3):          # churn: evicts A's cached pages
        b.submit(rng.integers(0, cfg.vocab_size, (20,)).astype(np.int32),
                 20)
        b.run()
    assert b.prefix_stats()["evictions"] > 0
    ra = b.submit(A, 4)
    np.testing.assert_array_equal(b.run()[ra], _oracle(cfg, params, A, 4))


def test_paged_mixed_greedy_sampled_hit_and_miss_paths():
    """Hit-vs-miss exactness under mixed traffic: greedy requests stay
    oracle-exact and a sampled request is the same pure function of
    (seed, temp, top_p) whether its prefix hits the cache, misses it,
    or the pool keeps no index (and pages of another size)."""
    cfg, params = _make()
    rng = np.random.default_rng(44)
    pre = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    samp_p = np.concatenate([pre, rng.integers(0, cfg.vocab_size,
                                               (4,)).astype(np.int32)])
    greedy_p = np.concatenate([pre, rng.integers(0, cfg.vocab_size,
                                                 (3,)).astype(np.int32)])

    def run(kw, warm):
        b = ContinuousBatcher(cfg, params, max_batch=2, **kw)
        if warm:    # populate the prefix index so the next admits HIT
            b.submit(np.concatenate(
                [pre, np.asarray([1], np.int32)]), 2)
            b.run()
        rs = b.submit(samp_p, 8, temperature=0.8, top_p=0.9, seed=7)
        rg = b.submit(greedy_p, 8)
        res = b.run()
        if warm:
            st = b.prefix_stats()
            assert st["hit"] >= 2, st
        return res[rs], res[rg]

    s_hit, g_hit = run({"kv_page_tokens": 8}, True)
    s_miss, g_miss = run({"kv_page_tokens": 8}, False)
    s_plain, g_plain = run({"prefix_cache": False}, False)
    np.testing.assert_array_equal(s_hit, s_plain)
    np.testing.assert_array_equal(s_miss, s_plain)
    want = _oracle(cfg, params, greedy_p, 8)
    for got in (g_hit, g_miss, g_plain):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kw", [{"prefill_chunk": 6},
                                {"decode_block_steps": 8},
                                {"speculative_k": 4}])
def test_paged_composes_with_decode_regimes(kw):
    """Paged KV under every decode regime (time-sliced chunked prefill,
    scanned blocks, speculative verify): greedy-exact, including a
    prefix-hit admission mid-composition."""
    cfg, params = _make()
    rng = np.random.default_rng(45)
    pre = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    reqs = [(np.concatenate([pre, rng.integers(
        0, cfg.vocab_size, (k,)).astype(np.int32)]), n)
        for k, n in ((3, 8), (5, 6))]
    reqs.append((np.tile(np.asarray([7, 11, 23], np.int32), 5), 10))
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                          **kw)
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, p, n))


def test_paged_with_tp_sharded_params_under_mesh():
    """Paged decode over Megatron-tp-sharded params on a 2-device mesh
    (the pool's head axis shards with tp): greedy-exact vs the solo
    sharded oracle, with a prefix hit in the mix."""
    from tensorflowonspark_tpu.parallel import MeshSpec, make_mesh
    from tensorflowonspark_tpu.parallel.sharding import flax_shardings

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                    intermediate_size=64, max_position_embeddings=48,
                    dtype=jnp.float32, pos_encoding="rope")
    params = GPT(cfg).init(jax.random.key(0),
                           jnp.ones((1, 4), jnp.int32))["params"]
    mesh = make_mesh(MeshSpec(tp=2, dp=1), devices=jax.devices()[:2])
    abstract = jax.eval_shape(
        lambda: GPT(cfg).init(jax.random.key(0), jnp.ones((1, 4), jnp.int32)))
    sharded = jax.device_put(params,
                             flax_shardings(mesh, abstract)["params"])

    rng = np.random.default_rng(46)
    pre = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    reqs = [(np.concatenate([pre, rng.integers(
        0, cfg.vocab_size, (k,)).astype(np.int32)]), n)
        for k, n in ((3, 8), (4, 6))]
    with mesh:
        b = ContinuousBatcher(cfg, sharded, max_batch=2, kv_page_tokens=8)
        results = {}
        for p, n in reqs:    # serialized so the second admission HITS
            rid = b.submit(p, n)
            results[rid] = b.run()[rid]
        for rid, (p, n) in zip(sorted(results), reqs):
            want = np.asarray(greedy_generate(
                cfg, sharded, jnp.asarray(p)[None, :], n))[0, len(p):]
            np.testing.assert_array_equal(results[rid], want)
    assert b.prefix_stats()["hit"] >= 1


def test_paged_validation():
    cfg, params = _make()
    with pytest.raises(ValueError, match="power of two"):
        ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=6)
    with pytest.raises(ValueError, match="divide"):
        ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=32)
    with pytest.raises(ValueError, match="kv_pool_pages"):
        ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                          kv_pool_pages=0)
    cfg8, params8 = _make(kv_cache_int8=True)
    with pytest.raises(ValueError, match="kv_cache_int8"):
        ContinuousBatcher(cfg8, params8, max_batch=2, kv_page_tokens=8)


@pytest.mark.parametrize("max_pos,page", [(48, 16), (24, 8), (20, 4)])
def test_default_pool_holds_a_whole_window_per_slot(max_pos, page):
    """Built with no paging argument the batcher serves from 16-token
    pages, halved until they divide the window, and from a pool that
    holds ``max_batch`` whole windows: ``max_batch`` requests of full
    length seat at once (no page backpressure where slots are free), and
    each is greedy-exact."""
    cfg, params = _make(max_position_embeddings=max_pos)
    b = ContinuousBatcher(cfg, params, max_batch=3)
    assert b.cfg.kv_page_tokens == page
    assert b.cfg.kv_pool_pages == 3 * max_pos // page
    rng = np.random.default_rng(max_pos)
    reqs = [(rng.integers(0, cfg.vocab_size, (max_pos - n,)).astype(np.int32),
             n) for n in (5, 6, 7)]
    rids = [b.submit(p, n) for p, n in reqs]
    b.step()
    assert all(b.slots), "a full-length request waited for pages"
    assert b.load()["free_pages"] == 0
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(cfg, params, p, n))


def test_default_pool_reports_itself():
    """``load()`` and ``prefix_stats()`` of a batcher built with no
    paging argument read a real pool with the shared-prefix index on."""
    cfg, params = _make()
    b = ContinuousBatcher(cfg, params, max_batch=2)
    assert b.load()["free_pages"] == b.load()["total_pages"] == 6
    assert b.prefix_stats()["total_pages"] == 6
    prompt = np.arange(1, 37, dtype=np.int32)          # two whole pages
    for _ in range(2):
        b.submit(prompt, 3)
        b.run()
    st = b.prefix_stats()
    assert st["miss"] == 1 and st["hit"] == 1 and st["cached_pages"] == 2, st
    assert b.load()["free_pages"] == 6       # cached pages are evictable


def test_block_decode_validation():
    cfg, params = _make()
    with pytest.raises(ValueError, match="decode_block_steps"):
        ContinuousBatcher(cfg, params, max_batch=2, decode_block_steps=1)
    with pytest.raises(ValueError, match="mutually exclusive"):
        ContinuousBatcher(cfg, params, max_batch=2, decode_block_steps=4,
                          speculative_k=2)


# ---------------------------------------------------------------------------
# KV-page session handoff (disaggregated prefill/decode; docs/serving.md)

def _drive_handoff(pre, max_steps=30):
    """Step a prefill-only batcher until its pending work is exported;
    returns every (request_id, session) pair."""
    sessions = []
    for _ in range(max_steps):
        pre.step()
        sessions.extend(pre.take_sessions())
        if not pre.load()["total"]:
            break
    return sessions


def test_handoff_greedy_exact_on_miss_path():
    """Prefill-only export → decode adopt: the stitched stream (first
    token from the prefill side + the decode side's tokens) equals the
    solo greedy oracle, and the decode batcher never runs a prefill
    dispatch."""
    cfg, params = _make()
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32), n)
            for t, n in ((5, 7), (11, 5), (16, 6), (3, 9))]
    pre = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                            prefill_only=True)
    rids = [pre.submit(p, n) for p, n in reqs]
    sessions = dict(_drive_handoff(pre))
    assert sorted(sessions) == sorted(rids)
    assert pre.sessions_exported == len(reqs)
    assert pre.decode_dispatches == 0, "a prefill pool must never step"

    dec = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    dmap = {dec.adopt_session(sessions[rid]): rid for rid in rids}
    results = dec.run()
    assert dec.prefill_dispatches == 0, \
        "a decode gang must never re-prefill an adopted session"
    assert dec.sessions_adopted == len(reqs)
    for drid, prid in dmap.items():
        prompt, n = reqs[rids.index(prid)]
        np.testing.assert_array_equal(results[drid],
                                      _oracle(cfg, params, prompt, n))


def test_handoff_sampled_exact():
    """A sampled session hands off with its sampler state: the decode
    side's continuation is token-identical to an unsplit batcher run of
    the same (prompt, budget, temperature, top_p, seed)."""
    cfg, params = _make()
    prompt = np.asarray([7, 3, 9, 1, 4, 2, 8], np.int32)
    kw = dict(temperature=0.8, top_p=0.9, seed=123)
    pre = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                            prefill_only=True)
    rid = pre.submit(prompt, 9, **kw)
    [(_, sess)] = _drive_handoff(pre)
    dec = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    drid = dec.adopt_session(sess)
    got = dec.run()[drid]

    solo = ContinuousBatcher(cfg, params, max_batch=1, kv_page_tokens=8)
    srid = solo.submit(prompt, 9, **kw)
    np.testing.assert_array_equal(got, solo.run()[srid])


def test_handoff_prefix_hit_path_exact_and_imports_only_tail():
    """A decode pool already holding the session's system prefix adopts
    WITHOUT importing the matched pages (cross-request reuse composes
    with the handoff) and stays oracle-exact."""
    cfg, params = _make()
    rng = np.random.default_rng(1)
    sysp = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    dec = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    seed_p = np.concatenate([sysp, rng.integers(0, cfg.vocab_size, (3,))
                             .astype(np.int32)])
    dec.submit(seed_p, 4)
    dec.run()                               # seeds sysp's 2 full pages
    h0 = dec.prefix_stats()

    prompt = np.concatenate([sysp, rng.integers(0, cfg.vocab_size, (5,))
                             .astype(np.int32)])
    pre = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                            prefill_only=True)
    pre.submit(prompt, 6)
    [(_, sess)] = _drive_handoff(pre)
    drid = dec.adopt_session(sess)
    got = dec.run()[drid]
    h1 = dec.prefix_stats()
    assert h1["hit"] == h0["hit"] + 1, "adopt missed the seeded prefix"
    np.testing.assert_array_equal(got, _oracle(cfg, params, prompt, 6))


def test_adopt_rejects_corrupt_and_mismatched_sessions_loudly():
    """A transfer whose per-page content hashes or layout signature
    don't verify raises a typed ``ValueError`` from ``adopt_session``
    itself — before any device write, without poisoning the batcher."""
    cfg, params = _make()
    prompt = np.asarray([5, 4, 3, 2, 1, 6, 7, 8, 9], np.int32)
    pre = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                            prefill_only=True)
    pre.submit(prompt, 5)
    [(_, sess)] = _drive_handoff(pre)

    dec = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    corrupt = dict(sess)
    corrupt["kv"] = [np.array(a, copy=True) for a in sess["kv"]]
    corrupt["kv"][0].flat[5] += 1
    with pytest.raises(ValueError, match="content hash mismatch"):
        dec.adopt_session(corrupt)
    mismatched = dict(sess, page_tokens=16)
    with pytest.raises(ValueError, match="page_tokens"):
        dec.adopt_session(mismatched)
    # a key-skewed descriptor is ValueError too — a KeyError would
    # escape the serve loop's typed-error bounce and crash the worker
    truncated = {k: v for k, v in sess.items() if k != "page_hashes"}
    with pytest.raises(ValueError, match="missing key"):
        dec.adopt_session(truncated)
    raced = dict(sess)
    raced["kv"] = [a[..., :-1] for a in sess["kv"]]
    with pytest.raises(ValueError, match="layout mismatch"):
        dec.adopt_session(raced)
    # the rejections never touched the engine: it still serves exactly
    drid = dec.adopt_session(sess)
    np.testing.assert_array_equal(dec.run()[drid],
                                  _oracle(cfg, params, prompt, 5))


def test_prefill_only_validation_and_direct_finish():
    cfg, params = _make()
    with pytest.raises(ValueError, match="decode-time"):
        ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                          prefill_only=True, speculative_k=2)
    pre = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                            prefill_only=True)
    with pytest.raises(ValueError, match="prefill-only"):
        pre.adopt_session({"v": 1})
    # a budget-1 request finishes AT the prefill (no session to hand
    # off): the prefill pool completes it directly
    prompt = np.asarray([1, 2, 3], np.int32)
    rid = pre.submit(prompt, 1)
    done = []
    for _ in range(5):
        done += pre.step()
        if done:
            break
    assert done == [rid] and not pre.take_sessions()
    np.testing.assert_array_equal(pre.result(rid),
                                  _oracle(cfg, params, prompt, 1))


def test_set_role_specializes_idle_engine_both_ways():
    """Promote-with-role (warm standby joining a disagg pool): a
    role-less engine flips to prefill posture and exports a session
    exactly as a constructor-built prefill pool would, then flips back
    to decode posture and adopts it — the two specializations one warm
    pool must be able to back."""
    cfg, params = _make()
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    assert not b.prefill_only
    b.set_role("prefill")
    assert b.prefill_only
    b.submit(prompt, 5)
    sessions = _drive_handoff(b)
    assert len(sessions) == 1 and b.decode_dispatches == 0
    b.set_role("decode")
    assert not b.prefill_only
    drid = b.adopt_session(sessions[0][1])
    np.testing.assert_array_equal(b.run()[drid],
                                  _oracle(cfg, params, prompt, 5))
    assert b.prefill_dispatches == 1     # the pre-handoff prefill only


def test_set_role_validation():
    cfg, params = _make()
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    with pytest.raises(ValueError, match="unknown role"):
        b.set_role("both")
    # prefill posture keeps the constructor's constraints
    spec = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                             speculative_k=2)
    with pytest.raises(ValueError, match="decode-time"):
        spec.set_role("prefill")
    # a live request pins the posture
    b.submit(np.asarray([1, 2, 3], np.int32), 3)
    with pytest.raises(RuntimeError, match="live requests"):
        b.set_role("prefill")
    b.run()
    b.set_role("prefill")                # drained: legal again
    assert b.prefill_only


def test_handoff_composes_with_chunked_prefill():
    """A long prompt streamed through the prefill pool's chunked
    admission exports the identical session a whole-prompt prefill
    would: the decode side stays oracle-exact."""
    cfg, params = _make()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (30,)).astype(np.int32)
    pre = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                            prefill_chunk=8, prefill_only=True)
    pre.submit(prompt, 6)
    sessions = _drive_handoff(pre)
    assert len(sessions) == 1
    dec = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    drid = dec.adopt_session(sessions[0][1])
    np.testing.assert_array_equal(dec.run()[drid],
                                  _oracle(cfg, params, prompt, 6))


def test_export_import_prefix_cache_roundtrip_exact():
    """The standby promotion's page clone: a donor's prefix-cache
    export imports into a fresh batcher as matchable cached pages, and
    decoding against them is oracle-exact (hash-verified; corrupt
    imports rejected)."""
    cfg, params = _make()
    rng = np.random.default_rng(4)
    sysp = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    donor = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    donor.submit(np.concatenate(
        [sysp, rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)]), 4)
    donor.run()
    export = donor.export_prefix_cache()
    assert export is not None and export["pages"] >= 2

    imp = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    assert imp.import_prefix_cache(export) == export["pages"]
    probe = np.concatenate(
        [sysp, rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)])
    rid = imp.submit(probe, 5)
    got = imp.run()[rid]
    assert imp.prefix_stats()["hit"] == 1, "imported pages never matched"
    np.testing.assert_array_equal(got, _oracle(cfg, params, probe, 5))

    bad = dict(export)
    bad["kv"] = [np.array(a, copy=True) for a in export["kv"]]
    bad["kv"][0].flat[0] += 1
    fresh = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    with pytest.raises(ValueError, match="content hash mismatch"):
        fresh.import_prefix_cache(bad)
    # a pool without the index has nothing to export
    plain = ContinuousBatcher(cfg, params, max_batch=2, prefix_cache=False)
    rid = plain.submit(np.arange(1, 25, dtype=np.int32), 2)
    plain.run()
    assert plain.export_prefix_cache() is None


# -- the pool's token row (models.gpt.kv_row_width) -----------------------
# one token's K (or V) heads lie side by side in a row padded to whole
# 128-lane tiles: [P*pt, W], [L, P*pt, W] under scan_layers

_ROWS = {"pad_160_to_256": dict(hidden_size=160, num_heads=5),
         "whole_128": dict(hidden_size=128, num_heads=4)}
_row_params = pytest.mark.parametrize("row", sorted(_ROWS))
_scan_params = pytest.mark.parametrize("scan", [False, True],
                                       ids=["layers", "scan_layers"])


def _make_row(row, scan=False):
    cfg = GPTConfig(vocab_size=61, num_layers=2, intermediate_size=64,
                    max_position_embeddings=32, dtype=jnp.float32,
                    scan_layers=scan, **_ROWS[row])
    params = GPT(cfg).init(jax.random.key(0),
                           jnp.ones((1, 4), jnp.int32))["params"]
    return cfg, params


def _pool_leaves(cache):
    return [leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(cache)[0]
            if getattr(path[-1], "key", None) in ("k", "v")]


@_scan_params
@_row_params
def test_paged_logits_match_dense(row, scan):
    """The same prefill and decode steps through the paged pool (rows
    routed through a shuffled block table) and through the dense per-row
    cache give the same logits: only the store/gather substrate differs.
    The pool's leaves are ``[.., P*pt, W]`` and the pad lanes stay 0."""
    import dataclasses

    from tensorflowonspark_tpu.models.gpt import init_cache, kv_row_width

    base, params = _make_row(row, scan)
    B, pt = 2, 8
    npg = base.max_position_embeddings // pt
    dense = dataclasses.replace(base, per_row_positions=True)
    paged = dataclasses.replace(dense, kv_page_tokens=pt,
                                kv_pool_pages=B * npg)
    W = kv_row_width(base.num_heads, base.head_dim)
    assert W % 128 == 0 and 0 <= W - base.hidden_size < 128

    table = np.random.default_rng(7).permutation(B * npg) \
        .reshape(B, npg).astype(np.int32)
    caches = {
        "dense": init_cache(dense, params, B),
        "paged": jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.broadcast_to(table, leaf.shape)
            if getattr(path[-1], "key", None) == "block_table" else leaf,
            init_cache(paged, params, B))}
    lead = (base.num_layers,) if scan else ()
    for leaf in _pool_leaves(caches["paged"]):
        assert leaf.shape == lead + (B * npg * pt, W)

    rng = np.random.default_rng(8)
    feeds = [rng.integers(0, base.vocab_size, (B, t)).astype(np.int32)
             for t in (5, 1, 1, 3, 1)]
    for tokens in feeds:
        logits = {}
        for name, cfg in (("dense", dense), ("paged", paged)):
            logits[name], vars_ = GPT(cfg, decode=True).apply(
                {"params": params, "cache": caches[name]}, tokens,
                mutable=["cache"])
            caches[name] = vars_["cache"]
        np.testing.assert_allclose(logits["paged"], logits["dense"],
                                   rtol=1e-5, atol=1e-5)
    for leaf in _pool_leaves(caches["paged"]):
        assert np.any(np.asarray(leaf[..., :base.hidden_size]))
        assert not np.any(np.asarray(leaf[..., base.hidden_size:]))


@_scan_params
@_row_params
def test_export_then_seat_returns_the_same_pages(row, scan):
    """Export -> seat between two batchers: the importer's pool gives
    back the donor's pages byte for byte (``[.., n, pt, W]`` each), and
    decoding against them is oracle-exact."""
    from tensorflowonspark_tpu.models.gpt import kv_row_width

    cfg, params = _make_row(row, scan)
    rng = np.random.default_rng(4)
    sysp = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    donor = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    donor.submit(np.concatenate(
        [sysp, rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)]), 4)
    donor.run()
    export = donor.export_prefix_cache()
    n = export["pages"]
    assert n >= 2
    lead = (cfg.num_layers,) if scan else ()
    page = lead + (n, 8, kv_row_width(cfg.num_heads, cfg.head_dim))
    assert [a.shape for a in export["kv"]] == [page] * len(export["kv"])

    imp = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    assert imp.import_prefix_cache(export) == n
    back = imp.export_prefix_cache()
    assert back["keys"] == export["keys"]
    assert back["page_hashes"] == export["page_hashes"]
    for a, b in zip(back["kv"], export["kv"]):
        np.testing.assert_array_equal(a, b)
    probe = np.concatenate(
        [sysp, rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)])
    rid = imp.submit(probe, 5)
    got = imp.run()[rid]
    assert imp.prefix_stats()["hit"] == 1
    np.testing.assert_array_equal(got, _oracle(cfg, params, probe, 5))


@pytest.mark.parametrize("path", ["session", "prefix_cache"])
def test_peer_with_another_row_width_is_refused(path):
    """``_kv_struct`` carries the pool's row: pages from a peer whose
    ``W`` differs never reach the device."""
    prompt = np.asarray([5, 4, 3, 2, 1, 6, 7, 8, 9], np.int32)
    cfg_a, params_a = _make_row("pad_160_to_256")
    cfg_b, params_b = _make_row("whole_128")
    a = ContinuousBatcher(cfg_a, params_a, max_batch=2, kv_page_tokens=8,
                          prefill_only=(path == "session"))
    b = ContinuousBatcher(cfg_b, params_b, max_batch=2, kv_page_tokens=8)
    assert [s[-1] for s, _ in a._kv_struct()] == [256] * 4
    assert [s[-1] for s, _ in b._kv_struct()] == [128] * 4
    a.submit(prompt, 5)
    if path == "session":
        [(_, sess)] = _drive_handoff(a)
        refused = lambda: b.adopt_session(sess)          # noqa: E731
    else:
        a.run()
        export = a.export_prefix_cache()
        refused = lambda: b.import_prefix_cache(export)  # noqa: E731
    with pytest.raises(ValueError, match="layout mismatch"):
        refused()
    rid = b.submit(prompt, 5)     # the refusal never touched the engine
    np.testing.assert_array_equal(b.run()[rid],
                                  _oracle(cfg_b, params_b, prompt, 5))
