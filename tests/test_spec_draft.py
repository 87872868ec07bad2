"""Draft-model speculative decoding (``models/serving.py`` DraftModel)
and the AOT executable cache (``serving/aot.py``).

Correctness never depends on the draft: every accepted token passed the
fused target verify, so outputs must equal the solo ``greedy_generate``
oracle whether the draft agrees (same weights), diverges (different
weights), or is absent.  The AOT cache's contract is load-or-compile:
a second process over the same directory loads every site, a corrupt
entry degrades to a recompile, never a wrong executable.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models import (GPT, GPTConfig, ContinuousBatcher,
                                          DraftModel, greedy_generate)
from tensorflowonspark_tpu.serving.aot import AOTExecutableCache


def _make(seed=0, **kw):
    base = dict(vocab_size=61, hidden_size=32, num_layers=2, num_heads=4,
                intermediate_size=64, max_position_embeddings=48,
                dtype=jnp.float32, pos_encoding="rope")
    cfg = GPTConfig(**{**base, **kw})
    params = GPT(cfg).init(jax.random.key(seed),
                           jnp.ones((1, 4), jnp.int32))["params"]
    return cfg, params


def _oracle(cfg, params, prompt, n):
    out = greedy_generate(cfg, params, jnp.asarray(prompt)[None, :], n)
    return np.asarray(out)[0, len(prompt):]


def test_draft_greedy_exact_and_accepts():
    """A same-weights draft must agree with the target, so acceptance is
    total, outputs stay oracle-exact, and the decode loop commits more
    than one token per dispatch."""
    cfg, params = _make()
    rng = np.random.default_rng(30)
    reqs = [(rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32), n)
            for t, n in ((5, 12), (3, 10))]
    b = ContinuousBatcher(cfg, params, max_batch=2, speculative_k=4)
    b.set_draft(DraftModel(cfg, params, window=16))
    rids = [b.submit(p, n) for p, n in reqs]
    results = b.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid], _oracle(cfg, params, p, n))
    assert b.draft_dispatches > 0
    assert b.spec_proposed > 0 and b.spec_accepted == b.spec_proposed
    # 22 tokens one-per-dispatch would cost >= 12 batched decode steps
    assert b.decode_dispatches < 12


def test_divergent_draft_stays_oracle_exact():
    """A draft with DIFFERENT weights mispredicts; the verify rejects
    and falls back to the target's own token — outputs identical to the
    no-draft run, token for token."""
    cfg, params = _make(seed=0)
    _, wrong = _make(seed=2)       # empirically disagrees with seed 0
    rng = np.random.default_rng(31)
    p = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    b = ContinuousBatcher(cfg, params, max_batch=1, speculative_k=4)
    b.set_draft(DraftModel(cfg, wrong, window=16))
    rid = b.submit(p, 14)
    results = b.run()
    np.testing.assert_array_equal(results[rid], _oracle(cfg, params, p, 14))
    assert b.spec_proposed > 0          # it did speculate...
    assert b.spec_accepted < b.spec_proposed   # ...and got corrected


def test_sampled_rows_keep_draft0_fallback():
    """Sampled slots are ineligible for draft speculation: with a draft
    armed they produce exactly the plain batcher's tokens (pure function
    of the request's sampling parameters)."""
    cfg, params = _make()
    rng = np.random.default_rng(32)
    rep = np.tile(np.asarray([5, 9], np.int32), 6)
    nov = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)

    def run(draft):
        b = ContinuousBatcher(cfg, params, max_batch=2,
                              speculative_k=4 if draft else None)
        if draft:
            b.set_draft(DraftModel(cfg, params, window=16))
        r_greedy = b.submit(rep, 10)
        r_samp = b.submit(nov, 8, temperature=0.9, top_p=0.8, seed=42)
        res = b.run()
        return res[r_greedy], res[r_samp]

    g_draft, s_draft = run(True)
    g_plain, s_plain = run(False)
    np.testing.assert_array_equal(g_draft, g_plain)
    np.testing.assert_array_equal(s_draft, s_plain)


def test_accept_len_histogram_drain():
    """Per-dispatch accepted lengths accumulate for the replica metrics
    loop and drain destructively (the histogram publisher's contract)."""
    cfg, params = _make()
    b = ContinuousBatcher(cfg, params, max_batch=1, speculative_k=4)
    b.set_draft(DraftModel(cfg, params, window=16))
    rid = b.submit(np.asarray([3, 1, 4, 1, 5], np.int32), 10)
    b.run()
    assert rid is not None
    lens = b.take_spec_accept_lens()
    assert lens and all(isinstance(n, int) and 0 <= n <= 4 for n in lens)
    assert b.take_spec_accept_lens() == []      # drained


def test_set_draft_validation():
    cfg, params = _make()
    draft = DraftModel(cfg, params, window=16)

    plain = ContinuousBatcher(cfg, params, max_batch=1)
    with pytest.raises(ValueError, match="speculative_k"):
        plain.set_draft(draft)                  # draft needs spec_k

    b = ContinuousBatcher(cfg, params, max_batch=1, speculative_k=4)
    with pytest.raises(TypeError):
        b.set_draft(object())
    cfg2, params2 = _make(vocab_size=37)
    with pytest.raises(ValueError, match="vocab"):
        b.set_draft(DraftModel(cfg2, params2, window=16))
    with pytest.raises(ValueError, match="window"):
        # window + k overruns the draft's positions: 46 + 4 > 48
        b.set_draft(DraftModel(cfg, params, window=46))
    with pytest.raises(ValueError):
        DraftModel(cfg, params, window=0)

    b.set_draft(draft)
    assert b._draft_model is draft
    b.set_draft(None)                           # clears cleanly
    assert b._draft_model is None

    pf = ContinuousBatcher(cfg, params, max_batch=1, kv_page_tokens=8)
    pf.set_role("prefill")
    with pytest.raises(ValueError, match="prefill"):
        pf.set_draft(draft)


def test_aot_cache_hit_miss_corrupt(tmp_path):
    """The load-or-compile contract on a trivial site: first handle
    compiles and serializes, a second handle over the same directory
    loads (0 compiles), a corrupt entry counts an error and degrades to
    a recompile that overwrites it — never a crash."""
    x = jnp.arange(8, dtype=jnp.float32)

    def use(expect):
        c = AOTExecutableCache(str(tmp_path))
        f = c.wrap(("site", "v0"), lambda a: a * 2 + 1)
        np.testing.assert_allclose(np.asarray(f(x)), np.arange(8) * 2 + 1)
        assert (c.loads, c.compiles) == expect
        return c

    use((0, 1))                                 # miss -> compile + store
    use((1, 0))                                 # hit -> pure load
    [entry] = [p for p in os.listdir(tmp_path) if p.endswith(".aotx")]
    with open(tmp_path / entry, "wb") as f:
        f.write(b"garbage")
    c = use((0, 1))                             # corrupt -> recompile
    assert c.errors == 1
    use((1, 0))                                 # ...which re-stored it


@pytest.mark.parametrize("dev", [0, 3], ids=["device0", "device3"])
def test_aot_entry_runs_on_its_own_device_of_eight(tmp_path, dev):
    """An entry compiled for ONE device loads and runs on exactly that
    device while eight exist (jax 0.9 ``deserialize_and_load`` defaults
    to every device of the backend: 'expected 8 shards, got [1]'), and
    entries for different devices never share a file."""
    devices = jax.devices()
    assert len(devices) == 8
    x = jax.device_put(jnp.arange(8, dtype=jnp.float32), devices[dev])

    def use(arg):
        c = AOTExecutableCache(str(tmp_path))
        out = c.wrap("site", lambda a: a * 3)(arg)
        np.testing.assert_allclose(np.asarray(out), np.arange(8) * 3)
        return c, out

    c, _ = use(x)
    assert (c.loads, c.compiles, c.errors) == (0, 1, 0)
    c, out = use(x)
    assert (c.loads, c.compiles, c.errors) == (1, 0, 0)
    assert out.sharding.device_set == {devices[dev]}
    # the same site on another device is a different entry: a miss,
    # not a load of this device's executable
    other = jax.device_put(x, devices[(dev + 1) % 8])
    c, out = use(other)
    assert (c.loads, c.compiles) == (0, 1)
    assert out.sharding.device_set == {devices[(dev + 1) % 8]}


def test_batcher_aot_identical_workload_compiles_zero(tmp_path):
    """A second batcher process-equivalent (fresh handles, same cache
    dir) over the identical workload resolves every serve-step site from
    disk — including verify and the draft's propose."""
    cfg, params = _make()
    p = np.asarray([2, 7, 1, 8], np.int32)

    def serve():
        cache = AOTExecutableCache(str(tmp_path))
        b = ContinuousBatcher(cfg, params, max_batch=2, speculative_k=4,
                              aot_cache=cache)
        b.set_draft(DraftModel(cfg, params, window=16))
        rid = b.submit(p, 9)
        out = b.run()[rid]
        np.testing.assert_array_equal(out, _oracle(cfg, params, p, 9))
        return cache.stats()

    first = serve()
    assert first["compiles"] > 0 and first["errors"] == 0
    second = serve()
    assert second["compiles"] == 0 and second["loads"] > 0


@pytest.mark.slow
def test_draft_composes_with_paged_prefix_cache():
    """Draft speculation over the paged-KV pool with the prefix cache:
    a shared system prompt hits the cache, the draft proposes, outputs
    stay oracle-exact."""
    cfg, params = _make()
    rng = np.random.default_rng(33)
    sys_p = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    reqs = [np.concatenate([sys_p, rng.integers(
        0, cfg.vocab_size, (3,)).astype(np.int32)]) for _ in range(3)]
    b = ContinuousBatcher(cfg, params, max_batch=2, speculative_k=4,
                          kv_page_tokens=8, prefix_cache=True)
    b.set_draft(DraftModel(cfg, params, window=16))
    rids = [b.submit(p, 8) for p in reqs]
    results = b.run()
    for rid, p in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid], _oracle(cfg, params, p, 8))
    assert b.spec_accepted > 0


@pytest.mark.slow
def test_draft_with_tp_sharded_params_under_mesh():
    """Draft propose + fused verify over Megatron-tp-sharded params on a
    2-device mesh: acceptance fires, outputs equal the sharded solo run
    (the gang-leader posture of ``serving/sharded.py``)."""
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
        b.set_draft(DraftModel(cfg, sharded, window=16))
        rid = b.submit(rep, 12)
        results = b.run()
        want = np.asarray(greedy_generate(
            cfg, sharded, jnp.asarray(rep)[None, :], 12))[0, len(rep):]
    np.testing.assert_array_equal(results[rid], want)
    assert b.spec_accepted > 0


@pytest.mark.slow
def test_standby_posture_from_prebaked_cache(tmp_path):
    """The promotion path end-to-end in-process: one engine pre-bakes
    the cache via the standby warm-up sweep; a fresh engine then warms
    with 0 compiles, survives the unload/reload weights posture, and
    serves oracle-exact with the draft re-armed."""
    from tensorflowonspark_tpu.serving.standby import _warm_batcher

    cfg, params = _make()

    def build():
        cache = AOTExecutableCache(str(tmp_path))
        b = ContinuousBatcher(cfg, params, max_batch=2, speculative_k=4,
                              aot_cache=cache)
        b.set_draft(DraftModel(cfg, params, window=16))
        return b, cache

    b1, c1 = build()
    _warm_batcher(b1)
    assert c1.compiles > 0

    b2, c2 = build()
    _warm_batcher(b2)
    assert c2.compiles == 0 and c2.loads > 0    # pure cache read

    host = jax.tree_util.tree_map(np.asarray, params)
    b2.unload_params()
    b2.load_params(host)
    b2.set_draft(DraftModel(cfg, params, window=16))   # promotion re-arm
    p = np.asarray([4, 2, 9, 7], np.int32)
    rid = b2.submit(p, 10)
    out = b2.run()[rid]
    np.testing.assert_array_equal(out, _oracle(cfg, params, p, 10))
    assert b2.spec_accepted > 0
