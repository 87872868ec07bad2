"""The paged-attention decode kernel (``ops.paged_attention``) under the
Pallas interpreter against the gather path's mathematics in float32 over
the same pool, and the rule that decides where the model uses it
(``models.gpt.attends_pages_in_place``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models import gpt as gpt_mod
from tensorflowonspark_tpu.models import serving as serving_mod
from tensorflowonspark_tpu.models.gpt import (GPT, GPTConfig,
                                              attends_pages_in_place,
                                              init_cache, kv_row_width)
from tensorflowonspark_tpu.models.serving import ContinuousBatcher
from tensorflowonspark_tpu.ops.paged_attention import \
    paged_decode_attention

PT, NPG = 16, 10            # a 160-position view: two chunks of the kernel
C = PT * NPG
WIDTHS = {"G1_W384_padded": (5, 1), "G4_W512": (8, 4)}      # Hkv, G (D 64)
#: per row: length, or (length, logical pages left unallocated)
LENGTHS = {
    "one": [1, 1, 1],
    "page_less_one": [PT - 1, 2 * PT - 1, 1],
    "whole_pages": [PT, 2 * PT, 8 * PT],
    "ragged": [1, PT - 1, PT, PT + 1, 100, 129, C - 3],
    "full": [C, C - 1, 7],
    "sentinel_inside": [(100, (2,)), (C, (0, 9)), 40],
    "parked_and_empty": [0, 33, (20, (0, 1))],
}


def _live(bt, lens, P, pt):
    """``[B, positions]``: inside the row's length, on an allocated page."""
    return (np.arange(bt.shape[1] * pt)[None] < lens[:, None]) \
        & np.repeat((bt >= 0) & (bt < P), pt, axis=1)


def _reference(q, kp, vp, bt, lens, Hkv, pt=PT):
    """The gather path in float32: every row's whole view, masked."""
    B, H, D = q.shape
    G, P = H // Hkv, kp.shape[0] // pt
    live = _live(bt, lens, P, pt)
    view = [np.asarray(p, np.float32).reshape(P, pt, -1)[
        np.clip(bt, 0, P - 1)][..., :Hkv * D].reshape(B, -1, Hkv, D)
        for p in (kp, vp)]
    k, v = (np.where(live[:, :, None, None], x, 0.0) for x in view)
    s = np.einsum("bkgd,bskd->bkgs",
                  np.asarray(q, np.float32).reshape(B, Hkv, G, D), k) \
        * D ** -0.5
    s = np.where(live[:, None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True)) * live[:, None, None]
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-30)
    return np.einsum("bkgs,bskd->bkgd", p, v).reshape(B, H, D)


def _case(widths, rows, dtype, pt=PT, seed=0):
    """Pools with NaN wherever nothing live lies (pad lanes, the last
    page's unwritten rows, pages of no row), tables in shuffled physical
    order, queries."""
    Hkv, G = WIDTHS[widths]
    D, W = 64, kv_row_width(Hkv, 64)
    rows = [r if isinstance(r, tuple) else (r, ()) for r in rows]
    B, P = len(rows), len(rows) * NPG + 3
    rng = np.random.default_rng(seed)
    kp, vp = (np.full((P * pt, W), np.nan, np.float32) for _ in range(2))
    bt = np.full((B, NPG), P, np.int32)
    order = iter(rng.permutation(P))
    for b, (n, holes) in enumerate(rows):
        for pg in range(-(-n // pt)):
            if pg in holes:
                continue
            bt[b, pg] = at = next(order)
            fill = min(pt, n - pg * pt)
            for pool in (kp, vp):
                pool[at * pt:at * pt + fill, :Hkv * D] = \
                    rng.standard_normal((fill, Hkv * D))
    q = rng.standard_normal((B, Hkv * G, D))
    lens = np.asarray([n for n, _ in rows], np.int32)
    q, kp, vp = (jnp.asarray(a, dtype) for a in (q, kp, vp))
    return q, kp, vp, bt, lens, Hkv


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_kernel_matches_the_gather_path_over_the_same_pool(widths, lengths):
    q, kp, vp, bt, lens, Hkv = _case(widths, LENGTHS[lengths], jnp.bfloat16)
    got = np.asarray(paged_decode_attention(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(lens), num_kv_heads=Hkv,
        page_tokens=PT))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    want = _reference(q, kp, vp, bt, lens, Hkv)
    # bf16 values, exact products, float32 sums on both sides
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    dead = ~_live(bt, lens, kp.shape[0] // PT, PT).any(axis=1)
    assert not got[dead].any(), "a row with nothing live returns zeros"


def test_kernel_reads_a_float32_pool_in_pages_of_eight():
    q, kp, vp, bt, lens, Hkv = _case(
        "G4_W512", [1, 9, (30, (1,)), 8 * NPG, 0], jnp.float32, pt=8)
    got = np.asarray(paged_decode_attention(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(lens), num_kv_heads=Hkv,
        page_tokens=8))
    np.testing.assert_allclose(
        got, _reference(q, kp, vp, bt, lens, Hkv, pt=8), atol=2e-5,
        rtol=2e-5)


def test_kernel_refuses_a_page_that_is_not_whole_tiles():
    q, kp, vp, bt, lens, Hkv = _case("G4_W512", [5], jnp.bfloat16)
    with pytest.raises(ValueError, match="whole"):
        paged_decode_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(lens),
                               num_kv_heads=Hkv, page_tokens=8)


# -- where the model uses it -------------------------------------------------

@pytest.fixture
def as_on_tpu(monkeypatch):
    """The model's rule sees a TPU backend; the kernel itself still sees
    the CPU and runs under the interpreter."""
    monkeypatch.setattr(gpt_mod, "_on_tpu", lambda: True)


def _cfg(**kw):
    return GPTConfig(**{**dict(
        vocab_size=61, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, max_position_embeddings=64,
        dtype=jnp.float32, per_row_positions=True, kv_page_tokens=8,
        kv_pool_pages=16), **kw})


def _params(cfg):
    return GPT(cfg).init(jax.random.key(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]


def _traces_kernel(cfg, T, B=2):
    """Whether the cached step of T tokens a row holds the kernel."""
    model = GPT(cfg, decode=True)
    params = jax.eval_shape(lambda: _params(cfg))
    cache = jax.eval_shape(lambda p: init_cache(cfg, p, B), params)
    text = str(jax.make_jaxpr(lambda p, c, t: model.apply(
        {"params": p, "cache": c}, t, mutable=["cache"]))(
            params, cache, jnp.zeros((B, T), jnp.int32)))
    return "pallas_call" in text


@pytest.mark.parametrize("why,kw,T,engages", [
    ("a decode step of a paged cache", {}, 1, True),
    ("a verify step", {}, 4, False),
    ("a prefill bucket", {}, 16, False),
    ("a sliding window", {"sliding_window": 16}, 1, False),
    ("a page of four float32 rows is half a tile", {"kv_page_tokens": 4}, 1,
     False),
    ("a page of eight bfloat16 rows is half a tile",
     {"dtype": jnp.bfloat16}, 1, False),
    ("the dense cache", {"kv_page_tokens": None, "kv_pool_pages": None}, 1,
     False),
])
def test_the_kernel_engages_by_shape_alone(as_on_tpu, why, kw, T, engages):
    cfg = _cfg(**kw)
    assert attends_pages_in_place(cfg, T) is engages, why
    assert _traces_kernel(cfg, T) is engages, why


def test_off_the_tpu_the_model_takes_the_gather_path():
    assert not attends_pages_in_place(_cfg())
    assert not _traces_kernel(_cfg(), 1)


def test_a_mesh_of_several_devices_takes_the_gather_path(as_on_tpu):
    from jax.sharding import Mesh

    cfg = _cfg()
    devs = np.asarray(jax.devices())
    if devs.size < 2:
        pytest.skip("one device")
    with Mesh(devs[:2].reshape(1, 2), ("dp", "tp")):
        assert not attends_pages_in_place(cfg)
        assert not _traces_kernel(cfg, 1)
    with Mesh(devs[:1].reshape(1, 1), ("dp", "tp")):
        assert attends_pages_in_place(cfg)


# -- the batcher: the same requests, kernel against gather -------------------

@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(_cfg(), num_layers=1, per_row_positions=False,
                              kv_page_tokens=None, kv_pool_pages=None)
    return cfg, _params(cfg)


def _gather_only(monkeypatch):
    for mod in (gpt_mod, serving_mod):
        monkeypatch.setattr(mod, "attends_pages_in_place",
                            lambda *a, **k: False)


def _serve(cfg, params, **kw):
    """Five requests through four slots (so one is admitted mid-flight);
    the streams, the batcher, and per request the teacher-forced logits
    of its stream's positions by the plain full forward."""
    sampled = kw.pop("sampled", False)
    b = ContinuousBatcher(cfg, params, max_batch=4, kv_page_tokens=8,
                          prefix_cache=False, **kw)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 9, 17, 3, 11)]
    rids = [b.submit(p, 7, **(dict(temperature=0.8, top_p=0.9, seed=7 + i)
                               if sampled and i % 2 else {}))
            for i, p in enumerate(prompts)]
    out = b.run()
    return prompts, [out[r] for r in rids], b


#: "greedy" is the step-by-step path (an ``eos_id`` no row emits makes the
#: batcher stand down at every step), "decode_ahead" what a default-built
#: batcher does
MODES = {"greedy": {"eos_id": -1}, "sampled": {"sampled": True},
         "decode_block_steps": {"decode_block_steps": 4},
         "decode_ahead": {}}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_batcher_streams_agree_with_the_gather_path(served, as_on_tpu,
                                                    monkeypatch, mode):
    cfg, params = served
    prompts, got, b = _serve(cfg, params, **dict(MODES[mode]))
    assert b.kv_pages_viewed > 0
    assert 0 < b.kv_pages_read < b.kv_pages_viewed
    if mode == "decode_block_steps":
        assert b.decode_steps > b.decode_dispatches
    # "sampled": the fifth request, a greedy one, decodes alone at the end
    assert (b.decode_ahead_dispatches > 0) == (mode in ("decode_ahead",
                                                        "sampled"))
    with monkeypatch.context() as m:
        _gather_only(m)
        _, want, g = _serve(cfg, params, **dict(MODES[mode]))
    assert g.kv_pages_viewed == 0 and g.kv_pages_read == b.kv_pages_read
    # teacher-forced: the full forward's logits over prompt + the
    # kernel's stream put the gather path's token where the kernel's is,
    # or within the serving tests' tolerance of it (a float32 near-tie)
    model = GPT(cfg)
    for p, a, w in zip(prompts, got, want):
        if a.tolist() == w.tolist():
            continue
        at = int(np.argmax(a != w))
        seq = np.concatenate([p, a[:at]])
        logits = np.asarray(model.apply({"params": params},
                                        seq[None])[0, -1])
        assert abs(logits[a[at]] - logits[w[at]]) < 1e-4, \
            (mode, at, a.tolist(), w.tolist())


def test_verify_and_prefill_dispatches_view_nothing(served, as_on_tpu):
    """A speculative batcher's verify takes the gather path (its pages are
    read, none viewed by the kernel); so do the prefills, chunked or not,
    which no counter sees at all."""
    cfg, params = served
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8,
                          prefix_cache=False, speculative_k=2,
                          prefill_chunk=8)
    rng = np.random.default_rng(2)
    base = rng.integers(0, cfg.vocab_size, 6)
    for _ in range(2):
        b.submit(np.tile(base, 3), 8)        # repeats: drafts exist
    b.run()
    assert b.spec_proposed > 0 and b.kv_pages_read > 0
    # only a step that drafted nothing falls through to the plain decode
    # step, which attends in place: fewer row-steps viewed than ran
    npg = cfg.max_position_embeddings // 8
    assert b.kv_pages_viewed % npg == 0
    assert b.kv_pages_viewed // npg < 2 * b.decode_dispatches


def test_decode_program_names_the_kernel_scope(served, as_on_tpu):
    """``attn/paged_decode`` in place of ``kv_gather``, ``scores`` and
    ``context`` (docs/observability.md "Profiler spans")."""
    cfg, params = served
    b = ContinuousBatcher(cfg, params, max_batch=2, kv_page_tokens=8)
    text = b._step.lower(params, b.cache, jnp.zeros((2,), jnp.int32)) \
        .as_text(debug_info=True)
    assert "/kv_store" in text and "/paged_decode" in text
    for scope in ("kv_gather", "scores", "context"):
        assert f"/{scope}" not in text, scope
