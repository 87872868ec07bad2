"""Pre-bake a serving tier's AOT executable cache.

Runs the SAME bucket x group warm-up sweep a warm standby pays
(``serving.standby._warm_batcher``) against a throwaway
``ContinuousBatcher`` armed with an ``AOTExecutableCache``
(``serving/aot.py``), so every serve-step executable the sweep touches —
decode step, the prefill bucket/group grid, scatter, and (with
``--spec-k``) the draft-propose + fused-verify pair — is compiled ONCE,
here, and serialized to the tier's AOT cache directory
(``util.aot_cache_dir()``: the ``aot/`` sub-directory of
``JAX_COMPILATION_CACHE_DIR`` where set, else of ``<checkout>/.jax_cache``).
Every later process of an ``aot_cache=True`` tier (a cold replica, a
promoting standby) resolves those sites by
``deserialize_and_load``: a cache read where the fleet used to pay an
XLA compile inside the cold-start/heal window.

    JAX_COMPILATION_CACHE_DIR=/shared/jax python scripts/tfos_warmcache.py \\
        --builder mypkg.models:my_builder --max-batch 4 --spec-k 4

The builder is any picklable-by-reference serving model builder
(``module:function`` resolving to ``f(args) -> (cfg, params)``); the
default is the tiny seeded GPT the serving benches use, which is what
the repo's CI smoke pre-bakes.  ``--runs 2 --check-warm`` is the
self-test mode (``scripts/ci.sh --bench-smoke``): run the sweep twice
against the same directory and FAIL unless the second run compiled
exactly 0 executables — the load-or-compile contract, checked
end-to-end.
"""

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

VOCAB, HIDDEN, LAYERS, HEADS, MAXLEN = 83, 32, 2, 4, 64


def default_builder(args):
    """The serving benches' tiny seeded GPT (kept in sync with
    ``scripts/bench_serving.py``), so CI's pre-bake smoke exercises the
    same executables the bench tier loads."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
                    num_heads=HEADS, intermediate_size=2 * HIDDEN,
                    max_position_embeddings=MAXLEN, dtype=jnp.float32,
                    pos_encoding="rope")
    params = GPT(cfg).init(jax.random.key(int(args.get("seed", 0))),
                           jnp.ones((1, 4), jnp.int32))["params"]
    return cfg, params


def _resolve_builder(spec: str | None):
    if not spec:
        return default_builder
    mod, sep, fn = spec.partition(":")
    if not sep:
        raise SystemExit(f"--builder wants module:function, got {spec!r}")
    return getattr(importlib.import_module(mod), fn)


def warm_once(builder, *, max_batch: int, seed: int,
              spec_k: int | None, draft_window: int,
              kv_page_tokens: int | None, prefill_chunk: int | None) -> dict:
    """One pre-bake pass: fresh batcher + fresh cache handle over the
    (shared) directory, the standby warm-up sweep, stats out."""
    from tensorflowonspark_tpu import util
    from tensorflowonspark_tpu.models.serving import (ContinuousBatcher,
                                                      DraftModel)
    from tensorflowonspark_tpu.serving.aot import AOTExecutableCache
    from tensorflowonspark_tpu.serving.standby import _warm_batcher

    util.enable_compilation_cache()
    cache = AOTExecutableCache(util.aot_cache_dir())
    cfg, params = builder({"seed": seed})
    kwargs = {}
    if spec_k is not None:
        kwargs["speculative_k"] = int(spec_k)
    if kv_page_tokens is not None:
        kwargs["kv_page_tokens"] = int(kv_page_tokens)
    if prefill_chunk is not None:
        kwargs["prefill_chunk"] = int(prefill_chunk)
    batcher = ContinuousBatcher(cfg, params, max_batch=int(max_batch),
                                aot_cache=cache, **kwargs)
    if spec_k is not None:
        # pre-bake the draft-propose executables too: same-config draft
        # (a real tier's draft differs, but its propose executable is
        # keyed on the DRAFT's config — pre-bake with --builder pointing
        # at the draft for that)
        batcher.set_draft(DraftModel(cfg, params, window=int(draft_window)))
    t0 = time.monotonic()
    _warm_batcher(batcher)
    return {"wall_secs": round(time.monotonic() - t0, 3), **cache.stats()}


def main():
    ap = argparse.ArgumentParser(
        description="Pre-bake serving AOT executables into the tier's "
                    "AOT cache (placed by JAX_COMPILATION_CACHE_DIR).")
    ap.add_argument("--builder", default=None,
                    help="module:function serving model builder "
                         "(default: the tiny bench GPT)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spec-k", type=int, default=None,
                    help="also pre-bake the speculative verify + "
                         "draft-propose executables for this k")
    ap.add_argument("--draft-window", type=int, default=32,
                    help="draft context window for the propose pre-bake")
    ap.add_argument("--kv-page-tokens", type=int, default=None,
                    help="pre-bake the PAGED executables (must match the "
                         "tier's batcher_kwargs)")
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--runs", type=int, default=1,
                    help="sweep repetitions (fresh batcher each)")
    ap.add_argument("--check-warm", action="store_true",
                    help="fail unless the LAST run compiled 0 "
                         "executables (CI self-test)")
    ap.add_argument("--json", action="store_true",
                    help="print per-run stats as JSON")
    args = ap.parse_args()

    builder = _resolve_builder(args.builder)
    runs = []
    for i in range(max(1, args.runs)):
        stats = warm_once(
            builder, max_batch=args.max_batch,
            seed=args.seed, spec_k=args.spec_k,
            draft_window=args.draft_window,
            kv_page_tokens=args.kv_page_tokens,
            prefill_chunk=args.prefill_chunk)
        runs.append(stats)
        if not args.json:
            print(f"run {i + 1}: {stats['compiles']} compiled, "
                  f"{stats['loads']} loaded, {stats['errors']} errors "
                  f"in {stats['wall_secs']}s -> {stats['dir']}")
    if args.json:
        print(json.dumps({"runs": runs}, indent=2))
    if args.check_warm and runs[-1]["compiles"] != 0:
        print(f"check-warm FAILED: last run compiled "
              f"{runs[-1]['compiles']} executable(s); a pre-baked cache "
              "must serve every site from disk", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
