"""One-shot real-chip sweep: every queued single-chip measurement in one run.

All on-chip measurements are orchestrated into ONE priority-ordered,
fail-forward run: each stage is a subprocess with its own timeout — so one
process at a time owns the chip, and this parent never imports jax —
artifacts are written incrementally, and two consecutive stage failures
abort.  Run it on the machine with the chip:

    python scripts/tpu_sweep.py            # full sweep, priority order
    python scripts/tpu_sweep.py --stage resnet --batch 512   # one stage

The committed ``bench_artifacts/`` rows of this sweep predate the attached
chip and have not been re-measured on it; ROADMAP A1's benchmark replaces
this script as the measurement path.

Stages, in value order (VERDICT r2 "next round" item 1):

1. ``resnet`` batch sweep       — b128/256/512/1024 (+remat fallback at
                                  b1024 OOM), img/s + MFU per point →
                                  ``resnet_sweep.json``;
2. ``flash`` block-size sweep   — block_q×block_k grid at T=4096, no-mask
                                  fast path, causal, sliding window →
                                  ``flash_sweep.json``;
3. ``decode`` matrix            — GQA (kv heads 12/4/1) × {bf16, int8,
                                  int8+int8kv} + sliding-window decode →
                                  ``decode_matrix.json``;
4. ``bench_overlap.py``         — the streamed-input overlap fraction with
                                  real async DMA → ``overlap_tpu.json``.

Every artifact records the device kind; refresh ``docs/performance.md`` from
them after the run.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(REPO, "bench_artifacts")


def _path(name: str) -> str:
    """Artifact path; smoke runs get a ``smoke_`` prefix so they can never
    clobber real-chip artifacts."""
    return os.path.join(ART, ("smoke_" if SMOKE else "") + name)


def _write(name: str, payload: dict) -> None:
    os.makedirs(ART, exist_ok=True)
    with open(_path(name), "w") as f:
        json.dump(payload, f, indent=2)
    print(f"sweep: wrote {os.path.relpath(_path(name), REPO)}", flush=True)


SMOKE = bool(os.environ.get("SWEEP_SMOKE"))  # tiny-shape CPU validation mode


def _merge_row(name: str, row: dict, key) -> None:
    """Merge ``row`` into the ``rows`` list of artifact ``name``: replaces
    any prior row with the same ``key(row)``, keeps the rest, sorts."""
    path = _path(name)
    data = {"rows": []}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["rows"] = [r for r in data["rows"] if key(r) != key(row)] + [row]
    data["rows"].sort(key=key)
    _write(name, data)


def _device():
    import jax

    d = jax.devices()[0]
    assert SMOKE or d.platform == "tpu", f"not a TPU: {d.platform}"
    return d


# ---------------------------------------------------------------------------
# Stage: resnet batch sweep
# ---------------------------------------------------------------------------
def stage_resnet(batch: int, remat: bool = False,
                 stem: str = "conv7", bn: str = "f32",
                 write: bool = True, loop: bool = False,
                 xla_label: str = "",
                 compiler_options: dict | None = None) -> dict:
    """One (batch, remat, stem, bn) point.  ``write=False`` (used by
    scripts/profile_resnet.py, whose timed loop runs under the profiler's
    trace overhead) skips the resnet_sweep.json merge so a profiling run
    can never overwrite a clean-timing row.

    ``loop=True`` runs the whole timed window inside ONE jitted
    ``lax.fori_loop`` (single dispatch) instead of one dispatch per step:
    the difference between the two rows isolates host-dispatch overhead:
    a large loop-vs-eager gap means the eager MFU number undercounts what
    the chip itself sustains."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.models import ResNet50

    dev = _device()
    image, steps, warmup = (64, 2, 1) if SMOKE else (224, 20, 3)
    if SMOKE:
        batch = min(batch, 8)
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, stem=stem,
                     norm_dtype=jnp.bfloat16 if bn == "bf16" else jnp.float32)
    tx = optax.sgd(0.1, momentum=0.9)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(
        (batch, image, image, 3)).astype(np.float32), jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 1000, (batch,)).astype(np.int32))
    variables = model.init(jax.random.key(0), x[:1], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt_state = tx.init(params)

    def loss_fn(p, bs, x, y):
        logits, updates = model.apply(
            {"params": p, "batch_stats": bs}, x, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, updates["batch_stats"]

    if remat:
        loss_fn = jax.checkpoint(loss_fn)

    def step_fn(p, bs, o, x, y):
        (loss, bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, bs, x, y)
        upd, o = tx.update(grads, o, p)
        return optax.apply_updates(p, upd), bs, o, loss

    step_jit = jax.jit(step_fn, donate_argnums=(0, 1, 2))
    # AOT-compile once and EXECUTE the same executable: calling the jit
    # wrapper after lower().compile() would trace+compile the identical
    # program a second time (these subprocesses start cold).
    # compiler_options is the MFU flag-attack lever: PJRT compile options
    # reach the TPU compiler where an XLA_FLAGS parser may not know the
    # xla_tpu_* names.
    step = step_jit.lower(params, batch_stats, opt_state, x, y).compile(
        compiler_options=compiler_options or None)
    cost = step.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))

    if loop:
        def megastep(p, bs, o, x, y, n):
            def body(_, carry):
                p, bs, o, _loss = carry
                p, bs, o, loss = step_fn(p, bs, o, x, y)
                return p, bs, o, loss
            return jax.lax.fori_loop(
                0, n, body, (p, bs, o, jnp.zeros((), jnp.float32)))

        # AOT like the eager path so compiler_options apply to the program
        # actually timed (a jit __call__ would compile without them)
        mega = jax.jit(
            megastep, static_argnums=(5,), donate_argnums=(0, 1, 2)
        ).lower(params, batch_stats, opt_state, x, y, steps).compile(
            compiler_options=compiler_options or None)
        # the compiled executable bakes the static n (same for warmup and
        # the timed call — a different n would be a fresh compile)
        params, batch_stats, opt_state, loss = mega(
            params, batch_stats, opt_state, x, y)
        float(loss)
        t0 = time.perf_counter()
        params, batch_stats, opt_state, loss = mega(
            params, batch_stats, opt_state, x, y)
        float(loss)
        dt = (time.perf_counter() - t0) / steps
    else:
        # the float(loss) fetch ends each timed region
        for _ in range(warmup):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, x, y)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, x, y)
        float(loss)
        dt = (time.perf_counter() - t0) / steps
    peak = 197e12 if "v5 lite" in dev.device_kind.lower() else None
    row = {
        "batch": batch, "remat": remat, "stem": stem, "bn": bn,
        "loop": loop, "xla": xla_label,
        "images_per_sec": round(batch / dt, 1),
        "step_ms": round(dt * 1e3, 2),
        "flops_per_step": flops,
        "mfu": round(flops / dt / peak, 4) if (flops and peak) else None,
        "device": dev.device_kind,
    }
    if xla_label:
        row["xla_flags"] = os.environ.get("XLA_FLAGS", "")
    if compiler_options:  # provenance regardless of labeling
        row["compiler_options"] = dict(compiler_options)
    print("sweep resnet:", json.dumps(row), flush=True)
    if write:
        _merge_row("resnet_sweep.json", row,
                   lambda r: (r["batch"], r["remat"], r.get("stem", "conv7"),
                              r.get("bn", "f32"), r.get("loop", False),
                              r.get("xla", "")))
    return row


# ---------------------------------------------------------------------------
# Stage: flash-attention block sweep + fast paths
# ---------------------------------------------------------------------------
def stage_flash() -> dict:
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.ops import flash_attention

    dev = _device()
    B, T, H, D = (2, 512, 4, 64) if SMOKE else (4, 4096, 12, 64)
    q = jax.random.normal(jax.random.key(0), (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, T, H, D), jnp.bfloat16)
    mask = jnp.ones((B, T), bool)

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (D ** 0.5)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def dense_causal(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (D ** 0.5)
        pos = jnp.arange(s.shape[-1])
        s = jnp.where(pos[:, None] >= pos[None, :], s, -1e30)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def timeit(fn, *args, iters=20):

        f = jax.jit(fn)
        o = f(*args)
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        for _ in range(iters):
            o = f(*args)
        jax.block_until_ready(o)
        return (time.perf_counter() - t0) / iters * 1e3  # ms

    out = {"shape": {"B": B, "T": T, "H": H, "D": D, "dtype": "bfloat16"},
           "device": dev.device_kind, "dense_ms": round(timeit(dense, q, k, v), 3)}
    blocks = {}
    for bq, bk in ((256, 256), (512, 512), (512, 1024), (1024, 512),
                   (1024, 1024)):
        try:
            blocks[f"{bq}x{bk}"] = round(timeit(
                lambda q, k, v: flash_attention(q, k, v, block_q=bq,
                                                block_k=bk), q, k, v), 3)
        except Exception as e:  # noqa: BLE001 — record and continue the grid
            blocks[f"{bq}x{bk}"] = f"failed: {e!r}"
        print(f"sweep flash: {bq}x{bk} -> {blocks[f'{bq}x{bk}']}", flush=True)
    out["block_ms"] = blocks
    ok = {k: v for k, v in blocks.items() if isinstance(v, float)}
    if ok:
        best = min(ok, key=ok.get)
        out["best_block"] = best
        out["best_speedup_vs_dense"] = round(out["dense_ms"] / ok[best], 3)
    _write("flash_sweep.json", out)  # block grid is safe even if the rest dies

    def section(key, fn, *a):
        try:
            out[key] = round(timeit(fn, *a), 3)
        except Exception as e:  # noqa: BLE001 — keep what we have
            out[key] = f"failed: {e!r}"
        print(f"sweep flash: {key} -> {out[key]}", flush=True)
        _write("flash_sweep.json", out)

    # no-mask fast path vs all-True mask (bias pass skipped entirely)
    section("nomask_ms", lambda q, k, v: flash_attention(q, k, v), q, k, v)
    section("allones_mask_ms",
            lambda q, k, v, m: flash_attention(q, k, v, mask=m), q, k, v, mask)
    section("causal_ms",
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
    for w in (256, 512, 1024):
        section(f"window{w}_ms",
                lambda q, k, v, w=w: flash_attention(q, k, v, causal=True,
                                                     window=w), q, k, v)

    # TRAINING regime: forward + backward through the custom VJP — the
    # number that decides whether flash should be the training-attention
    # default (fwd-only above decides the inference default)
    def fwdbwd(attn_fn):
        def loss(q, k, v):
            return attn_fn(q, k, v).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))

    section("dense_fwdbwd_ms", fwdbwd(dense), q, k, v)
    section("flash_fwdbwd_ms",
            fwdbwd(lambda q, k, v: flash_attention(q, k, v, causal=True)),
            q, k, v)
    section("dense_causal_fwdbwd_ms",
            fwdbwd(lambda q, k, v: dense_causal(q, k, v)), q, k, v)
    if isinstance(out.get("flash_fwdbwd_ms"), float) \
            and isinstance(out.get("dense_causal_fwdbwd_ms"), float):
        out["fwdbwd_speedup_vs_dense_causal"] = round(
            out["dense_causal_fwdbwd_ms"] / out["flash_fwdbwd_ms"], 3)
        _write("flash_sweep.json", out)
    return out


# ---------------------------------------------------------------------------
# Stage: GPT-124M training step MFU (the transformer-side headline)
# ---------------------------------------------------------------------------
def stage_gpt_train(batch: int, remat: bool = False,
                    attn: str = "dense", model: str = "124m") -> dict:
    """Train-step throughput/MFU for GPT-124M (768/12L/12H) or GPT-350M
    (1024/24L/16H) at T=1024, bf16, tied chunked xent head, adamw.

    MFU here uses the ANALYTIC FLOP count (6·P_matmul·tokens for the
    matmul params + 12·L·B·T²·H for attention scores·values, fwd+bwd),
    not ``cost_analysis()``: the chunked LM head runs under ``lax.scan``
    whose body XLA's analysis counts once instead of ×trip-count
    (the same undercount scripts/scaling_model.py corrects for), so the
    XLA number is reported alongside but not used for MFU.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.models import GPT, GPTConfig
    from tensorflowonspark_tpu.ops import tied_softmax_xent

    dev = _device()
    size = model  # `model` is rebound to the GPT module below
    dims = {"124m": (768, 12, 12, 3072),
            "350m": (1024, 24, 16, 4096)}[size]
    H_, L_, heads_, ffn_ = dims
    cfg = GPTConfig(vocab_size=50257, hidden_size=H_, num_layers=L_,
                    num_heads=heads_, intermediate_size=ffn_,
                    max_position_embeddings=1024, dtype=jnp.bfloat16,
                    remat=remat)
    T, steps, warmup = 1024, 10, 2
    if SMOKE:
        cfg = dataclasses.replace(cfg, vocab_size=512, hidden_size=64,
                                  num_layers=2, num_heads=4,
                                  intermediate_size=128,
                                  max_position_embeddings=128)
        T, steps, warmup, batch = 128, 2, 1, min(batch, 2)
    if attn == "flash":
        from tensorflowonspark_tpu.ops import flash_attention
        cfg = dataclasses.replace(cfg, attention_fn=flash_attention)
    model = GPT(cfg)
    tx = optax.adamw(3e-4)
    ids = jax.random.randint(jax.random.key(1), (batch, T + 1), 0,
                             cfg.vocab_size)
    x, y = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.key(0), x[:1])["params"]
    opt_state = tx.init(params)

    def loss_fn(p, x, y):
        hidden = model.apply({"params": p}, x, method="hidden")
        table = p["tok_emb"]["embedding"]
        table = getattr(table, "value", table)
        return tied_softmax_xent(hidden, table, y).mean()

    def step_fn(p, o, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        upd, o = tx.update(grads, o, p)
        return optax.apply_updates(p, upd), o, loss

    # AOT-compile once and execute that executable (see stage_resnet)
    step = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
        params, opt_state, x, y).compile()
    cost = step.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0.0))

    # analytic fwd+bwd FLOPs: matmul params (every 2D+ leaf; excludes
    # norms/biases and the position table; includes the tied head via
    # tok_emb) + attention
    H, L = cfg.hidden_size, cfg.num_layers
    p_matmul = sum(
        leaf.size for path, leaf in
        jax.tree_util.tree_leaves_with_path(params)
        if getattr(leaf, "ndim", 0) >= 2
        and not any(getattr(k, "key", None) == "pos_emb" for k in path))
    flops = 6 * p_matmul * batch * T + 12 * L * batch * T * T * H

    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, x, y)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, x, y)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / steps
    peak = 197e12 if "v5 lite" in dev.device_kind.lower() else None
    row = {
        "batch": batch, "seq": T, "remat": remat, "attn": attn,
        "model": size,
        "tokens_per_sec": round(batch * T / dt, 1),
        "step_ms": round(dt * 1e3, 2),
        "flops_analytic": flops, "flops_xla": xla_flops,
        "mfu": round(flops / dt / peak, 4) if peak else None,
        "device": dev.device_kind,
    }
    print("sweep gpt_train:", json.dumps(row), flush=True)
    _merge_row("gpt_train_sweep.json", row,
               lambda r: (r["batch"], r["remat"], r.get("attn", "dense"),
                          r.get("model", "124m")))
    return row


# ---------------------------------------------------------------------------
# Stage: decode matrix (GQA x quantization x window)
# ---------------------------------------------------------------------------
def stage_decode() -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPT, GPTConfig, greedy_generate
    from tensorflowonspark_tpu.ops import quantize_params

    dev = _device()
    base = GPTConfig(vocab_size=32000, hidden_size=768, num_layers=12,
                     num_heads=12, intermediate_size=3072,
                     max_position_embeddings=1024, dtype=jnp.bfloat16)
    if SMOKE:
        base = dataclasses.replace(base, vocab_size=512, hidden_size=64,
                                   num_layers=2, num_heads=4,
                                   intermediate_size=128,
                                   max_position_embeddings=512)
    B, T0, NEW = (2, 8, 8) if SMOKE else (8, 128, 128)
    prompt = jax.random.randint(jax.random.key(1), (B, T0), 0,
                                base.vocab_size)
    gen = jax.jit(greedy_generate, static_argnums=(0, 3))

    def tps(cfg, params, iters=3, fn=None, ids=None):
        # fetching the generated ids (a few KB) proves the decode loops
        # actually ran on device.
        fn = fn or gen
        ids = prompt if ids is None else ids
        out = fn(cfg, params, ids, NEW)
        jax.device_get(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(cfg, params, ids, NEW)
        jax.device_get(out)
        return round(B * NEW / ((time.perf_counter() - t0) / iters), 1)

    kv_list = (12, 4, 1) if base.num_heads == 12 else tuple(sorted(
        {base.num_heads, max(1, base.num_heads // 2), 1}, reverse=True))
    rows = []
    for kv in kv_list:
        cfg = dataclasses.replace(base, num_kv_heads=kv)
        params = GPT(cfg).init(jax.random.key(0),
                               jnp.ones((1, 8), jnp.int32))["params"]
        row = {"kv_heads": kv, "bf16_tps": tps(cfg, params)}
        try:
            qp = jax.device_put(quantize_params(params))
            row["int8_tps"] = tps(cfg, qp)
            row["int8_kv_tps"] = tps(
                dataclasses.replace(cfg, kv_cache_int8=True), qp)
        except Exception as e:  # noqa: BLE001 — partial rows still useful
            row["quant_error"] = repr(e)
        try:
            qp4 = jax.device_put(quantize_params(params, bits=4))
            row["int4_tps"] = tps(cfg, qp4)
        except Exception as e:  # noqa: BLE001
            row["int4_error"] = repr(e)
        rows.append(row)
        print("sweep decode:", json.dumps(row), flush=True)
    # sliding-window + rolling cache decode (long-context regime)
    try:
        wcfg = dataclasses.replace(base, sliding_window=256,
                                   rolling_kv_cache=True)
        params = GPT(wcfg).init(jax.random.key(0),
                                jnp.ones((1, 8), jnp.int32))["params"]
        rows.append({"window": 256, "rolling": True,
                     "bf16_tps": tps(wcfg, params)})
        print("sweep decode:", json.dumps(rows[-1]), flush=True)
    except Exception as e:  # noqa: BLE001
        rows.append({"window": 256, "error": repr(e)})
    # prompt-lookup speculative decoding on a repetitive continuation —
    # the regime it exists for (greedy-exact either way)
    try:
        import functools

        from tensorflowonspark_tpu.models import lookup_generate

        params = GPT(base).init(jax.random.key(0),
                                jnp.ones((1, 8), jnp.int32))["params"]
        # period <= T0/2 so the prompt really contains repeated n-grams
        # (T0=8 in smoke: period 4)
        period = min(16, max(2, T0 // 2))
        rep = jnp.tile(jnp.arange(period), (B, T0 // period + 1))[:, :T0]
        lk = jax.jit(functools.partial(lookup_generate, draft_len=8),
                     static_argnums=(0, 3))
        _, st = lookup_generate(base, params, rep, NEW, draft_len=8,
                                return_stats=True)
        rows.append({"spec_lookup": True,
                     "greedy_tps": tps(base, params, ids=rep),
                     "lookup_tps": tps(base, params, fn=lk, ids=rep),
                     "forwards": int(st["forwards"]), "tokens": NEW})
        print("sweep decode:", json.dumps(rows[-1]), flush=True)
    except Exception as e:  # noqa: BLE001
        rows.append({"spec_lookup": True, "error": repr(e)})
    out = {"batch": B, "prompt": T0, "new_tokens": NEW,
           "model": "gpt-124M-ish", "device": dev.device_kind, "rows": rows}
    _write("decode_matrix.json", out)
    return out


# ---------------------------------------------------------------------------
# Stage: continuous-batching serving throughput
# ---------------------------------------------------------------------------
def stage_serving() -> dict:
    """ContinuousBatcher vs arrival-order static batching on mixed-length
    traffic — measured under BOTH arrival regimes:

    - ``steady``: every request queued upfront (the drain-a-backlog case);
    - ``bursty``: requests arrive in waves mid-decode (the regime
      continuous batching exists for — slots must be refilled while
      others decode).

    Per pattern: tokens/sec, slot occupancy (useful slot-steps /
    capacity slot-steps — the utilization static batching wastes on
    drained stragglers), prefill-admission overhead as a fraction of
    wall time, and the prefill dispatch count (batched group admission:
    O(buckets), not O(requests)).  Symmetric sequential-dispatch counts
    stay as the hardware-independent check."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.models import (ContinuousBatcher, GPT,
                                              GPTConfig, greedy_generate)

    dev = _device()
    cfg = GPTConfig(vocab_size=32000, hidden_size=768, num_layers=12,
                    num_heads=12, intermediate_size=3072,
                    max_position_embeddings=1024, dtype=jnp.bfloat16)
    n_req, lo, hi, slots = 16, 32, 128, 4
    if SMOKE:
        cfg = dataclasses.replace(cfg, vocab_size=512, hidden_size=64,
                                  num_layers=2, num_heads=4,
                                  intermediate_size=128,
                                  max_position_embeddings=256)
        n_req, lo, hi, slots = 6, 4, 12, 2
    params = GPT(cfg).init(jax.random.key(0),
                           jnp.ones((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(0)
    # one shared prompt length -> one prefill bucket; budgets vary
    T0 = 16 if not SMOKE else 4
    reqs = [(rng.integers(0, cfg.vocab_size, (T0,)).astype(np.int32),
             int(rng.integers(lo, hi + 1))) for _ in range(n_req)]
    total_tokens = sum(n for _, n in reqs)

    # ONE batcher for warmup and timing: its decode/prefill/scatter
    # executables compile on the warm drain and are reused by the timed
    # drains (a fresh instance would re-jit everything inside the timed
    # window, distorting the comparison against the warmed static path)
    batcher = ContinuousBatcher(cfg, params, max_batch=slots)

    def run_continuous(b, schedule):
        """Drive the batcher against an arrival ``schedule``
        (``[(arrive_at_step, request), ...]``); admission wall time is
        measured via a timed wrapper, dispatch counts come from the
        batcher's own public counters."""
        admit_s = [0.0]
        orig_admit = b._admit

        def timed_admit():
            t = time.perf_counter()
            try:
                return orig_admit()
            finally:
                admit_s[0] += time.perf_counter() - t

        b._admit = timed_admit
        prefills0 = b.prefill_dispatches
        decodes0 = b.decode_dispatches
        dsteps0 = b.decode_steps
        try:
            pending = sorted(schedule, key=lambda x: x[0])
            rids, remaining, steps = [], set(), 0
            while pending or remaining:
                while pending and pending[0][0] <= steps:
                    _, (p, n) = pending.pop(0)
                    rid = b.submit(p, n)
                    rids.append(rid)
                    remaining.add(rid)
                remaining.difference_update(b.step())
                steps += 1
            res = b.run()
            # THIS drain's requests must have produced exactly the token
            # budget (no eos is configured, so budgets are fully consumed);
            # the shared batcher accumulates results across drains, so the
            # check is per-drain by request id
            got = sum(len(res[r]) for r in rids)
            assert got == total_tokens, (got, total_tokens)
            return (steps, admit_s[0], b.prefill_dispatches - prefills0,
                    b.decode_dispatches - decodes0,
                    b.decode_steps - dsteps0)
        finally:
            b._admit = orig_admit

    def measure(schedule, label, b=None):
        b = batcher if b is None else b
        run_continuous(b, schedule)                  # warm compiles
        t0 = time.perf_counter()
        steps, admit_s, prefills, decodes, dsteps = run_continuous(
            b, schedule)
        dt = time.perf_counter() - t0
        return {
            f"{label}_tps": round(total_tokens / dt, 1),
            f"{label}_steps": steps,
            # decode occupancy: each request's FIRST token comes from its
            # prefill dispatch, so a budget-n request uses n-1 decode
            # slot-steps; the denominator counts DECODE STEPS (== decode
            # dispatches without blocking), not loop iterations — a
            # bursty gap where all slots drained and the host just spins
            # toward the next arrival is not chip capacity
            f"{label}_occupancy": round(
                (total_tokens - n_req) / (dsteps * slots), 3),
            f"{label}_admission_frac": round(admit_s / dt, 4),
            f"{label}_prefill_dispatches": prefills,
            f"{label}_decode_dispatches": decodes,
            f"{label}_decode_steps": dsteps,
        }

    steady = [(0, r) for r in reqs]
    # waves of `slots` requests landing every (lo+hi)//2 steps — past the
    # minimum budget, so short-budget tenants have retired and freed
    # slots while long ones still decode: admission genuinely lands
    # mid-flight (each same-bucket wave is one batched prefill).  An
    # interval below `lo` would degenerate to the steady backlog: no
    # slot frees before every wave has queued.
    bursty = [((lo + hi) // 2 * (i // slots), r)
              for i, r in enumerate(reqs)]
    row = {"requests": n_req, "slots": slots, "budgets": f"{lo}-{hi}",
           "useful_tokens": total_tokens, "device": dev.device_kind}
    row.update(measure(steady, "steady"))
    row.update(measure(bursty, "bursty"))

    # ---- multi-step decode blocks: same steady backlog, but each
    # dispatch scans up to 16 decode steps (`decode_block_steps`) — the
    # amortization lever for per-dispatch latency: where a dispatch
    # costs as much as a step, this is where continuous batching's
    # wall-clock should close on static's lax.scan groups while keeping
    # slot-level admission (occupancy unchanged).
    blocked_b = ContinuousBatcher(cfg, params, max_batch=slots,
                                  decode_block_steps=16)
    row.update(measure(steady, "blocked", b=blocked_b))
    row["blocked_steps_per_dispatch"] = round(
        row["blocked_decode_steps"]
        / max(row["blocked_decode_dispatches"], 1), 2)

    # ---- speculative continuous batching: same slot machinery, each
    # step drafts per-slot from the request's own history and ONE verify
    # dispatch commits per-row accepted lengths.  Repetitive prompts
    # (the lookup regime: extraction/quoting/code) so acceptance fires;
    # the tokens-per-dispatch ratio is the win a chip realizes as
    # latency (decode is weight-read-bound, k+1 positions ride along).
    rng_s = np.random.default_rng(7)
    rep_reqs = [(np.tile(rng_s.integers(0, cfg.vocab_size,
                                        (4,)).astype(np.int32), 4),
                 int(rng_s.integers(lo, hi + 1))) for _ in range(n_req)]
    rep_tokens = sum(n for _, n in rep_reqs)

    def run_spec(spec_k):
        # warm and time the SAME instance (executables are per-instance
        # closures; a fresh batcher would recompile inside the window),
        # accounting by counter deltas
        b = ContinuousBatcher(cfg, params, max_batch=slots,
                              speculative_k=spec_k)
        for p, n in rep_reqs:
            b.submit(p, n)
        b.run()                                  # warm compiles
        d0, a0, p0 = (b.decode_dispatches, b.spec_accepted,
                      b.spec_proposed)
        rids = [b.submit(p, n) for p, n in rep_reqs]
        t0 = time.perf_counter()
        res = b.run()
        dt = time.perf_counter() - t0
        got = sum(len(res[r]) for r in rids)
        assert got == rep_tokens, (got, rep_tokens)
        return (dt, b.decode_dispatches - d0, b.spec_accepted - a0,
                b.spec_proposed - p0)

    dt_spec, disp_spec, acc, prop = run_spec(4)
    dt_nospec, _, _, _ = run_spec(None)
    row.update({
        "spec_tps": round(rep_tokens / dt_spec, 1),
        "nospec_tps_same_traffic": round(rep_tokens / dt_nospec, 1),
        "spec_speedup": round(dt_nospec / dt_spec, 3),
        # decode-only accounting, mirroring the occupancy formula:
        # each request's first token comes from its prefill dispatch
        "spec_tokens_per_dispatch": round(
            (rep_tokens - n_req) / max(disp_spec, 1), 3),
        "spec_acceptance": round(acc / max(prop, 1), 3),
        "spec_note": "tokens_per_dispatch is the transferable number: "
                     "where dispatch latency dominates (and on CPU, where "
                     "each forward is compute-bound) spec_speedup "
                     "understates what a local-dispatch TPU serving stack "
                     "gets — there the (k+1)-position verify rides the "
                     "same weight reads and acceptance converts to latency",
    })

    gen = jax.jit(greedy_generate, static_argnums=(0, 3))

    def run_static():
        # arrival-order groups of `slots`, padded to the group max budget
        got = 0
        for i in range(0, n_req, slots):
            group = reqs[i:i + slots]
            prompts = jnp.asarray(np.stack([p for p, _ in group]))
            n = max(b for _, b in group)
            out = gen(cfg, params, prompts, n)
            jax.device_get(out)
            got += sum(b for _, b in group)
        assert got == total_tokens

    run_static()                          # warm compiles per budget
    t0 = time.perf_counter()
    run_static()
    dt_stat = time.perf_counter() - t0

    # symmetric accounting — sequential device programs on the critical
    # path: static runs (1 group prefill + max_budget-1 decode steps) per
    # group = sum of group max budgets; continuous runs its decode steps
    # plus its (batched) prefill dispatches
    stat_dispatches = sum(max(b for _, b in reqs[i:i + slots])
                          for i in range(0, n_req, slots))
    static_tps = round(total_tokens / dt_stat, 1)
    n_groups = (n_req + slots - 1) // slots
    row.update({
        "static_tps": static_tps,
        # same decode-only accounting: each group's prefill emits the
        # first token, so decode steps = stat_dispatches - n_groups
        "static_occupancy": round(
            (total_tokens - n_req)
            / ((stat_dispatches - n_groups) * slots), 3),
        "speedup_steady": round(row["steady_tps"] / static_tps, 3),
        "speedup_bursty": round(row["bursty_tps"] / static_tps, 3),
        # host-dispatch distortion guard: continuous pays one host
        # round trip PER DISPATCH while static greedy runs each group
        # inside one lax.scan program — the dispatch counts separate
        # scheduling efficiency (what the batcher controls) from
        # dispatch latency (what the deployment controls)
        "dispatches_continuous": row["steady_steps"]
        + row["steady_prefill_dispatches"],
        "dispatches_static": stat_dispatches,
    })
    print("sweep serving:", json.dumps(row), flush=True)
    _write("serving_throughput.json", row)
    return row


# ---------------------------------------------------------------------------
# Stage: BERT-base SQuAD-style fine-tune through the L5 ML-pipeline path
# ---------------------------------------------------------------------------
def _bert_squad_train_fn(args, ctx):
    """Estimator ``train_fn`` for :func:`stage_bert_squad` — a BERT QA
    fine-tune step (start/end span logits) fed through the real L5 data
    plane (DataFrame -> queues -> DataFeed), timing steady-state
    examples/sec with the feed wait measured separately.  Module-level so
    multiprocessing 'spawn' can re-import it."""
    import json as _json
    import time as _time

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.models import Bert, BertConfig

    cfg = BertConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                     num_layers=args.layers, num_heads=args.heads,
                     intermediate_size=args.ffn,
                     max_position_embeddings=args.seq,
                     dtype=jnp.bfloat16, dropout_rate=0.0)

    class BertQA(nn.Module):
        @nn.compact
        def __call__(self, ids, mask):
            hidden = Bert(cfg)(ids, mask)
            # span head in f32: two logits per position (start, end)
            return nn.Dense(2, dtype=jnp.float32)(
                hidden.astype(jnp.float32))

    model = BertQA()
    tx = optax.adamw(3e-5)
    B, T = args.batch_size, args.seq
    ids0 = jnp.ones((B, T), jnp.int32)
    mask0 = jnp.ones((B, T), bool)
    params = model.init(jax.random.key(0), ids0, mask0)["params"]
    opt_state = tx.init(params)

    def loss_fn(p, ids, mask, start, end, w):
        logits = model.apply({"params": p}, ids, mask)
        ls = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :, 0], start)
        le = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :, 1], end)
        return ((ls + le) * w).sum() / jnp.maximum(2.0 * w.sum(), 1.0)

    def step_fn(p, o, ids, mask, start, end, w):
        loss, grads = jax.value_and_grad(loss_fn)(p, ids, mask,
                                                  start, end, w)
        upd, o = tx.update(grads, o, p)
        return optax.apply_updates(p, upd), o, loss

    step_jit = jax.jit(step_fn, donate_argnums=(0, 1))
    step = step_jit.lower(params, opt_state, ids0, mask0,
                          jnp.zeros((B,), jnp.int32),
                          jnp.zeros((B,), jnp.int32),
                          jnp.ones((B,), jnp.float32)).compile()
    cost = step.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))

    feed = ctx.get_data_feed(train_mode=True)
    warmup = 2
    n_steps = timed_steps = 0
    feed_s = t_timed0 = 0.0
    loss = None
    while not feed.should_stop():
        f0 = _time.perf_counter()
        batch = feed.next_batch_arrays(B, timeout=120)
        f1 = _time.perf_counter()
        if batch is None:
            break
        ids_c, start_c, end_c = batch
        n = len(ids_c)
        pad = B - n
        ids = np.zeros((B, T), np.int32)
        ids[:n] = ids_c            # already a stacked (n, seq) int array
        start = np.zeros((B,), np.int32)
        start[:n] = np.asarray(start_c, np.int32)
        end = np.zeros((B,), np.int32)
        end[:n] = np.asarray(end_c, np.int32)
        w = np.concatenate([np.ones(n, np.float32),
                            np.zeros(pad, np.float32)])
        params, opt_state, loss = step(params, opt_state,
                                       jnp.asarray(ids), mask0,
                                       jnp.asarray(start),
                                       jnp.asarray(end), jnp.asarray(w))
        n_steps += 1
        if n_steps == warmup:
            float(loss)                       # drain before the window
            t_timed0 = _time.perf_counter()
        elif n_steps > warmup:
            feed_s += f1 - f0
            timed_steps += 1
    if loss is not None:
        final_loss = float(loss)              # drains the last step
    dt_total = _time.perf_counter() - t_timed0 if timed_steps else 0.0

    if ctx.worker_num == 0 and timed_steps:
        dev = jax.devices()[0]
        peak = 197e12 if "v5 lite" in dev.device_kind.lower() else None
        dt = dt_total / timed_steps
        row = {"model": f"bert_L{args.layers}_h{args.hidden}_qa",
               "seq": T, "batch": B, "timed_steps": timed_steps,
               "examples_per_sec": round(B / dt, 2),
               "step_ms": round(dt * 1e3, 2),
               "feed_wait_frac": round(feed_s / dt_total, 4),
               "flops_per_step": flops,
               "mfu": round(flops / dt / peak, 4)
               if (flops and peak) else None,
               "loss": round(final_loss, 4),
               "path": "TFEstimator.fit (L5 pipeline, InputMode.SPARK)",
               "device": dev.device_kind}
        with open(args.result_path, "w") as f:
            _json.dump(row, f)


def stage_bert_squad() -> dict:
    """BASELINE.json configs[3]: BERT-base SQuAD-style fine-tune driven
    end-to-end through the ML-pipeline Estimator (the L5 path) — the
    DataFrame is fed through the queue data plane to a worker that runs
    the span-head train step on the chip.  The driver pins itself to CPU
    (the worker owns the chip); the measured row (examples/sec, MFU,
    feed-wait fraction) comes back through a result file because the
    estimator path deliberately has no tensor return channel."""
    import argparse as _ap
    import tempfile

    from tensorflowonspark_tpu import pipeline as _pl
    from tensorflowonspark_tpu.dataframe import DataFrame, Row

    if SMOKE:
        dims = dict(layers=2, hidden=64, heads=4, ffn=128, seq=32,
                    vocab=512, batch=4)
        n_rows = 40
    else:
        dims = dict(layers=12, hidden=768, heads=12, ffn=3072, seq=384,
                    vocab=30522, batch=24)
        n_rows = 24 * 14                       # 2 warmup + 12 timed steps
    # the chip belongs to the WORKER: the driver must not init the TPU
    # backend, and the worker must not inherit the driver's cpu pin
    worker_platform = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    worker_env = ({"JAX_PLATFORMS": worker_platform} if worker_platform
                  else {"JAX_PLATFORMS": ""})

    result_path = os.path.join(tempfile.mkdtemp(), "bert_squad_row.json")
    rng = __import__("numpy").random.default_rng(0)
    rows = [Row(input_ids=rng.integers(
                    0, dims["vocab"], (dims["seq"],)).astype(int).tolist(),
                start=int(rng.integers(0, dims["seq"])),
                end=int(rng.integers(0, dims["seq"])))
            for _ in range(n_rows)]
    df = DataFrame(rows, num_partitions=2)

    args = _ap.Namespace(result_path=result_path, **dims)
    est = (_pl.TFEstimator(_bert_squad_train_fn, args,
                           worker_env=worker_env)
           .setClusterSize(1)
           .setBatchSize(dims["batch"])
           .setEpochs(1))
    est.fit(df)

    with open(result_path) as f:
        row = json.load(f)
    print("sweep bert_squad:", json.dumps(row), flush=True)
    _write("bert_squad.json", row)
    return row


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------
def _select_stages(stages: list, only: str) -> list:
    """Filter + reorder stages to the ``--only`` list, IN ITS ORDER — a
    resume can put diagnosis stages (profile, loop-dispatch) first so the
    highest-value artifacts are captured first."""
    wanted = {s.strip() for s in only.split(",") if s.strip()}
    unknown = wanted - {name for name, _, _ in stages}
    if unknown:
        raise SystemExit(f"--only names not in the stage list: "
                         f"{sorted(unknown)}")
    by_name = {s[0]: s for s in stages}
    order = [s.strip() for s in only.split(",") if s.strip()]
    return [by_name[n] for n in dict.fromkeys(order)]


def _parse_compiler_options(spec: str) -> dict:
    """``k=v,k2=v2`` → dict with int/float/bool-looking values coerced to
    their Python types: PJRT option plumbing on some backends rejects a
    stringly-typed value for a typed option at compile time with an
    opaque error (ADVICE r5 item 3), so ``...=98304`` must arrive as an
    int and ``...=true`` as a bool.  Anything else stays a string."""
    def coerce(v: str):
        low = v.strip().lower()
        if low in ("true", "false"):
            return low == "true"
        try:
            return int(v)
        except ValueError:
            pass
        try:
            return float(v)
        except ValueError:
            return v

    out = {}
    for kv in spec.split(","):
        k, _, v = kv.partition("=")
        if not _ or not k.strip():
            raise ValueError(f"--compiler-options entry {kv!r} is not k=v")
        out[k.strip()] = coerce(v)
    return out


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--stage", default=None,
                   help="run one stage in-process (internal)")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--remat", action="store_true")
    p.add_argument("--stem", default="conv7", choices=("conv7", "s2d"))
    p.add_argument("--bn", default="f32", choices=("f32", "bf16"))
    p.add_argument("--attn", default="dense", choices=("dense", "flash"))
    p.add_argument("--model", default="124m", choices=("124m", "350m"),
                   help="gpt_train model size (350m: 1024/24L/16H)")
    p.add_argument("--loop", action="store_true",
                   help="time a single-dispatch jitted fori_loop window "
                        "(isolates host-dispatch overhead)")
    p.add_argument("--only", default=None,
                   help="comma-separated stage-name filter for resuming an "
                        "interrupted sweep (names as printed, e.g. "
                        "'resnet_b256_bnbf16,flash_sweep')")
    p.add_argument("--xla-flags", default=None,
                   help="extra XLA_FLAGS appended before any jax import "
                        "(pass as --xla-flags=--xla_... so argparse does "
                        "not eat the leading dashes) — "
                        "the MFU flag-attack lever (each stage is its own "
                        "subprocess, so flags cannot leak between stages)")
    p.add_argument("--xla-label", default="",
                   help="short row label for an --xla-flags experiment "
                        "(part of the resnet_sweep merge key)")
    p.add_argument("--compiler-options", default=None,
                   help="comma-separated key=value PJRT compile options "
                        "(e.g. xla_tpu_scoped_vmem_limit_kib=98304), "
                        "handed to the TPU compiler at compile time")
    args = p.parse_args()
    copts = None
    if args.compiler_options:
        copts = _parse_compiler_options(args.compiler_options)
        if not args.xla_label:
            # never let a flag-modified row collide with the baseline's
            # merge key (xla="") — that would silently overwrite the
            # control measurement with no provenance.  Label from the raw
            # strings so bools render as typed on the wire but stable in
            # the merge key.
            args.xla_label = "copts:" + ",".join(sorted(
                kv.strip() for kv in args.compiler_options.split(",")))

    if args.xla_flags:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " " + args.xla_flags).strip()

    if args.stage == "resnet":
        stage_resnet(args.batch, args.remat, args.stem, args.bn,
                     loop=args.loop, xla_label=args.xla_label,
                     compiler_options=copts)
        return
    if args.stage == "gpt_train":
        stage_gpt_train(args.batch, args.remat, args.attn, args.model)
        return
    if args.stage == "flash":
        stage_flash()
        return
    if args.stage == "decode":
        stage_decode()
        return
    if args.stage == "serving":
        stage_serving()
        return
    if args.stage == "bert_squad":
        stage_bert_squad()
        return

    t_start = time.monotonic()
    me = os.path.abspath(__file__)
    stages: list[tuple[str, list[str], int]] = [
        ("resnet_b256", [sys.executable, me, "--stage", "resnet",
                         "--batch", "256"], 900),
        ("resnet_b512", [sys.executable, me, "--stage", "resnet",
                         "--batch", "512"], 900),
        ("resnet_b1024", [sys.executable, me, "--stage", "resnet",
                          "--batch", "1024"], 900),
        ("resnet_b128", [sys.executable, me, "--stage", "resnet",
                         "--batch", "128"], 900),
        ("resnet_b256_s2d", [sys.executable, me, "--stage", "resnet",
                             "--batch", "256", "--stem", "s2d"], 900),
        ("resnet_b256_bnbf16", [sys.executable, me, "--stage", "resnet",
                                "--batch", "256", "--bn", "bf16"], 900),
        # stack the two r5 wins: bf16 BN (+28% at b256) on the best batch
        # (b128) and under the single-dispatch loop window
        ("resnet_b128_bnbf16", [sys.executable, me, "--stage", "resnet",
                                "--batch", "128", "--bn", "bf16"], 900),
        ("resnet_b128_bnbf16_loop",
         [sys.executable, me, "--stage", "resnet", "--batch", "128",
          "--bn", "bf16", "--loop"], 900),
        ("resnet_b256_bnbf16_loop",
         [sys.executable, me, "--stage", "resnet", "--batch", "256",
          "--bn", "bf16", "--loop"], 900),
        ("flash_sweep", [sys.executable, me, "--stage", "flash"], 1200),
        ("gpt_train_b8", [sys.executable, me, "--stage", "gpt_train",
                          "--batch", "8"], 900),
        ("gpt_train_b32_remat", [sys.executable, me, "--stage", "gpt_train",
                                 "--batch", "32", "--remat"], 900),
        ("gpt_train_b8_flash", [sys.executable, me, "--stage", "gpt_train",
                                "--batch", "8", "--attn", "flash"], 900),
        # MFU at 3x the parameters (flash+remat; no-remat 350m at b8
        # does not fit): keeps the 350m ledger row reproducible
        ("gpt_train_350m_b8_flash_remat",
         [sys.executable, me, "--stage", "gpt_train", "--batch", "8",
          "--attn", "flash", "--remat", "--model", "350m"], 1500),
        ("decode_matrix", [sys.executable, me, "--stage", "decode"], 1800),
        ("serving", [sys.executable, me, "--stage", "serving"], 1500),
        # bench_overlap writes its own overlap_<platform>.json; skipped in
        # smoke so a CPU smoke run can't clobber the committed CPU artifact
        *([] if SMOKE else [
            ("overlap_tpu", [sys.executable,
                             os.path.join(REPO, "scripts",
                                          "bench_overlap.py"),
                             "--batch-mb", "64"], 900)]),
        ("resnet_b1024_remat", [sys.executable, me, "--stage", "resnet",
                                "--batch", "1024", "--remat"], 900),
        # single-dispatch fori_loop window: isolates host-dispatch
        # overhead from what the chip itself sustains
        ("resnet_b256_loop", [sys.executable, me, "--stage", "resnet",
                              "--batch", "256", "--loop"], 900),
        ("resnet_b128_loop", [sys.executable, me, "--stage", "resnet",
                              "--batch", "128", "--loop"], 900),
        *([] if SMOKE else [
            ("embedding_native", [sys.executable,
                                  os.path.join(REPO, "scripts",
                                               "bench_embedding.py"),
                                  "--platform", "native", "--ep", "1"],
             900),
            # xprof capture of the b256 train step: the category/self-time
            # split that tells us where the ~0.24 MFU actually goes
            ("resnet_profile", [sys.executable,
                                os.path.join(REPO, "scripts",
                                             "profile_resnet.py"),
                                "--batch", "256"], 1200)]),
        # MFU flag attack (VERDICT r4 item 2): the roofline proved 3.08x
        # SOFTWARE headroom at b256; these A/B the compiler levers most
        # likely to move scheduling/fusion — each in its own subprocess so
        # XLA_FLAGS cannot leak.  Rows land beside the b256 control in
        # resnet_sweep.json keyed by the xla label.  TPU-only: the CPU
        # jaxlib build does not register xla_tpu_* flags (fatal "Unknown
        # flag"); both names verified present in this image's libtpu.so.
        *([] if SMOKE else [
            ("resnet_b256_vmem96",
             [sys.executable, me, "--stage", "resnet", "--batch", "256",
              "--compiler-options",
              "xla_tpu_scoped_vmem_limit_kib=98304",
              "--xla-label", "vmem96"], 900),
            ("resnet_b256_vmem128",
             [sys.executable, me, "--stage", "resnet", "--batch", "256",
              "--compiler-options",
              "xla_tpu_scoped_vmem_limit_kib=131072",
              "--xla-label", "vmem128"], 900),
            ("resnet_b256_nolhs",
             [sys.executable, me, "--stage", "resnet", "--batch", "256",
              "--compiler-options",
              "xla_tpu_enable_latency_hiding_scheduler=false",
              "--xla-label", "nolhs"], 900)]),
        # BASELINE configs[3]: the L5 pipeline path's first perf row —
        # deliberately LAST (VERDICT r4 item 9: only after the chip
        # queue drains)
        ("bert_squad", [sys.executable, me, "--stage", "bert_squad"],
         2400),
    ]
    if args.only:
        stages = _select_stages(stages, args.only)

    # no device probe here: a stage's own process is the first (and only)
    # one to touch the chip, and a stage that finds none fails by itself
    print("sweep: starting priority-ordered stages", flush=True)

    summary = {"started": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
               "stages": {}}
    consecutive_failures = 0
    for name, argv, budget in stages:
        t0 = time.monotonic()
        print(f"sweep: === {name} (budget {budget}s) ===", flush=True)
        try:
            r = subprocess.run(argv, timeout=budget, cwd=REPO,
                               capture_output=True, text=True)
            ok = r.returncode == 0
            tail = (r.stdout + r.stderr)[-1500:]
        except subprocess.TimeoutExpired:
            ok, tail = False, "TIMEOUT"
        dt = round(time.monotonic() - t0, 1)
        summary["stages"][name] = {"ok": ok, "seconds": dt}
        print(f"sweep: {name}: {'ok' if ok else 'FAILED'} in {dt}s",
              flush=True)
        if not ok:
            print(tail, flush=True)
            consecutive_failures += 1
            if consecutive_failures >= 2:
                print("sweep: two consecutive failures — aborting",
                      flush=True)
                break
        else:
            consecutive_failures = 0
    summary["total_seconds"] = round(time.monotonic() - t_start, 1)
    # a resumed sweep (--only) extends the prior run's stage record; a full
    # sweep starts a fresh summary
    prior_path = _path("sweep_summary.json")
    if args.only and os.path.exists(prior_path):
        with open(prior_path) as f:
            prior = json.load(f)
        prior_stages = prior.get("stages", {})
        prior_stages.update(summary["stages"])
        summary["stages"] = prior_stages
        summary["started"] = prior.get("started", summary["started"])
        # wall time accumulates across the original run and every resume
        summary["total_seconds"] = round(
            summary["total_seconds"] + prior.get("total_seconds", 0.0), 1)
    _write("sweep_summary.json", summary)


if __name__ == "__main__":
    main()
