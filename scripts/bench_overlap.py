"""Streamed-input overlap proof: does the data plane hide host->device cost?

VERDICT r2 weak #4: the streamed (InputMode.SPARK-equivalent) path had never
been measured where the framework, not the host-to-device link, is the
bound.  An in-process synthetic producer feeds host batches through
``data.device_prefetch`` into a compiled step, and we compare three regimes

  cached    — input already device-resident (pure-compute lower bound);
  naive     — synchronous ``device_put`` then step, no pipelining;
  prefetch  — ``device_prefetch(depth)`` (the framework's streaming path).

Reported: per-regime step time, the streamed/cached ratio for both paths,
and the overlap fraction

    overlap = (t_naive - t_prefetch) / (t_naive - t_cached)

1.0 = prefetch hides the entire h2d copy behind compute; 0 = no better than
synchronous.  Honest caveat: on CPU the "device" is host memory, so h2d is
a memcpy — the artifact records platform and measured copy bandwidth, and
the TPU row is filled in when a real-chip session runs this script
(SURVEY.md §3.2's divergence promise: chunked queues + async prefetch
instead of the reference's per-sample feed).

Usage: ``python scripts/bench_overlap.py [--batch-mb 32] [--steps 30]``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch-mb", type=float, default=32.0,
                   help="approx host bytes per batch")
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--depth", type=int, default=2)
    p.add_argument("--hidden", type=int, default=1024,
                   help="row width of the synthetic batch")
    p.add_argument("--layers", type=int, default=8,
                   help="scan iterations per step (scales compute vs copy; "
                   "elementwise body, so compute is bandwidth-bound and "
                   "stays comparable to the h2d copy on any backend)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.data import device_prefetch

    platform = jax.devices()[0].platform
    H = args.hidden
    rows = max(1, int(args.batch_mb * 1e6) // (H * 4))
    batch_bytes = rows * H * 4
    steps = args.steps

    # synthetic bandwidth-bound step: `layers` elementwise passes + reduce.
    # Elementwise (not matmul) keeps compute within a small factor of the
    # copy on every backend, so the overlap question is actually testable;
    # tanh defeats XLA constant-folding the whole scan into one pass.
    W = jnp.float32(1.0001)

    @jax.jit
    def step(x, W):
        def body(h, _):
            return jnp.tanh(h * W) + h, None
        h, _ = jax.lax.scan(body, x, None, length=args.layers)
        return jnp.sum(h)

    host_batches = [np.random.default_rng(i)
                    .standard_normal((rows, H)).astype(np.float32)
                    for i in range(min(4, steps))]  # cycle a few host buffers

    def producer():
        for i in range(steps):
            yield host_batches[i % len(host_batches)]

    # warmup / compile.  Every timed region ends in block_until_ready.

    xd = jax.device_put(host_batches[0])
    jax.block_until_ready(step(xd, W))

    # ---- cached: input device-resident ----
    t0 = time.perf_counter()
    out = None
    for _ in range(steps):
        out = step(xd, W)
    jax.block_until_ready(out)
    t_cached = (time.perf_counter() - t0) / steps

    # ---- naive: synchronous put-then-step ----
    # Drain the step output each iteration (the copy is serialized
    # transitively via the data dependency): without it, dispatch would overlap step k's compute with
    # step k+1's device_put, silently pipelining the "unpipelined" baseline.
    # The per-step drain cost is charged only to this loop and overlap rises
    # with t_naive, so it would BIAS THE OVERLAP FRACTION UP — measure the
    # drain's own cost on an already-complete array and subtract it.
    t0 = time.perf_counter()
    for x in producer():
        d = jax.device_put(x)
        out = step(d, W)
        jax.block_until_ready(out)
    t_naive_raw = (time.perf_counter() - t0) / steps
    t0 = time.perf_counter()
    for _ in range(steps):
        jax.block_until_ready(out)  # out is already complete: pure drain cost
    t_drain = (time.perf_counter() - t0) / steps
    t_naive = t_naive_raw - t_drain

    # ---- prefetch: the framework streaming path ----
    t0 = time.perf_counter()
    for d in device_prefetch(producer(), depth=args.depth):
        out = step(d, W)
    jax.block_until_ready(out)
    t_prefetch = (time.perf_counter() - t0) / steps

    # raw copy bandwidth for context (host fetch proves the copy landed).
    # The drain's own cost — nontrivial on CPU, where its reduction re-reads
    # the batch at the same DRAM bandwidth as the memcpy being measured —
    # must be measured ON THE BATCH SHAPE (t_drain above drained the scalar
    # step output; the batch-shaped reduction also jit-compiles on first
    # use), warmed and timed outside the copy window, then subtracted.
    d0 = jax.device_put(host_batches[0])
    jax.block_until_ready(d0)  # compile the batch-shape reduction
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(d0)  # already complete: pure batch-drain cost
    t_drain_batch = (time.perf_counter() - t0) / 5
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(host_batches[0]))
    copy_s = max(time.perf_counter() - t0 - t_drain_batch, 1e-9)
    h2d_MBps = batch_bytes / copy_s / 1e6

    denom = t_naive - t_cached
    overlap = (t_naive - t_prefetch) / denom if denom > 1e-9 else None
    result = {
        "platform": platform,
        "batch_bytes": batch_bytes,
        "steps": steps,
        "depth": args.depth,
        "t_cached_ms": t_cached * 1e3,
        "t_naive_ms": t_naive * 1e3,
        "t_naive_drain_correction_ms": t_drain * 1e3,
        "t_prefetch_ms": t_prefetch * 1e3,
        "streamed_vs_cached_naive": t_naive / t_cached,
        "streamed_vs_cached_prefetch": t_prefetch / t_cached,
        "overlap_fraction": overlap,
        "h2d_MBps": h2d_MBps,
        "note": "overlap=1 means device_prefetch hides the full h2d copy "
                "behind compute"
                + ("; CPU backend device_put is a synchronous memcpy on the "
                   "caller thread, so ~0 overlap here is the expected "
                   "backend property, not a framework failure — the TPU "
                   "run (async DMA) is the regime the claim is about"
                   if platform == "cpu" else ""),
    }
    os.makedirs(os.path.join(REPO, "bench_artifacts"), exist_ok=True)
    path = os.path.join(REPO, "bench_artifacts", f"overlap_{platform}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
