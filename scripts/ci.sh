#!/usr/bin/env bash
# CI entrypoint: the tfos-check static-analysis gate + the tier-1 test
# command from ROADMAP.md, as one script — what a pre-merge pipeline (or a
# developer wanting the full pre-push story) runs.
#
#   scripts/ci.sh               # analysis gate, then tier-1 tests
#   scripts/ci.sh --check       # analysis gate only (fast, no jax)
#   scripts/ci.sh --bench-smoke # analysis gate + bench_dataplane.py --smoke
#                               # (cross-host bulk transport A/B: schema,
#                               # byte-identical, kill-switch fallback
#                               # gates) + bench_batch.py on a tiny
#                               # 4-shard manifest (artifact schema + the
#                               # zero-reprocess/oracle resume gates) +
#                               # bench_serving.py --sharded --smoke (a
#                               # 2-device tp gang: oracle/zero-loss/schema
#                               # gates on the sharded serving plane) +
#                               # --prefix-heavy --smoke + --disagg --smoke
#                               # (disaggregated pools: handoff/oracle/
#                               # zero-prefill-on-decode gates) + --warm
#                               # + --spec --smoke (draft speculation +
#                               # AOT warm-up A/B) + tfos_warmcache.py
#                               # --check-warm (pre-baked cache must
#                               # compile 0 on the second sweep) +
#                               # --failover --smoke (chaos driver kill
#                               # healed by journal replay: zero-loss,
#                               # oracle-exact, mid-canary rollout
#                               # continuation gates) + bench_continual.py
#                               # --smoke (the standing train→eval→rollout
#                               # loop: a trainer-published quality
#                               # regression rejected at the offline gate
#                               # and never canaried, a good candidate
#                               # promoted fleet-wide, every served output
#                               # oracle-exact, zero loss)
#
# The analysis gate (docs/analysis.md) runs all eleven project rules —
# per-file (closure-capture, jit-purity, lock-discipline, resource-lifecycle,
# broad-except, metric-naming) plus the cross-file protocol/concurrency/drift
# set (wire-protocol, journal-kinds, blocking-under-lock, compat-discipline,
# doc-drift) — and the exports-drift check against the committed
# analysis_baseline.json ratchet (which ships EMPTY — new findings fail CI,
# they don't get grandfathered).  The gate also enforces a wall-clock budget:
# the full repo-wide run must finish in under 30 seconds.
# The tier-1 command mirrors ROADMAP.md exactly, including the timeout and
# the DOTS_PASSED accounting, so local runs and the driver agree.
set -uo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"

echo "== tfos-check gate =="
_check_t0=$(date +%s)
python scripts/tfos_check.py --stats
rc=$?
_check_secs=$(( $(date +%s) - _check_t0 ))
if [ $rc -ne 0 ]; then
    echo "tfos-check gate FAILED (rc=$rc)" >&2
    exit $rc
fi
echo "tfos-check wall clock: ${_check_secs}s (budget 30s)"
if [ "$_check_secs" -ge 30 ]; then
    echo "tfos-check gate FAILED: ${_check_secs}s exceeds the 30s budget" >&2
    exit 1
fi

if [ "${1:-}" = "--check" ]; then
    exit 0
fi

if [ "${1:-}" = "--bench-smoke" ]; then
    echo "== bench smoke (data plane / bulk transport) =="
    # loopback-simulated cross-host A/B: bulk transport vs per-message
    # pickle with shm pinned off.  Hard gates: artifact schema,
    # byte-identical round-trips, kill-switch fallback; the 1.5x speed
    # gate is advisory at smoke sizes.  Writes dataplane_smoke.json
    # (never the committed full artifact).
    JAX_PLATFORMS=cpu python scripts/bench_dataplane.py --smoke
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "dataplane bench smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    echo "== bench smoke (batch plane) =="
    # bench_batch.py --smoke validates its own artifact schema and fails
    # on the resume-correctness gates (zero reprocess, oracle-identical)
    JAX_PLATFORMS=cpu python scripts/bench_batch.py --smoke
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "bench smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    echo "== bench smoke (sharded serving plane) =="
    # a real 2-device tp gang behind the serving tier: fails itself on
    # the locked-vs-solo oracle, zero-loss, and artifact-schema gates
    JAX_PLATFORMS=cpu python scripts/bench_serving.py --sharded --smoke
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "sharded serving bench smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    echo "== bench smoke (paged-KV prefix cache) =="
    # paged decode + shared prefix cache behind a real replica: fails
    # itself on the locked-oracle, prefix-hit, and schema gates (speed
    # gates advisory in smoke)
    JAX_PLATFORMS=cpu python scripts/bench_serving.py --prefix-heavy --smoke
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "prefix serving bench smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    echo "== bench smoke (disaggregated prefill/decode) =="
    # specialized prefill/decode pools with KV-page handoff: fails
    # itself on the oracle, zero-loss, handoff, zero-prefill-on-decode
    # and artifact-schema gates; writes disagg_serving_smoke.json
    # (never the committed full artifact)
    JAX_PLATFORMS=cpu python scripts/bench_serving.py --disagg --smoke
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "disagg serving bench smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    echo "== bench smoke (warm-standby heal) =="
    # a chaos kill healed via warm-standby promotion + peer weight
    # clone: fails itself on the cold-spawn floor, zero-loss, oracle,
    # and artifact-schema gates; writes elasticity_smoke.json (never
    # the committed full artifact)
    JAX_PLATFORMS=cpu python scripts/bench_serving.py --warm
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "warm-standby heal bench smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    echo "== bench smoke (draft speculation + AOT) =="
    # draft-propose/target-verify A/B (oracle-exact, acceptance>0) and
    # the AOT warm-up A/B (pre-baked load arm must compile 0); writes
    # spec_serving_smoke.json (never the committed full artifact)
    JAX_PLATFORMS=cpu python scripts/bench_serving.py --spec --smoke
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "spec serving bench smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    echo "== bench smoke (AOT pre-bake CLI) =="
    # warm the cache twice into a throwaway dir: the second sweep must
    # load every serve-step executable and compile exactly 0
    _aotdir=$(mktemp -d)
    JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR="$_aotdir" \
        python scripts/tfos_warmcache.py --spec-k 4 --runs 2 --check-warm
    rc=$?
    rm -rf "$_aotdir"
    if [ $rc -ne 0 ]; then
        echo "warmcache smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    echo "== bench smoke (multi-model rollout) =="
    # 2 models on one tier (per-model oracle-exact routing + throughput
    # floor) and a forced canary regression auto-rolled back by the
    # metrics gate; writes rollout_serving_smoke.json (never the
    # committed full artifact)
    JAX_PLATFORMS=cpu python scripts/bench_rollout.py --smoke
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "rollout bench smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    echo "== bench smoke (driver failover) =="
    # a chaos 'kill driver' hard-crashes the control plane mid-stream;
    # resume_driver replays the write-ahead journal onto the surviving
    # replicas: fails itself on the zero-loss, oracle-exact, requeue,
    # and mid-canary rollout-continuation gates; writes
    # failover_smoke.json (never the committed full artifact)
    JAX_PLATFORMS=cpu python scripts/bench_serving.py --failover --smoke
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "driver failover bench smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    echo "== bench smoke (continual loop) =="
    # the standing train→eval→rollout pipeline end to end: a real
    # trainer publishes adapter candidates over the queue plane, the
    # batch plane's offline gate rejects the quality regression (never
    # canaried), the good candidate canaries and promotes fleet-wide.
    # Hard gates: outcomes exact, zero request loss, every served
    # output oracle-exact for a vetted version; writes
    # continual_smoke.json (never the committed full artifact)
    JAX_PLATFORMS=cpu python scripts/bench_continual.py --smoke
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "continual bench smoke FAILED (rc=$rc)" >&2
        exit $rc
    fi
    exit 0
fi

echo "== tier-1 tests (ROADMAP.md) =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit $rc
