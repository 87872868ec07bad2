"""Worker-side serving replica: ContinuousBatcher behind the node queues.

``serve_replica`` is a ``map_fun`` launched through the ordinary cluster
runtime (``TPUCluster.run`` / ``node.run``), so a serving replica gets the
whole worker substrate for free: the node's
:class:`~tensorflowonspark_tpu.queues.QueueServer` (with per-connection
shm negotiation) as its request/response plane, crash files + the
``error`` queue for failure propagation, and the
:class:`~tensorflowonspark_tpu.health.HeartbeatReporter` the driver's
:class:`~tensorflowonspark_tpu.health.ClusterMonitor` watches.

The loop interleaves request intake with decode — the same shape as
``examples/gpt/cluster_serving.py``'s worker, upgraded for the online
tier:

- intake is near-non-blocking while any slot is decoding (a blocking
  wait would stall every in-flight request) and blocks briefly when idle;
  with every slot busy it still sweeps the queue per step so CONTROL
  messages (a standby's weight-clone request, ``EndOfFeed``) never
  starve behind a full decode convoy — a gen request read during the
  sweep is carried to the next free slot;
- every committed token streams back immediately through the batcher's
  ``on_token`` hook, flushed as one ``{"event": "tok"}`` delta message
  per request per step (so a K-token block/speculative commit costs one
  message, not K);
- each decode step reports ``ctx.report_step(steps, phase="serving")`` —
  the driver's hang watchdog therefore covers the decode loop itself
  (a wedged device dispatch stops the step counter and trips
  ``step_timeout``/staleness exactly like a wedged training step), and
  chaos plans get their deterministic ``at_step`` trigger;
- response messages piggyback the batcher's
  :meth:`~tensorflowonspark_tpu.models.serving.ContinuousBatcher.load`
  total, giving the scheduler real queue depth for routing;
- an :class:`~tensorflowonspark_tpu.marker.EndOfFeed` marker (sent by
  ``cluster.shutdown`` — or per-replica by ``ServingCluster.
  retire_replica`` — exactly as for a training feed) stops intake; the
  loop drains its in-flight requests and exits cleanly;
- the loop runs under a :class:`~tensorflowonspark_tpu.preemption.
  PreemptionGuard`: a SIGTERM (spot/preemptible reclaim, or the chaos
  ``replace`` verb) is latched instead of killing the process mid-
  decode.  The replica flips into DRAIN mode — the heartbeat phase
  turns ``preempted`` (the driver's serving tier sees it, stops routing
  and spawns a replacement), intake keeps consuming whatever the
  dispatcher already queued, in-flight slots decode to completion, and
  the process exits 0.  Elastic membership turns the reclaim into a
  planned departure instead of a failure (docs/serving.md).

``args`` contract (all keys prefixed ``serve_``):

- ``serve_model_builder(args) -> (cfg, params)`` — a picklable callable
  (top-level function) building the model in the worker process;
- ``serve_max_batch`` (default 4), ``serve_eos_id`` (default None),
  ``serve_batcher_kwargs`` (extra ``ContinuousBatcher`` kwargs, e.g.
  ``decode_block_steps``/``speculative_k`` — note blocks trade intake
  latency for dispatch amortization);
- ``serve_idle_poll`` / ``serve_busy_poll`` — intake timeouts (secs): how
  long a sweep of the request queue waits with nothing seated / with a
  slot free beside seated rows and no decode step queued ahead.
"""

from __future__ import annotations

import collections
import logging
import queue as _queue
import statistics
import threading
import time as _time

from tensorflowonspark_tpu import metrics as _metrics
from tensorflowonspark_tpu import observability as _obs
from tensorflowonspark_tpu import tracing
from tensorflowonspark_tpu.marker import EndOfFeed, Marker
from tensorflowonspark_tpu.preemption import PreemptionGuard
from tensorflowonspark_tpu.serving.scheduler import (REQUEST_QUEUE,
                                                     RESPONSE_QUEUE)

logger = logging.getLogger(__name__)


def serving_aot_cache(args):
    """The tier's AOT serialized-executable cache (``serving/aot.py``),
    or None when not armed (``args["serve_aot_cache"]`` truthy —
    ``ServingCluster.run(aot_cache=True)``).  It lives beside the
    persistent XLA cache (``util.aot_cache_dir``: one fixed directory,
    placeable with ``JAX_COMPILATION_CACHE_DIR``) — shared by every
    replica, gang leader, standby, and the ``tfos_warmcache.py``
    pre-bake CLI of every run.  The gang's mesh spec is mixed into every
    entry key so differently-sharded tiers never collide in one
    directory."""
    if not args.get("serve_aot_cache"):
        return None
    from tensorflowonspark_tpu import util as _util
    from tensorflowonspark_tpu.serving.aot import AOTExecutableCache

    return AOTExecutableCache(_util.aot_cache_dir(),
                              extra_key=repr(args.get("serve_mesh")))


def build_draft_model(args):
    """Build this arg view's draft model (``serve_draft_builder``, or
    ``serve_draft_base_builder`` [+ ``serve_draft_adapter``] for a
    registry adapter version), device-put, wrapped in a
    :class:`~tensorflowonspark_tpu.models.serving.DraftModel` with the
    configured ``serve_draft_window``; None when no draft is configured.
    The draft is "just another model version": the same builder/adapter
    resolution the hot-swap and standby-promote paths use."""
    builder = args.get("serve_draft_builder")
    base = args.get("serve_draft_base_builder")
    if builder is None and base is None:
        return None
    import jax

    from tensorflowonspark_tpu.models.serving import DraftModel

    # the draft version's own serve_args overlay applies only while
    # BUILDING the draft (rollout.draft_overlay stashes it here) — a
    # draft's seed/knobs must never leak into the target's arg view
    draft_args = dict(args)
    draft_args.update(args.get("serve_draft_args") or {})
    if builder is not None:
        cfg, params = builder(draft_args)
    else:
        from tensorflowonspark_tpu.serving.rollout import \
            build_registered_model

        draft_args["serve_base_builder"] = base
        draft_args["serve_adapter"] = args.get("serve_draft_adapter")
        cfg, params = build_registered_model(draft_args)
    return DraftModel(cfg, jax.device_put(params),
                      window=int(args.get("serve_draft_window", 64)))


def arm_draft(batcher, args) -> None:
    """(Re)arm or clear the batcher's draft model from an arg view —
    boot, standby promotion, and hot swap all funnel here so target and
    draft can never go incoherent: a view without draft keys CLEARS any
    armed draft (swap-away invalidation), one with them builds and
    validates the new draft (typed errors from ``set_draft``, raised
    before any params move)."""
    draft = None
    if not getattr(batcher, "prefill_only", False) \
            and getattr(batcher, "spec_k", None) is not None:
        draft = build_draft_model(args)
    batcher.set_draft(draft)


def serve_clone_request(batcher, item: dict, ctx,
                        export_pages: bool = True) -> None:
    """Source side of peer weight cloning: ship this replica's params to
    the requester named in ``item`` (a promoted warm standby), off the
    decode thread so a bulk transfer never stalls in-flight streams.

    The transfer rides the requester's own node queue plane — a
    ``QueueClient`` to ``item["reply_addr"]`` (zero-copy shm negotiated
    automatically on a shared host) carrying one
    ``{"op": "standby", "event": "params"}`` message.  A paged batcher's
    message ALSO carries its shared prefix-cache pages
    (``ContinuousBatcher.export_prefix_cache``: content-hashed KV page
    data + chain keys over the page-transfer plane), so the promoted
    standby inherits this replica's prefix hits instead of starting
    cold.  The page gather runs HERE, on the serve-loop thread — the
    decode steps donate the cache buffer, so a concurrent off-thread
    gather would read freed device memory.  ``export_pages=False``
    (mesh-sharded gang tiers) skips the snapshot entirely: the sharded
    importer discards pages anyway, so gathering them would only stall
    serving and bloat the heal-critical transfer."""
    reg = _metrics.get_registry()
    m_clones = reg.counter(
        "tfos_replica_clones_served_total",
        "Peer weight-clone transfers served by this replica.")
    prefix_pages = None
    try:
        export = (getattr(batcher, "export_prefix_cache", None)
                  if export_pages else None)
        if export is not None:
            prefix_pages = export()
    # tfos: ignore[broad-except] — the weight clone is the heal-critical
    # payload; a failed prefix-page snapshot only costs post-heal TTFT
    except Exception:
        logger.exception("replica %d: prefix-cache export for clone "
                         "failed; shipping weights only", ctx.executor_id)

    def _send():
        import jax
        import numpy as np

        from tensorflowonspark_tpu.queues import QueueClient

        try:
            # host-gather ONE copy; the queue plane's pickle-5 path moves
            # it out-of-band (shm zero-copy when driver-negotiated)
            params = jax.tree.map(lambda x: np.asarray(x), batcher.params)
            cli = QueueClient(tuple(item["reply_addr"]),
                              item["reply_authkey"], timeout=60.0)
            try:
                cli.put(REQUEST_QUEUE,
                        {"op": "standby", "event": "params",
                         "params": params, "src": ctx.executor_id,
                         "prefix_pages": prefix_pages},
                        timeout=60)
            finally:
                cli.close()
            m_clones.inc()
            logger.info("replica %d served a weight clone to %s",
                        ctx.executor_id, item.get("reply_addr"))
        # tfos: ignore[broad-except] — a failed clone must not kill the
        # serving replica; the standby's clone timeout falls back to
        # checkpoint restore
        except Exception:
            logger.exception("replica %d: peer weight clone failed",
                             ctx.executor_id)

    threading.Thread(target=_send, name="serve-clone", daemon=True).start()


def resolve_version_params(args, item, base_cache: dict | None = None):
    """Build a model-version payload's parameter tree (the hot-swap
    message / the standby promote payload): the payload's ``builder`` —
    or ``base_builder`` + ``adapter`` delta for adapter versions — run
    over this worker's args with the version's ``serve_args`` overlaid
    (so a builder keying on e.g. ``seed`` sees the version's value).
    Returns ``(params, version_args)``; the caller loads the params and
    keeps ``version_args`` as its live arg view.

    ``base_cache``: the worker's PRISTINE-BASE cache.  Adapter swaps
    ship delta-only payloads, and re-applying a delta over the cached
    base beats rebuilding base+delta every swap.  The cache is only
    consulted when the payload's ``serve_args`` overlay carries no
    builder-visible knob (a non-``serve_``-prefixed key like ``seed``
    changes what the base builder returns) — otherwise the base is
    rebuilt.  Capped at one entry: a model's adapter versions share one
    base by construction (adapter-over-adapter is rejected at
    registration)."""
    version_args = dict(args)
    version_args.update(item.get("serve_args") or {})
    base = item.get("base_builder")
    if base is not None:
        from tensorflowonspark_tpu.serving.rollout import apply_adapter

        delta = item.get("adapter")
        version_args["serve_base_builder"] = base
        version_args["serve_adapter"] = delta
        overlay = item.get("serve_args") or {}
        cacheable = (base_cache is not None
                     and not any(not str(k).startswith("serve_")
                                 for k in overlay))
        key = (getattr(base, "__module__", None),
               getattr(base, "__qualname__", repr(base)))
        base_params = base_cache.get(key) if cacheable else None
        if base_params is None:
            _, base_params = base(version_args)
            if cacheable:
                base_cache.clear()
                base_cache[key] = base_params
        # apply_adapter never mutates the base leaves (delta'd paths get
        # fresh arrays), so the cached tree stays pristine
        params = (apply_adapter(base_params, delta) if delta
                  else base_params)
    else:
        builder = item.get("builder") or args["serve_model_builder"]
        _, params = builder(version_args)
    return params, version_args


def _donation_counter():
    """The one donation-counter family (both the export and import
    sites record into it; a single definition cannot drift)."""
    return _metrics.get_registry().counter(
        "tfos_replica_prefix_donations_total",
        "Cross-pool prefix-cache page donations by direction.",
        labelnames=("direction",))


def serve_prefix_donation(batcher, item, ctx) -> None:
    """Source side of cross-pool prefix-page donation: snapshot this
    (prefill) replica's shared prefix-cache pages and ship them straight
    to the requesting decode gang's queue plane (zero-copy/bulk
    negotiated like any tensor payload).  The gather runs HERE, on the
    serve-loop thread — decode steps donate the cache buffer, so an
    off-thread gather would read freed device memory; only the send is
    off-thread."""
    export = getattr(batcher, "export_prefix_cache", None)
    pages = None
    try:
        if export is not None:
            pages = export()
    # tfos: ignore[broad-except] — a donation is an optimization; a
    # failed snapshot must not kill the serving replica
    except Exception:
        logger.exception("replica %d: prefix-cache export for donation "
                         "failed", ctx.executor_id)
    if not pages:
        logger.info("replica %d: nothing to donate (empty prefix "
                    "cache)", ctx.executor_id)
        return
    m_donations = _donation_counter()

    def _send():
        from tensorflowonspark_tpu.queues import QueueClient

        try:
            cli = QueueClient(tuple(item["reply_addr"]),
                              item["reply_authkey"], timeout=60.0)
            try:
                cli.put(REQUEST_QUEUE,
                        {"op": "prefix", "event": "pages", "export": pages,
                         "src": ctx.executor_id}, timeout=60)
            finally:
                cli.close()
            m_donations.inc(direction="exported")
            logger.info("replica %d donated %d prefix page(s) to %s",
                        ctx.executor_id, pages["pages"],
                        item.get("reply_addr"))
        # tfos: ignore[broad-except] — the recipient may have died; the
        # donation just doesn't happen
        except Exception:
            logger.exception("replica %d: prefix-page donation failed",
                             ctx.executor_id)

    threading.Thread(target=_send, name="serve-prefix-donate",
                     daemon=True).start()


def serving_batcher_kwargs(args) -> dict:
    """The ``ContinuousBatcher`` kwargs for this worker's role:
    ``serve_batcher_kwargs`` overlaid with the role's entry from
    ``serve_disagg`` (``{"prefill_kwargs": ..., "decode_kwargs": ...}``)
    and — for a prefill-pool worker — ``prefill_only=True``.  Shared by
    the plain replica, the gang leader, and the warm standby, so every
    specialization builds the identical engine."""
    kwargs = dict(args.get("serve_batcher_kwargs") or {})
    role = args.get("serve_role")
    if role:
        kwargs.update(dict(
            (args.get("serve_disagg") or {}).get(f"{role}_kwargs") or {}))
    if role == "prefill":
        kwargs["prefill_only"] = True
    if (args.get("serve_draft_builder")
            or args.get("serve_draft_base_builder")) \
            and role != "prefill" and not kwargs.get("prefill_only") \
            and not (args.get("serve_disagg") and role is None) \
            and "speculative_k" not in kwargs \
            and "decode_block_steps" not in kwargs:
        # a configured draft implies speculation: arm the verify window
        # (serve_draft_k) unless the caller pinned either decode knob.
        # Role-less workers of a disagg tier (warm standbys) stay
        # unarmed — they may be promoted into a prefill pool, which
        # set_role refuses under decode-time knobs.
        kwargs["speculative_k"] = int(args.get("serve_draft_k", 4))
    return kwargs


class _SlowTurns:
    """The one fixed rule on the loop's phase clocks (docs/observability.md
    "Reading a stall"): a loop turn longer than 3x the median of the last
    32 turns and longer than 0.5 s (once 8 turns are known) increments
    ``tfos_replica_slow_steps_total{phase=<the phase that took most of
    it>}``, logs one WARNING with the turn's phase split, and emits
    ``replica_slow_step`` into ``trace_events.jsonl``.  A turn's length
    leaves out its ``idle`` seconds: waiting for requests is no stall.  A
    steady turn costs one read of each phase clock."""

    FACTOR, MIN_SECS, HISTORY, MIN_HISTORY = 3.0, 0.5, 32, 8

    def __init__(self, reg, spans, tracer, replica: int):
        self._phases = [name.rsplit("/", 1)[1]
                        for name in _obs.REPLICA_PHASES]
        self._clocks = [spans.seconds[name] for name in _obs.REPLICA_PHASES]
        self._idle = _obs.REPLICA_PHASES.index(_obs.SERVE_IDLE)
        self._m_slow = reg.counter(
            "tfos_replica_slow_steps_total",
            "Loop turns longer than 3x the median of the last 32 and "
            "longer than 0.5 s, by the phase that took most of the turn.",
            labelnames=("phase",))
        self._tracer, self._replica = tracer, replica
        self._recent: collections.deque = collections.deque(
            maxlen=self.HISTORY)
        self._last = [c.value() for c in self._clocks]
        self._t = _time.perf_counter()

    def turn_done(self, step: int) -> None:
        now = _time.perf_counter()
        vals = [c.value() for c in self._clocks]
        if vals[0] is None:         # TFOS_NO_TELEMETRY=1: no clocks
            return
        split = [v - p for v, p in zip(vals, self._last)]
        turn = now - self._t - split[self._idle]
        self._last, self._t = vals, now
        recent = self._recent
        median = statistics.median(recent) \
            if turn > self.MIN_SECS and len(recent) >= self.MIN_HISTORY \
            else None
        if median is not None and turn > self.FACTOR * median:
            split[self._idle] = 0.0
            phase = self._phases[split.index(max(split))]
            by_phase = {p: round(s, 6)
                        for p, s in zip(self._phases, split) if s > 0}
            self._m_slow.inc(phase=phase)
            logger.warning(
                "replica %d: slow loop turn at step %d: %.3f s against a "
                "median of %.3f s; most of it in %s; phase split %s",
                self._replica, step, turn, median, phase, by_phase)
            self._tracer.event("replica_slow_step", None,
                               replica=self._replica, step=step,
                               seconds=turn, phase=phase, split=by_phase)
        recent.append(turn)


def serve_replica(args, ctx) -> None:
    """The serving-tier ``map_fun``: serve generate requests until the
    driver sends ``EndOfFeed``."""
    # jax (and the model stack) import inside the worker process only —
    # the harness contract is that no jax import happens before map_fun
    # (node.run already placed the persistent compile cache via the env)
    from tensorflowonspark_tpu.models.serving import ContinuousBatcher

    cfg, params = args["serve_model_builder"](args)
    batcher = ContinuousBatcher(
        cfg, params,
        max_batch=int(args.get("serve_max_batch", 4)),
        eos_id=args.get("serve_eos_id"),
        aot_cache=serving_aot_cache(args),
        **serving_batcher_kwargs(args))
    arm_draft(batcher, args)
    run_serve_loop(args, ctx, batcher, role=args.get("serve_role"))


def run_serve_loop(args, ctx, batcher, *, step_hook=None,
                   label: str = "replica", role: str | None = None,
                   base_args: dict | None = None) -> None:
    """THE serving loop (module docstring): intake ⇄ step interleave over
    the node queue plane until ``EndOfFeed`` / a drained preemption.

    Shared by :func:`serve_replica` (a single-process replica) and the
    mesh-sharded gang leader (:mod:`~tensorflowonspark_tpu.serving.
    sharded`), which passes ``step_hook(steps, load)`` — called once per
    decode step, after the step's deltas are flushed — to run the gang's
    step barrier; a hook exception (a lost shard) propagates out exactly
    like a device failure, crashing the worker so the driver classifies
    the whole gang dead.

    ``role`` specializes the loop for a disaggregated pool
    (docs/serving.md "Disaggregated prefill/decode"): every response
    message carries the role so the scheduler can audit routing;
    ``"prefill"`` flushes each admitted request's exported session as a
    ``{"event": "handoff"}`` message (the batcher never decode-steps
    it); ``"decode"`` accepts ``{"op": "adopt"}`` intake items and seats
    them via ``batcher.adopt_session`` — a corrupt/raced transfer's
    ``ValueError`` bounces back as a typed error without touching the
    engine.

    ``base_args`` (a promoted standby passes its PRISTINE boot args
    while ``args`` carries the promoted version's serve_args overlay)
    is the base a later hot swap's version_args build from — so a
    rollback away from the promoted version fully sheds its knobs."""
    mgr = ctx.mgr
    if mgr is None:
        raise RuntimeError("the serving loop needs the node queue server "
                           "(InputMode.SPARK)")
    idle_poll = float(args.get("serve_idle_poll", 0.5))
    busy_poll = float(args.get("serve_busy_poll", 0.005))
    # how long a preempted replica keeps polling intake after its queue
    # looks empty: covers the window before the driver notices the
    # 'preempted' heartbeat phase and stops routing (heartbeat interval
    # + monitor poll), so a request dispatched into that window is still
    # served rather than stranded
    preempt_grace = float(args.get("serve_preempt_grace", 2.0))
    #: artificial per-step latency (benches/chaos: a deterministic
    #: "slow version" for rollout-gate testing); a model swap's
    #: serve_args overlay can change it live
    step_delay = float(args.get("serve_step_delay", 0.0))

    deltas: dict[int, list[int]] = {}   # batcher rid -> tokens this step
    carry = None   # gen request read during a full-slots control sweep
    pending_swap = None   # a model hot-swap awaiting an idle batcher

    def on_token(brid: int, tok: int) -> None:
        deltas.setdefault(brid, []).append(int(tok))

    # batcher rid -> (scheduler rid, trace id, wall clock of its intake)
    rid_map: dict[int, tuple[int, str | None, float | None]] = {}
    first_sent: set[int] = set()        # batcher rids past first delta
    stopping = False
    steps = 0
    served = 0

    # telemetry: this worker process's registry rides the heartbeat
    # payload back to the driver (health.HeartbeatReporter); spans land
    # in <working_dir>/trace_events.jsonl (tracing.py)
    reg = _metrics.get_registry()
    # the two stamps the driver's hop clocks read (observability.hop_clocks):
    # ``t_put`` on every tok/done message, ``t_in`` on a request's first
    put_stamp = (lambda: {"t_put": _time.time()}) if reg.enabled else dict
    m_steps = reg.counter("tfos_replica_steps_total",
                          "Decode steps executed by this replica.")
    m_tokens = reg.counter("tfos_replica_tokens_total",
                           "Tokens streamed by this replica.")
    m_served = reg.counter("tfos_replica_requests_total",
                           "Requests served to completion by this replica.")
    g_load = reg.gauge("tfos_replica_load_count",
                       "Batcher queue depth (active+pending+reserved).")
    # engine counters the batcher already keeps, surfaced as heartbeat-
    # carried metrics: tokens-per-dispatch (steps+tokens over dispatches)
    # is the amortization ratio, spec proposed/accepted the speculation
    # win, free pages + prefix outcomes the paged-KV story
    m_disp = reg.counter(
        "tfos_replica_decode_dispatches_total",
        "Decode DISPATCHES (a scanned block or fused verify counts "
        "once; compare tfos_replica_steps_total for the ratio).")
    m_prefill = reg.counter(
        "tfos_replica_prefill_dispatches_total",
        "Prefill dispatches (a batched admission group counts once).")
    m_spec = reg.counter(
        "tfos_replica_spec_tokens_total",
        "Speculative tokens by outcome (proposed/accepted).",
        labelnames=("outcome",))
    h_accept = reg.histogram(
        "tfos_replica_spec_accept_len_count",
        "Accepted draft length per drafted row per verify dispatch — "
        "the tokens-per-dispatch distribution behind the "
        "proposed/accepted totals (each commit is accept_len + 1 bonus "
        "token from one dispatch).")
    g_pages = reg.gauge(
        "tfos_replica_kv_pages_free_count",
        "Allocatable KV pages (free + evictable cached) in the paged "
        "pool.")
    m_prefix = reg.counter(
        "tfos_replica_prefix_cache_requests_total",
        "Prefix-cache admission outcomes (hit/miss/partial).",
        labelnames=("outcome",))
    m_sessions = reg.counter(
        "tfos_replica_sessions_total",
        "KV-page handoff sessions by direction (exported by a prefill "
        "pool / adopted by a decode pool).", labelnames=("direction",))
    m_aot = reg.counter(
        "tfos_replica_aot_resolves_total",
        "AOT serve-step executable resolutions by outcome (load = disk "
        "hit, compile = miss paid with a compile, error = corrupt "
        "entry or failed write, each degraded to a compile).",
        labelnames=("outcome",))
    # expert layers and per-row recurrent state (models/moe.py, models/gpt.py
    # ShortConv and PowerRetention): what the batcher read with its tokens
    # or counted on the host, as deltas; the paged decode step's page
    # accounting and its steps run ahead likewise
    m_engine = {
        "decode_ahead_dispatches": reg.counter(
            "tfos_replica_decode_ahead_dispatches_total",
            "Plain decode steps dispatched before the running step's "
            "tokens were fetched (ContinuousBatcher._stands_down): over "
            "tfos_replica_decode_dispatches_total, the share of decode "
            "steps whose host turn hid behind the device."),
        "expert_assignments": reg.counter(
            "tfos_replica_expert_assignments_total",
            "Expert assignments made (rows x experts per token), summed "
            "over expert layers and over decode and prefill dispatches."),
        "expert_peak_assignments": reg.counter(
            "tfos_replica_expert_peak_assignments_total",
            "The busiest expert's assignments, summed likewise: x "
            "experts / assignments is the load's unevenness (1 = even)."),
        "expert_assignments_held": reg.counter(
            "tfos_replica_expert_assignments_held_total",
            "Expert assignments that fell to experts this replica holds "
            "(GPTConfig.experts_held; all of them unless told), summed "
            "likewise: over tfos_replica_expert_assignments_total the "
            "share of the router's choices computed here."),
        "experts_touched": reg.counter(
            "tfos_replica_experts_touched_total",
            "Experts that got at least one assignment, summed likewise: "
            "the expert weights a dispatch had to read."),
        "prefill_experts_touched": reg.counter(
            "tfos_replica_prefill_experts_touched_total",
            "The part of tfos_replica_experts_touched_total that prefill "
            "dispatches account for: the rest is the decode steps'."),
        "state_rows_seated": reg.counter(
            "tfos_replica_state_rows_seated_total",
            "Rows whose recurrent state an admission wrote (configurations "
            "with conv, retention or mamba2 layers)."),
        "carried_prefills": reg.counter(
            "tfos_replica_carried_prefills_total",
            "Prefill dispatches that brought recurrent state with them (a "
            "chunked admission's final call): 1 - this over "
            "tfos_replica_prefill_dispatches_total is the share of "
            "prefills whose retention layers had no state to query "
            "(ops.power_retention.retention_chunked)."),
        "state_bytes_moved": reg.counter(
            "tfos_replica_state_bytes_moved_total",
            "Bytes of per-row recurrent state the decode steps read and "
            "wrote (models.gpt.state_step_bytes of the whole batch per "
            "step: a retention state once each through the kernel, a "
            "third time through the jax.numpy arithmetic; a mamba2 "
            "layer's SSM state and convolution tail once each)."),
        "grouped_matmul_calls": reg.counter(
            "tfos_replica_grouped_matmul_calls_total",
            "tfos_grouped_matmul kernel calls (ops.grouped_matmul) the "
            "dispatched decode and prefill programs held, host arithmetic "
            "per dispatch (models.moe.grouped_matmul_calls): over decode "
            "+ prefill dispatches the calls per dispatch, 0 says the "
            "expert layers' ragged_dot path ran, or the model has none."),
        "kv_pages_read": reg.counter(
            "tfos_replica_kv_pages_read_total",
            "KV pages the seated rows' lengths cover, summed over decode "
            "dispatches."),
        "kv_pages_viewed": reg.counter(
            "tfos_replica_kv_pages_viewed_total",
            "KV pages of the seated rows' whole views, summed over the "
            "decode dispatches that attended over the pages in place "
            "(ops.paged_attention): read / viewed is the share the kernel "
            "touches, 0 says the whole-view gather ran.")}
    m_standdowns = reg.counter(
        "tfos_replica_decode_ahead_standdowns_total",
        "Decode dispatches that had no plain step dispatched ahead behind "
        "them, by what stood in its way (models.serving.STANDDOWNS): with "
        "tfos_replica_decode_ahead_dispatches_total they sum to "
        "tfos_replica_decode_dispatches_total.", labelnames=("why",))
    last = {"decode_dispatches": 0, "prefill_dispatches": 0,
            **dict.fromkeys(m_engine, 0),
            "spec_proposed": 0, "spec_accepted": 0,
            "sessions_exported": 0, "sessions_adopted": 0,
            "standdowns": {},
            "hit": 0, "miss": 0, "partial": 0,
            "aot_loads": 0, "aot_compiles": 0, "aot_errors": 0}

    def publish_engine_counters() -> None:
        """Move the batcher's lifetime counters into the registry as
        deltas (the registry is cumulative per process already)."""
        for attr, inc in (("decode_dispatches", m_disp.inc),
                          ("prefill_dispatches", m_prefill.inc),
                          *((a, m.inc) for a, m in m_engine.items())):
            cur = getattr(batcher, attr, 0)
            if cur > last[attr]:
                inc(cur - last[attr])
                last[attr] = cur
        for attr, outcome in (("spec_proposed", "proposed"),
                              ("spec_accepted", "accepted")):
            cur = getattr(batcher, attr, 0)
            if cur > last[attr]:
                m_spec.inc(cur - last[attr], outcome=outcome)
                last[attr] = cur
        for attr, direction in (("sessions_exported", "exported"),
                                ("sessions_adopted", "adopted")):
            cur = getattr(batcher, attr, 0)
            if cur > last[attr]:
                m_sessions.inc(cur - last[attr], direction=direction)
                last[attr] = cur
        for why, cur in getattr(batcher, "decode_ahead_standdowns",
                                {}).items():
            before = last["standdowns"].get(why, 0)
            if cur > before:
                m_standdowns.inc(cur - before, why=why)
                last["standdowns"][why] = cur
        take_lens = getattr(batcher, "take_spec_accept_lens", None)
        if take_lens is not None:
            for n in take_lens():
                h_accept.record(n)
        aot = getattr(batcher, "_aot", None)
        if aot is not None:
            for attr, outcome in (("loads", "load"),
                                  ("compiles", "compile"),
                                  ("errors", "error")):
                cur = getattr(aot, attr, 0)
                if cur > last[f"aot_{attr}"]:
                    m_aot.inc(cur - last[f"aot_{attr}"], outcome=outcome)
                    last[f"aot_{attr}"] = cur
        prefix_stats = getattr(batcher, "prefix_stats", None)
        if prefix_stats is not None:
            stats = prefix_stats()
            for outcome in ("hit", "miss", "partial"):
                if stats[outcome] > last[outcome]:
                    m_prefix.inc(stats[outcome] - last[outcome],
                                 outcome=outcome)
                    last[outcome] = stats[outcome]

    tracer = tracing.tracer_for(ctx.working_dir)
    #: role piggyback on every response message — the scheduler audits
    #: that a pool member really serves its registered specialization
    role_extra = {} if role is None else {"role": role}

    def busy() -> bool:
        return batcher.load()["total"] > 0

    # the loop thread's phase spans (docs/observability.md "Profiler
    # spans"): leaves that partition a loop turn; the batcher's own
    # (admit, prefill/decode dispatch and fetch, emit) fall inside
    # batcher.step() and share the clocks' family
    spans = _obs.PhaseSpans()
    slow_turns = _SlowTurns(reg, spans, tracer, ctx.executor_id)
    turn_marks = _obs.step_marks(_obs.SERVE_STEP)

    def next_item(free: bool, draining: bool):
        """One read of the request queue.  With every slot busy the wait
        is near zero (a control sweep), and so it is while the batcher
        holds a step queued ahead: the device has its work, and a wait
        longer than that step would leave it idle after it.  With
        nothing seated the replica is waiting for requests, which is the
        ``idle`` phase."""
        seated = busy()
        timeout = 0.001 if not free or getattr(
            batcher, "step_queued", False) else (
                busy_poll if seated else (0.05 if draining else idle_poll))
        if seated:
            return mgr.queue_get(REQUEST_QUEUE, timeout=timeout)
        with spans(_obs.SERVE_IDLE):
            return mgr.queue_get(REQUEST_QUEUE, timeout=timeout)

    swap_base = base_args if base_args is not None else args
    #: pristine-base cache for delta-only adapter swaps (see
    #: resolve_version_params) — lives for the serve loop's lifetime
    swap_base_cache: dict = {}

    def apply_model_swap(item: dict, cur_delay: float):
        """Apply a drained hot swap (docs/serving.md "Multi-model
        serving"): params from a peer clone (the version already serves
        elsewhere) or the payload's builder/adapter; the already-
        compiled batcher re-arms via ``load_params`` (shape-validated —
        an incompatible version bounces back typed, the OLD params keep
        serving).  Returns the new per-step delay, ``cur_delay`` on a
        failed swap, or None when an EndOfFeed interrupted the clone
        wait (tier shutdown)."""
        import jax

        old_params = batcher.params
        old_draft = getattr(batcher, "_draft_model", None)
        params = None
        version_args = dict(swap_base)
        version_args.update(item.get("serve_args") or {})
        peer = item.get("peer")
        # adapter payloads are DELTA-ONLY: re-applying the delta over the
        # pristine base (cached locally) always beats cloning full params
        # from a peer, so the peer hint is ignored for them
        if peer is not None and item.get("base_builder") is None:
            from tensorflowonspark_tpu.serving.standby import (
                _STOP, _clone_from_peer)

            got = _clone_from_peer(ctx, mgr, peer, timeout=float(
                args.get("serve_clone_timeout", 60.0)))
            if got is _STOP:
                return None
            if got is not None:
                params = got["params"]
        try:
            if params is None:
                params, version_args = resolve_version_params(
                    swap_base, item, base_cache=swap_base_cache)
            # draft coherence BEFORE the params move: the new version's
            # draft arms (or a version without one clears the old draft)
            # while the old target still serves — a bad draft payload
            # bounces typed below with the old (params, draft) pair
            # fully intact, and the swapped target can never decode
            # against a stale draft (which would only cost acceptance,
            # but would lie about the version's measured speedup)
            arm_draft(batcher, version_args)
            batcher.unload_params()
            batcher.load_params(jax.device_put(params))
        # tfos: ignore[broad-except] — a bad version payload must bounce
        # back typed, not kill a serving replica; the old params are
        # restored so the gang keeps serving its registered version
        except Exception as e:
            if batcher.params is None:
                batcher.load_params(old_params)
            if getattr(batcher, "_draft_model", old_draft) is not old_draft:
                batcher.set_draft(old_draft)
            logger.exception("replica %d: model swap to %s@%s failed",
                             ctx.executor_id, item.get("model"),
                             item.get("version"))
            mgr.queue_put(RESPONSE_QUEUE,
                          {"rid": None, "event": "model_swap_failed",
                           "error": f"{type(e).__name__}: {e}",
                           "swap_token": item.get("swap_token"),
                           "load": 0, **role_extra})
            return cur_delay
        mgr.queue_put(RESPONSE_QUEUE,
                      {"rid": None, "event": "model_swapped",
                       "model": item.get("model"),
                       "version": item.get("version"),
                       "swap_token": item.get("swap_token"), "load": 0,
                       **role_extra})
        logger.info("replica %d hot-swapped to model %s@%s",
                    ctx.executor_id, item.get("model"),
                    item.get("version"))
        return float(version_args.get("serve_step_delay", 0.0))

    served_model = args.get("serve_model")
    logger.info("%s %d serving (max_batch=%d%s)", label, ctx.executor_id,
                batcher.max_batch,
                "" if not served_model
                else f", model {served_model[0]}@{served_model[1]}")
    draining = False
    drain_started = 0.0
    guard = PreemptionGuard()
    with guard, turn_marks:
        turn_marks.next(steps)
        while True:
            with spans(_obs.SERVE_INTAKE):
                if guard.preempted and not draining:
                    draining = True
                    drain_started = _time.monotonic()
                    logger.warning(
                        "replica %d preempted: draining in-flight work, then "
                        "exiting cleanly (grace poll %.1fs)", ctx.executor_id,
                        preempt_grace)
                    tracer.event("replica_preempted", None,
                                 replica=ctx.executor_id,
                                 inflight=batcher.load()["total"])
                if pending_swap is not None and not stopping \
                        and carry is None and not busy():
                    # the driver drained this gang first, so the batcher is
                    # idle here; a swap racing early-routed work simply
                    # waits for the next idle step
                    item, pending_swap = pending_swap, None
                    got = apply_model_swap(item, step_delay)
                    if got is None:     # EndOfFeed landed mid-clone
                        stopping = True
                        break
                    step_delay = got
                queue_idle = False
                while not stopping:
                    free = batcher.has_free_slot()
                    if carry is not None:
                        if not free:
                            break
                        item, carry = carry, None
                    else:
                        try:
                            # even with every slot busy, sweep the queue with
                            # a near-zero timeout: CONTROL messages (clone,
                            # EndOfFeed) must not starve behind a full batch
                            # — a promoted standby's weight clone would
                            # otherwise wait out the whole decode convoy
                            item = next_item(free, draining)
                        except (_queue.Empty, TimeoutError):
                            queue_idle = True
                            break
                        if not free and isinstance(item, dict) \
                                and item.get("op") == "gen":
                            # a gen request read during the control sweep:
                            # hold it for the next free slot (it would have
                            # sat at the queue head anyway)
                            carry = item
                            break
                    if isinstance(item, EndOfFeed):
                        stopping = True
                        break
                    if isinstance(item, Marker):
                        continue
                    if isinstance(item, dict) and item.get("op") == "clone":
                        # a promoted standby asks for this replica's weights
                        serve_clone_request(
                            batcher, item, ctx,
                            export_pages=not args.get("serve_mesh"))
                        continue
                    if isinstance(item, dict) and item.get("op") == "model":
                        ev = item.get("event")
                        if ev == "swap":
                            # a hot swap: applied at the loop top once the
                            # batcher is idle (the driver drained first, so
                            # normally it already is)
                            pending_swap = item
                        elif ev == "cancel":
                            # the driver's swap call gave up (ack timeout):
                            # drop a swap not yet applied.  One already
                            # applied (or mid-apply) acks late instead, and
                            # the scheduler relabels on the late ack — the
                            # routing label always tracks the served
                            # version.
                            pending_swap = None
                        continue
                    if isinstance(item, dict) and item.get("op") == "prefix":
                        ev = item.get("event")
                        if ev == "export":
                            # a decode gang asks for this pool's prefix
                            # pages (cross-pool donation)
                            serve_prefix_donation(batcher, item, ctx)
                        elif ev == "pages":
                            # a donated page set arrives: import as cached,
                            # refcount-0, evictable pages — matchable by
                            # the very next admission/adopt
                            try:
                                importer = getattr(batcher,
                                                   "import_prefix_cache",
                                                   None)
                                n = (0 if importer is None
                                     else importer(item.get("export")))
                                if n:
                                    _donation_counter().inc(
                                        n, direction="imported")
                                logger.info(
                                    "replica %d imported %d donated prefix "
                                    "page(s) from %s", ctx.executor_id, n,
                                    item.get("src"))
                            # tfos: ignore[broad-except] — a corrupt/
                            # mismatched donation is rejected by the hash/
                            # layout checks; the replica serves on
                            except Exception:
                                logger.exception(
                                    "replica %d: donated prefix-page import "
                                    "failed", ctx.executor_id)
                        continue
                    if isinstance(item, dict) and item.get("op") == "adopt":
                        # a handed-off session: seat it without re-prefilling.
                        # adopt_session verifies layout + per-page content
                        # hashes HERE — a corrupt or raced transfer raises
                        # before any device write and bounces back typed,
                        # the engine stays healthy
                        try:
                            brid = batcher.adopt_session(item["session"],
                                                         on_token=on_token)
                        except ValueError as e:
                            mgr.queue_put(RESPONSE_QUEUE,
                                          {"rid": item.get("rid"),
                                           "event": "error", "error": str(e),
                                           **role_extra})
                            continue
                        rid_map[brid] = (item["rid"], item.get("trace"), None)
                        tracer.event(
                            "replica_adopt", item.get("trace"),
                            rid=item["rid"], replica=ctx.executor_id,
                            pages=int(item["session"].get("pages", 0)))
                        continue
                    if not (isinstance(item, dict)
                            and item.get("op") == "gen"):
                        logger.warning(
                            "replica %d: ignoring non-request item %r",
                            ctx.executor_id, type(item))
                        continue
                    try:
                        brid = batcher.submit(
                            item["prompt"], int(item["max_new_tokens"]),
                            temperature=float(item.get("temperature", 0.0)),
                            top_p=float(item.get("top_p", 1.0)),
                            seed=int(item.get("seed", 0)), on_token=on_token)
                    except ValueError as e:
                        # a malformed request must not kill the replica; bounce
                        # the typed error back to the scheduler
                        mgr.queue_put(RESPONSE_QUEUE,
                                      {"rid": item.get("rid"),
                                       "event": "error",
                                       "error": str(e), **role_extra})
                        continue
                    rid_map[brid] = (item["rid"], item.get("trace"),
                                     _time.time() if reg.enabled else None)
                    tracer.event("replica_intake", item.get("trace"),
                                 rid=item["rid"], replica=ctx.executor_id,
                                 prompt_tokens=len(item["prompt"]))
            if not busy():
                if stopping:
                    break
                if draining and queue_idle and (
                        _time.monotonic() - drain_started >= preempt_grace):
                    break   # grace-window drain complete: exit cleanly
                continue
            done = batcher.step()
            if step_delay:
                # a stand-in for a slow model's device step: the loop
                # waits on it as it waits on the device
                with spans(_obs.BATCHER_DECODE_FETCH):
                    _time.sleep(step_delay)
            steps += 1
            with spans(_obs.SERVE_PUBLISH):
                # serving-phase heartbeat: arms the hang watchdog on the
                # decode loop and gives chaos its at_step trigger.  A
                # draining replica reports phase 'preempted' — every step
                # would otherwise clobber the preemption flip back to
                # 'serving' and the driver would never see the grace window
                # (it drains-and-replaces off this).  guard.preempted, not
                # just `draining`: a SIGTERM landing MID-iteration (after
                # the loop-top check) must not have this very step publish
                # 'serving' over note_preempted's flip — if the batcher
                # idles right after, no later step would ever correct it
                ctx.report_step(
                    steps, phase="preempted"
                    if (draining or guard.preempted) else "serving")
                ld = batcher.load()
                load = ld["total"]
                free_pages = int(ld.get("free_pages", 0))
                # acceptance piggyback: cumulative proposed/accepted ride
                # every response message of a speculating replica, so the
                # scheduler's metrics()["replicas"] shows tokens-per-
                # dispatch without log scraping
                spec_extra = {} if getattr(batcher, "spec_k", None) is None \
                    else {"spec": {"proposed": batcher.spec_proposed,
                                   "accepted": batcher.spec_accepted}}
                m_steps.inc()
                g_load.set(load)
                g_pages.set(free_pages)
                publish_engine_counters()
            with spans(_obs.SERVE_FLUSH):
                for brid, toks in deltas.items():
                    rid, trace, t_in = rid_map[brid]
                    first = {}
                    if brid not in first_sent:
                        first_sent.add(brid)
                        tracer.event("replica_first_token", trace, rid=rid,
                                     replica=ctx.executor_id)
                        if t_in is not None:
                            first = {"t_in": t_in}
                    m_tokens.inc(len(toks))
                    mgr.queue_put(RESPONSE_QUEUE,
                                  {"rid": rid, "event": "tok",
                                   "tokens": toks, "load": load,
                                   "free_pages": free_pages, **spec_extra,
                                   **role_extra, **first, **put_stamp()})
                deltas.clear()
                for brid in done:
                    batcher.result(brid, pop=True)  # tokens already streamed
                    rid, trace, _ = rid_map.pop(brid)
                    first_sent.discard(brid)
                    tracer.event("replica_done", trace, rid=rid,
                                 replica=ctx.executor_id)
                    m_served.inc()
                    mgr.queue_put(RESPONSE_QUEUE,
                                  {"rid": rid, "event": "done", "load": load,
                                   "free_pages": free_pages, **spec_extra,
                                   **role_extra, **put_stamp()})
                    served += 1
                if role == "prefill":
                    # prefill pool: flush each admitted request's exported
                    # session AFTER its first-token delta (same queue, FIFO:
                    # the driver sees TTFT close before the handoff).  The
                    # session's KV pages ride the queue/shm plane like any
                    # bulk tensor — zero-copy on a shared host.
                    for brid, session in batcher.take_sessions():
                        rid, trace, _ = rid_map.pop(brid)
                        first_sent.discard(brid)
                        tracer.event(
                            "replica_handoff", trace, rid=rid,
                            replica=ctx.executor_id,
                            pages=int(session.get("pages", 0)),
                            bytes=int(sum(a.nbytes for a in session["kv"])))
                        mgr.queue_put(RESPONSE_QUEUE,
                                      {"rid": rid, "event": "handoff",
                                       "session": session, "load": load,
                                       "free_pages": free_pages,
                                       **role_extra})
                        served += 1
                if step_hook is not None:
                    # gang barrier AFTER the step's deltas are flushed, so
                    # barrier latency never delays token delivery
                    step_hook(steps, load)
            slow_turns.turn_done(steps)
            turn_marks.next(steps)
    logger.info("%s %d %s: %d requests over %d steps "
                "(%d prefill + %d decode dispatches)", label,
                ctx.executor_id,
                "drained after preemption" if draining else "drained",
                served, steps, batcher.prefill_dispatches,
                batcher.decode_dispatches)
