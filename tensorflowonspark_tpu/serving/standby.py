"""Warm-standby gangs: pre-warmed spare replicas that close the heal window.

Every heal path the tier had before this module was COLD: a dead,
preempted, or scale-up replica paid full process boot + jit compile +
(checkpoint) restore before taking a request — exactly the window that
melts under a traffic spike or a correlated preemption.  This module
keeps ``warm_standbys=N`` spare replica gangs fully initialized but
unregistered, so a heal becomes *promote + load weights* instead of
*spawn + compile + restore*.

Worker side (:func:`serve_standby`, the standby map_fun):

- boots like a serving replica — process up, mesh built for sharded
  gangs, the persistent compilation cache every worker shares
  (``util.compilation_cache_dir``), model constructed, and the serve-step
  dispatches COMPILED via a throwaway warm-up decode — then **unloads
  the parameters** (:meth:`~tensorflowonspark_tpu.models.serving.
  ContinuousBatcher.unload_params`) and idles in heartbeat phase
  ``standby``, never registered with the scheduler;
- on a driver ``{"op": "standby", "event": "promote"}`` control message
  it re-arms: **peer weight cloning** first — it asks the live peer
  replica named in the message for its params over the existing
  queue/shm data plane (leader-to-leader bulk transfer, one message,
  zero-copy on a shared host) — falling back to rebuilding through the
  tier's ``model_builder`` (the checkpoint-restore path) when no healthy
  peer exists or the clone times out; then acks ``standby_ready`` on its
  response queue and enters the ordinary serve loop.  Promotion cost is
  transfer + load, not restore-from-store;
- ``EndOfFeed`` (tier shutdown) exits the wait loop cleanly; a SIGTERM/
  SIGKILL simply kills the process — the driver's monitor classifies it
  and the pool backfills (a standby carries no in-flight work to drain).

Driver side (:class:`StandbyPool`):

- :meth:`fill` boots the pool through the cluster's live-membership path
  (``cluster.add_workers`` with the standby map_fun — gang-sized blocks,
  watched by the monitor, invisible to the scheduler);
- :meth:`acquire` pops one standby ATOMICALLY — the dedup that makes a
  concurrent replica failure + autoscaler scale-up promote two
  *different* standbys (or one promotion + one cold spawn), never the
  same standby twice;
- :meth:`handle_failure` reaps a dead standby gang (EndOfFeed the
  survivors, retire from cluster + monitor) and backfills in the
  background — the pool self-heals under churn;
- :meth:`backfill_async` restores the pool after every promotion.

``docs/robustness.md`` has the lifecycle diagram and the heal-time
model; ``docs/serving.md`` the knob table.
"""

from __future__ import annotations

import contextlib
import logging
import queue as _queue
import threading
import time

from tensorflowonspark_tpu import metrics as _metrics
from tensorflowonspark_tpu.marker import EndOfFeed, Marker
from tensorflowonspark_tpu.serving.scheduler import (REQUEST_QUEUE,
                                                     RESPONSE_QUEUE)

logger = logging.getLogger(__name__)

#: heartbeat phases a standby worker publishes: warming (building +
#: compiling) → ``standby`` (ready to promote) — the driver's
#: ``wait_standbys`` polls for the latter
STANDBY_WARMUP_PHASE = "standby_warmup"
STANDBY_PHASE = "standby"

#: sentinel: an EndOfFeed interrupted the promotion — exit, don't serve
_STOP = object()


# --------------------------------------------------------- worker side

def serve_standby(args, ctx) -> None:
    """The warm-standby map_fun: fully initialize, unload params, idle in
    phase ``standby`` until promoted or shut down (module docstring).

    Takes the same ``args`` contract as :func:`~tensorflowonspark_tpu.
    serving.replica.serve_replica` / :func:`~tensorflowonspark_tpu.
    serving.sharded.serve_sharded_replica` plus ``serve_clone_timeout``
    (secs to wait for a peer weight clone before falling back to the
    model builder; default 60)."""
    spec = None
    if args.get("serve_mesh"):
        from tensorflowonspark_tpu.serving.sharded import (GangSpec,
                                                           _member_loop,
                                                           gang_of)

        spec = GangSpec.from_args(args)
        leader_eid, rank = gang_of(ctx.executor_id, spec.gang_size)
        if rank != 0:
            # a standby gang's members run the ordinary barrier loop —
            # idle until the promoted leader starts posting barriers
            _member_loop(args, ctx, spec, leader_eid, rank)
            return
    _standby_leader(args, ctx, spec)


def _standby_leader(args, ctx, spec) -> None:
    from tensorflowonspark_tpu.serving.replica import (
        arm_draft, run_serve_loop, serving_aot_cache,
        serving_batcher_kwargs)

    mgr = ctx.mgr
    if mgr is None:
        raise RuntimeError("the standby loop needs the node queue server "
                           "(InputMode.SPARK)")
    ctx.report_step(0, phase=STANDBY_WARMUP_PHASE)
    from tensorflowonspark_tpu.models.serving import ContinuousBatcher

    mesh = barrier = None
    shard_fn = None
    if spec is not None:
        from tensorflowonspark_tpu.serving.sharded import (
            GangBarrier, build_gang_mesh, default_shard_params)

        mesh = build_gang_mesh(spec)
        shard_fn = args.get("serve_shard_params") or default_shard_params
        members = sorted(
            (n for n in ctx.cluster_info
             if ctx.executor_id < n["executor_id"]
             < ctx.executor_id + spec.gang_size),
            key=lambda n: n["executor_id"])
        barrier = GangBarrier(
            members,
            boot_timeout=float(args.get("serve_gang_boot_timeout", 120.0)),
            step_timeout=float(args.get("serve_gang_step_timeout", 30.0)))
    cfg, params = args["serve_model_builder"](args)
    mesh_ctx = mesh if mesh is not None else contextlib.nullcontext()
    with mesh_ctx:
        if shard_fn is not None:
            params = shard_fn(cfg, params, mesh)
        batcher = ContinuousBatcher(
            cfg, params,
            max_batch=int(args.get("serve_max_batch", 4)),
            eos_id=args.get("serve_eos_id"),
            aot_cache=serving_aot_cache(args),
            **serving_batcher_kwargs(args))
        # arm the tier's draft BEFORE the warm-up sweep, so the draft
        # propose + fused verify executables are part of what the
        # standby pre-pays (and what the AOT cache pre-bakes)
        arm_draft(batcher, args)
        try:
            if barrier is not None:
                barrier.hello()
            _warm_batcher(batcher)
            batcher.unload_params()     # warm posture: compiled, weightless
            ctx.report_step(0, phase=STANDBY_PHASE)
            logger.info("standby %d warm (serve step compiled, params "
                        "unloaded)", ctx.executor_id)
            promote = _standby_wait(mgr)
            if promote is None:         # EndOfFeed: tier shutdown
                logger.info("standby %d retired unpromoted", ctx.executor_id)
                return
            got = _acquire_params(args, ctx, mgr, promote, cfg)
            if got is _STOP:
                # EndOfFeed landed mid-promotion (tier shutdown, or the
                # autoscaler retired us before the clone finished):
                # exit cleanly instead of serving unregistered forever
                logger.info("standby %d stopped during promotion",
                            ctx.executor_id)
                return
            params, prefix_pages = got
            role = promote.get("role")
            if role is not None:
                # promote-with-role (disaggregated tier): specialize the
                # pre-warmed engine for the pool this standby joins.  The
                # standby was built from the tier's BASE batcher kwargs —
                # per-role overlays (e.g. prefill_chunk) need a batcher
                # rebuild, which would re-pay the compiles the pool
                # exists to hoist, so the promoted gang serves with the
                # base engine and a notice is logged.
                overlay = (args.get("serve_disagg") or {}).get(
                    f"{role}_kwargs")
                if overlay:
                    logger.warning(
                        "standby %d promoted into the %s pool: the "
                        "tier's %s_kwargs overlay %r does not apply to "
                        "a pre-warmed engine (serving with base batcher "
                        "config)", ctx.executor_id, role, role, overlay)
                batcher.set_role(role)
                logger.info("standby %d specialized for the %s pool",
                            ctx.executor_id, role)
            if shard_fn is not None:
                params = shard_fn(cfg, params, mesh)
            else:
                # a peer clone arrives as HOST numpy: commit it to the
                # device ONCE — jitted steps would otherwise re-upload
                # the whole tree on every dispatch
                import jax

                params = jax.device_put(params)
            batcher.load_params(params)
            if prefix_pages is not None and spec is None:
                # the peer's prefix-cache pages rode the clone (KV
                # computed under the very weights just loaded): import
                # them so post-heal same-system-prompt TTFT keeps its
                # hits.  Single-process replicas only — a gang's pool
                # leaves are mesh-sharded, host pages would need a
                # resharding pass.  Best-effort: a failed import costs
                # TTFT, never the promotion.
                try:
                    n = batcher.import_prefix_cache(prefix_pages)
                    logger.info("standby %d imported %d cloned prefix-"
                                "cache page(s)", ctx.executor_id, n)
                # tfos: ignore[broad-except] — the heal must complete
                # even when the page clone is unusable (hash mismatch,
                # geometry drift); the warm pool exists for capacity
                except Exception:
                    logger.exception("standby %d: cloned prefix-cache "
                                     "import failed; serving cold-cache",
                                     ctx.executor_id)
            mgr.queue_put(RESPONSE_QUEUE,
                          {"rid": None, "event": "standby_ready",
                           "load": 0, "source": promote.get("source"),
                           **({} if role is None else {"role": role})})
            logger.info("standby %d promoted (source=%s%s): serving",
                        ctx.executor_id, promote.get("source"),
                        "" if promote.get("model") is None
                        else f", model={promote['model']}"
                             f"@{promote.get('version')}")
            # the promoted model's serve_args overlay (e.g. a seed, a
            # bench's step delay) applies to the serve LOOP; the
            # pristine boot args stay the base for later hot swaps, so
            # a rollback away from this version fully sheds its knobs
            loop_args = (dict(args, **promote["serve_args"])
                         if promote.get("serve_args") else args)
            if any(loop_args.get(k) != args.get(k)
                   for k in ("serve_draft_builder",
                             "serve_draft_base_builder",
                             "serve_draft_adapter", "serve_draft_window",
                             "serve_draft_k", "seed")):
                try:
                    # the PROMOTED version's overlay changed the draft
                    # config: re-arm from its arg view (swap or clear) —
                    # an unchanged overlay keeps the boot draft and its
                    # warmed propose executables.  Best-effort: a
                    # standby that already acked standby_ready must
                    # serve, so a bad overlay draft costs speculation,
                    # never the heal
                    arm_draft(batcher, loop_args)
                # tfos: ignore[broad-except] — see above; the target
                # params are already live and correct without any draft
                except Exception:
                    logger.exception(
                        "standby %d: draft re-arm on promotion failed; "
                        "serving without speculation draft",
                        ctx.executor_id)
                    batcher.set_draft(None)
            run_serve_loop(loop_args, ctx, batcher,
                           step_hook=None if barrier is None
                           else barrier.step,
                           label="promoted-standby", role=role,
                           base_args=args)
        finally:
            if barrier is not None:
                barrier.stop()


def _warm_batcher(batcher) -> None:
    """Pay the serve-step compiles with throwaway decodes.

    Not just one: the compiled-prefill registry is keyed on (prompt
    bucket, admission-group rows), and a promoted standby's first real
    traffic arrives as GROUPS — a single solo warm-up would leave the
    batched prefill/scatter executables to compile inside the heal
    window (exactly the cold cost the pool exists to hoist).  So sweep
    the small bucket x group grid the serve path actually uses; the
    greedy decode step compiles once on the first wave.  Further shapes
    compile on demand — and hit the fleet's persistent cache.

    With an AOT cache armed the sweep is load-or-compile: executables
    pre-baked by ``scripts/tfos_warmcache.py`` (or a previous standby)
    deserialize instead of compiling.  A speculating batcher sweeps with
    budget 4, not 2 — the spec step only engages with >1 token remaining
    (budget 2 commits its whole budget at admission + first verify-less
    step), so a 2-token sweep would leave the draft-propose and fused
    verify executables to compile inside the heal window."""
    import numpy as np

    budget = 2 if getattr(batcher, "spec_k", None) is None else 4
    group_sizes = sorted({1, min(2, batcher.max_batch), batcher.max_batch})
    for plen in (3, 6, 9):            # pow2 prompt buckets 4 / 8 / 16
        if plen + budget > batcher.cfg.max_position_embeddings:
            continue
        for rows in group_sizes:
            rids = [batcher.submit(np.ones(plen, np.int32), budget)
                    for _ in range(rows)]
            pending = set(rids)
            for _ in range(256):
                pending -= set(batcher.step())
                if not pending:
                    break
            for rid in rids:
                batcher.result(rid, pop=True)


def _standby_wait(mgr) -> dict | None:
    """Idle on the request queue until the promote control message (or
    ``EndOfFeed``/gang stop → None).  Anything else queued this early is
    re-injected for the serve loop (dispatch can race the promote ack)."""
    stash = []
    try:
        while True:
            try:
                item = mgr.queue_get(REQUEST_QUEUE, timeout=0.5)
            except (_queue.Empty, TimeoutError):
                continue
            if isinstance(item, EndOfFeed):
                return None
            if isinstance(item, dict) and item.get("op") == "standby" \
                    and item.get("event") == "promote":
                return item
            if isinstance(item, dict) and item.get("op") == "gang" \
                    and item.get("event") == "stop":
                return None
            if isinstance(item, Marker):
                continue
            stash.append(item)
    finally:
        for item in stash:
            with contextlib.suppress(Exception):
                mgr.queue_put(REQUEST_QUEUE, item)


def _acquire_params(args, ctx, mgr, promote: dict, cfg):
    """The promoted standby's weights: peer clone first, model-builder
    (checkpoint restore) fallback.  Returns ``(params, prefix_pages)``
    — ``prefix_pages`` is the peer's cloned prefix-cache export, and
    ONLY rides the clone path: builder-restored weights may differ from
    any peer's, and prefix K/V computed under other weights would
    silently decode wrong tokens.  ``_STOP`` when an ``EndOfFeed``
    interrupted the clone wait (tier shutdown / concurrent retire).

    A promote message carrying a MODEL-VERSION payload (``model``/
    ``builder``/``base_builder``/``adapter``/``serve_args`` — the
    shared spare pool re-armed per model, docs/serving.md) builds
    through THAT payload on the fallback path; the driver already
    restricted ``peer`` to replicas serving the same version, so the
    clone path is version-correct by construction."""
    peer = promote.get("peer")
    if peer is not None:
        got = _clone_from_peer(
            ctx, mgr, peer,
            timeout=float(args.get("serve_clone_timeout", 60.0)))
        if got is _STOP:
            return _STOP
        if got is not None:
            return got["params"], got.get("prefix_pages")
        logger.warning("standby %d: peer clone from %s failed/timed out; "
                       "falling back to the model builder",
                       ctx.executor_id, peer.get("executor_id"))
    if promote.get("model") is not None or promote.get("builder") \
            or promote.get("base_builder"):
        from tensorflowonspark_tpu.serving.replica import \
            resolve_version_params

        params, _ = resolve_version_params(args, promote)
        return params, None
    _cfg, params = args["serve_model_builder"](args)
    return params, None


def _clone_from_peer(ctx, mgr, peer: dict, timeout: float):
    """Pull params from a live peer replica over the queue/shm plane:
    post a ``clone`` request carrying OUR reply address onto the peer's
    request queue, then wait for the params message on our own.  Returns
    the whole params message (host-numpy ``"params"`` tree plus the
    peer's optional ``"prefix_pages"`` export), or None on any
    failure."""
    from tensorflowonspark_tpu.queues import QueueClient

    me = next(n for n in ctx.cluster_info
              if n["executor_id"] == ctx.executor_id)
    try:
        cli = QueueClient(tuple(peer["addr"]), peer["authkey"], timeout=30.0)
        try:
            cli.put(REQUEST_QUEUE,
                    {"op": "clone", "reply_addr": tuple(me["addr"]),
                     "reply_authkey": me["authkey"]}, timeout=10)
        finally:
            cli.close()
    # tfos: ignore[broad-except] — an unreachable peer (it may have just
    # died, which is why we are being promoted) must degrade to the
    # restore fallback, not crash the promotion
    except Exception:
        logger.exception("standby %d: clone request to peer %s failed",
                         ctx.executor_id, peer.get("executor_id"))
        return None
    stash = []
    deadline = time.monotonic() + timeout
    try:
        while time.monotonic() < deadline:
            try:
                item = mgr.queue_get(REQUEST_QUEUE, timeout=0.5)
            except (_queue.Empty, TimeoutError):
                continue
            if isinstance(item, dict) and item.get("op") == "standby" \
                    and item.get("event") == "params":
                return item
            if isinstance(item, EndOfFeed):
                return _STOP        # shutdown/retire raced the promotion
            if isinstance(item, Marker):
                continue
            stash.append(item)      # early-dispatched gen requests
        return None
    finally:
        for item in stash:
            with contextlib.suppress(Exception):
                mgr.queue_put(REQUEST_QUEUE, item)


# --------------------------------------------------------- driver side

class StandbyPool:
    """Driver-side inventory of warm standby gangs (module docstring).

    All mutation happens under one lock; :meth:`acquire` POPS, so two
    concurrent heal decisions can never promote the same standby.  The
    pool emits its lifecycle (``standby_booted`` / ``standby_dead`` /
    ``standby_backfill_failed``) into the tier's ``serving_events.jsonl``
    and mirrors its size into ``tfos_serving_standby_count``.
    """

    def __init__(self, serving, size: int):
        if int(size) < 1:
            raise ValueError(f"StandbyPool needs size >= 1, got {size}")
        self.serving = serving
        self.size = int(size)
        self._lock = threading.Lock()
        self._entries: dict[int, dict] = {}   # leader eid -> {info, members}
        self._gang: dict[int, int] = {}       # every standby eid -> leader
        #: every standby worker eid lost to failure while UNPROMOTED —
        #: the tier's shutdown tolerates these corpses like failed-over
        #: replicas (they were handled: the pool backfilled)
        self.dead: set[int] = set()
        self._stopped = False
        #: serializes fill/backfill: two concurrent promotions each
        #: trigger a backfill, and unserialized check-then-boot loops
        #: would overshoot the pool size
        self._fill_lock = threading.Lock()
        self._g_count = _metrics.get_registry().gauge(
            "tfos_serving_standby_count",
            "Warm standby replicas ready to promote.")
        self._g_count.set(0)

    # -- lifecycle ---------------------------------------------------------
    def fill(self, timeout: float | None = None) -> None:
        """Boot standbys until the pool holds ``size`` (blocking on each
        gang's reservation; the model build + compile warm-up continues
        in the worker after this returns — gate on :meth:`wait_warm`).
        Serialized: concurrent backfills top the pool up exactly once."""
        with self._fill_lock:
            while not self._stopped and len(self._entries) < self.size:
                self._boot_one(timeout=timeout)

    def stop(self) -> None:
        """No further backfills; the cluster's shutdown EndOfFeed retires
        the unpromoted standbys themselves."""
        self._stopped = True
        self._g_count.remove()

    # -- inventory ---------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            return {"standbys": len(self._entries),
                    "ready": sorted(self._entries)}

    def leader_of(self, eid: int) -> int | None:
        """The standby gang leader owning ``eid`` (None when ``eid`` is
        not an unpromoted standby worker)."""
        with self._lock:
            return self._gang.get(int(eid))

    def acquire(self) -> tuple[int, dict] | None:
        """Pop the oldest (warmest) standby atomically; None when empty.
        The entry leaves the pool's ownership entirely — from here on the
        gang is the caller's (scheduler registration, failure domain)."""
        with self._lock:
            if not self._entries:
                return None
            eid = min(self._entries)
            entry = self._entries.pop(eid)
            for e in (eid, *entry["members"]):
                self._gang.pop(e, None)
            self._g_count.set(len(self._entries))
        return eid, entry

    def wait_warm(self, timeout: float = 120.0) -> bool:
        """Block until every pooled standby heartbeats phase ``standby``
        (serve step compiled, params unloaded).  False on timeout or when
        the tier runs without a monitor."""
        monitor = self.serving.monitor
        if monitor is None:
            return False
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                leaders = list(self._entries)
            if leaders:
                nodes = monitor.node_metrics()
                if all(nodes.get(e, {}).get("phase") == STANDBY_PHASE
                       for e in leaders):
                    return True
            time.sleep(0.2)
        return False

    # -- churn -------------------------------------------------------------
    def handle_failure(self, failed_eids) -> set[int]:
        """Absorb worker deaths that hit UNPROMOTED standbys: remove the
        gang from the pool, reap its surviving processes, backfill in the
        background.  Returns every executor id belonging to an affected
        standby gang (the caller excludes them from replica failover)."""
        leaders = {self.leader_of(int(e)) for e in failed_eids}
        leaders.discard(None)
        handled: set[int] = set()
        for leader in sorted(leaders):
            with self._lock:
                entry = self._entries.pop(leader, None)
                if entry is None:
                    continue
                gang = (leader, *entry["members"])
                for e in gang:
                    self._gang.pop(e, None)
                self._g_count.set(len(self._entries))
            handled.update(gang)
            self.dead.update(gang)
            logger.warning("warm standby %d died; pool backfills", leader)
            self.serving.scheduler.emit_event(
                "standby_dead", replica=leader, members=list(gang[1:]))
            # off the caller's thread: handle_failure runs inside the
            # monitor's poll (holding its _poll_lock — ignore_workers
            # would self-deadlock) and the reap does queue I/O
            threading.Thread(target=self._reap_and_backfill, args=(gang,),
                             name=f"standby-reap-{leader}",
                             daemon=True).start()
        return handled

    def backfill_async(self) -> None:
        """Restore the pool toward ``size`` on a background thread (the
        promotion/heal path must not block on a fresh gang's boot)."""
        if self._stopped:
            return
        threading.Thread(target=self._backfill,
                         name="standby-backfill", daemon=True).start()

    # -- internals ---------------------------------------------------------
    def _boot_one(self, timeout: float | None = None) -> int:
        serving = self.serving
        gsz = (1 if serving.gang_spec is None
               else serving.gang_spec.gang_size)
        added = serving.cluster.add_workers(
            gsz, map_fun=serve_standby, tf_args=serving._serve_args,
            timeout=timeout)
        leader = added[0]
        eid = int(leader["executor_id"])
        members = tuple(int(b["executor_id"]) for b in added[1:])
        with self._lock:
            self._entries[eid] = {"info": leader, "members": members}
            for e in (eid, *members):
                self._gang[e] = eid
            self._g_count.set(len(self._entries))
        serving.scheduler.emit_event(
            "standby_booted", replica=eid, members=list(members),
            pool=len(self._entries))
        logger.info("warm standby %d booted (pool %d/%d)", eid,
                    len(self._entries), self.size)
        return eid

    def _backfill(self) -> None:
        try:
            self.fill()
        # tfos: ignore[broad-except] — a failed backfill (cluster
        # shutting down, spawn refused) degrades the pool, it must not
        # kill the thread group or the heal that triggered it
        except Exception:
            if not self._stopped:
                logger.exception("warm-standby backfill failed")
                with contextlib.suppress(Exception):
                    self.serving.scheduler.emit_event(
                        "standby_backfill_failed",
                        pool=len(self._entries))

    def _reap_and_backfill(self, gang) -> None:
        self._reap(gang)
        self._backfill()

    def _reap(self, gang) -> None:
        """Stop a dead standby gang's survivors: EndOfFeed each shard
        (best-effort), retire from the monitor and the cluster so late
        exits are never classified and shutdown skips the slots."""
        serving = self.serving
        if serving.monitor is not None:
            serving.monitor.ignore_workers(gang)
        for e in gang:
            with contextlib.suppress(Exception):
                serving.cluster._client_for(e).put(REQUEST_QUEUE,
                                                   EndOfFeed(), timeout=5)
            with contextlib.suppress(Exception):
                serving.cluster.retire_worker(e)
