"""Mesh-sharded serving replicas: gang-scheduled multichip model instances.

This is where the two flagship subsystems finally meet: the parallelism
layer's device meshes (``parallel.mesh`` — tp/pp/ep axes, the dry-run'd
``MULTICHIP_r0*.json`` configurations) move BEHIND the serving tier, so a
routable replica is no longer one ``ContinuousBatcher`` process but a
**gang**: ``gang_size`` worker processes that boot, serve, fail, and
retire as one schedulable unit — the replica-as-gang shape of production
engines, where a tensor-parallel model instance spans several processes
but is one endpoint to the router.

Gang anatomy (``serve_sharded_replica``, the map_fun every gang process
runs; rank = ``executor_id % gang_size`` picks the role):

- **rank 0 — the leader.** Builds the gang's device mesh over its local
  devices (``GangSpec.axes``, e.g. ``{"tp": 2}``; on a TPU host all of a
  host's chips belong to one process, on CPU the mesh is simulated via
  ``XLA_FLAGS=--xla_force_host_platform_device_count``), shards the
  model's parameters onto it — Megatron-style tp via the model's own
  ``nn.with_partitioning`` annotations (``flax_shardings``) for the
  dense GPT path, or a caller-supplied ``serve_shard_params(cfg, params,
  mesh) -> params`` for pp (``pipeline_apply`` stages) and ep-routed MoE
  (``moe_apply`` specs) layouts — and then runs intake / continuous
  batching / ``on_token`` streaming EXACTLY as ``serve_replica`` does
  (the loop is literally shared: :func:`~tensorflowonspark_tpu.serving.
  replica.run_serve_loop`), every prefill/decode dispatch compiled over
  the mesh.
- **ranks 1..gang_size-1 — shard members.** Ordinary cluster workers
  that rendezvoused through the same reservation server; each serves the
  gang's **step barrier** over its own node queue plane: the leader
  posts a ``{"op": "gang", "event": "barrier", "seq", "steps"}`` message
  after every decode step, the member acks it and reports the leader's
  step count through ``ctx.report_step(phase="serving")`` — so the
  driver's hang watchdog covers every shard of the gang, and chaos plans
  get their deterministic ``at_step`` trigger on ANY shard.  On a
  multi-host deployment the members own the mesh's remote slices and the
  barrier carries the step descriptor they execute under
  ``jax.distributed``; on a single host (and the CPU-simulated meshes
  the tests/benches run) the leader's process owns every device and the
  members are the gang's failure-domain stand-ins — same lifecycle,
  same failover, same heartbeats.

Failure semantics (the point of the gang):

- a member lost mid-service surfaces twice, independently: the driver's
  :class:`~tensorflowonspark_tpu.health.ClusterMonitor` classifies the
  process exit and the serving tier resolves ANY shard's death to the
  whole gang (``ReplicaScheduler`` keeps a member→leader map), marking
  the gang dead ONCE and re-queueing its in-flight requests to the
  survivors with the skip-dedup replay (oracle-exact streams, as PR 3);
  meanwhile the leader's next barrier ack fails and it raises
  :class:`GangShardLost` — a loud crash, never a silent half-width gang;
- a leader lost the same way leaves members idling on their barrier
  queue; the tier reaps them with a per-member ``EndOfFeed`` so they
  exit cleanly and the gang's processes never outlive its death;
- preemption (SIGTERM / chaos ``replace``) of ANY shard drains the gang
  leader under its grace window and the tier replaces the FULL gang.

``args`` contract adds to ``serve_replica``'s: ``serve_mesh`` (axis-name
→ size dict), ``serve_gang_size`` (processes per gang, default = the
mesh's device count), optional ``serve_shard_params`` (picklable
``(cfg, params, mesh) -> params``), ``serve_gang_boot_timeout`` /
``serve_gang_step_timeout`` (member hello / per-step ack deadlines).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import math
import queue as _queue
import time as _time

from tensorflowonspark_tpu import device_info
from tensorflowonspark_tpu import metrics as _metrics
from tensorflowonspark_tpu.marker import EndOfFeed, Marker
from tensorflowonspark_tpu.preemption import PreemptionGuard
from tensorflowonspark_tpu.queues import QueueClient
from tensorflowonspark_tpu.serving.replica import (run_serve_loop,
                                                   serving_batcher_kwargs)
from tensorflowonspark_tpu.serving.scheduler import (REQUEST_QUEUE,
                                                     RESPONSE_QUEUE)

logger = logging.getLogger(__name__)


class GangShardLost(RuntimeError):
    """A gang member stopped answering the step barrier: the sharded
    replica can no longer run its mesh program at full width, so the
    leader crashes loudly and the driver fails the WHOLE gang over."""


#: ``parallel.mesh.AXES``, restated: the driver and the gang's shard
#: members validate a spec without importing the ``parallel`` package,
#: which imports jax (tests/test_chip_ownership.py pins the two equal)
AXES = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class GangSpec:
    """Shape of one sharded replica: the device-mesh axes its model
    shards over and the number of worker processes in its gang.

    ``axes`` uses the canonical mesh axis names (``parallel.mesh.AXES``)
    — e.g. ``{"tp": 2}`` for a 2-way tensor-parallel dense replica,
    ``{"pp": 2, "tp": 2}`` for a 4-device pipeline x tensor gang,
    ``{"ep": 4}`` for ep-routed MoE.  ``gang_size`` defaults to the mesh
    device count (one process per device slot); a multi-chip host can
    run fewer processes than devices (e.g. one 4-chip leader process and
    no members: ``gang_size=1``).
    """

    axes: dict
    gang_size: int | None = None

    def __post_init__(self):
        axes = dict(self.axes)
        unknown = set(axes) - set(AXES)
        if unknown:
            raise ValueError(f"unknown mesh axes {sorted(unknown)} in gang "
                             f"spec; valid axes: {AXES}")
        for ax, s in axes.items():
            if not isinstance(s, int) or s < 1:
                raise ValueError(f"gang mesh axis '{ax}' has invalid size "
                                 f"{s!r} (want a positive int)")
        object.__setattr__(self, "axes", axes)
        size = self.gang_size if self.gang_size is not None else self.devices
        if int(size) < 1:
            raise ValueError(f"gang_size must be >= 1, got {size}")
        object.__setattr__(self, "gang_size", int(size))

    @property
    def devices(self) -> int:
        """Devices in one gang's mesh — the replica's capacity weight."""
        return math.prod(self.axes.values()) if self.axes else 1

    def describe(self) -> str:
        axes = ",".join(f"{a}={s}" for a, s in self.axes.items()
                        if s != 1) or "1 device"
        return f"mesh[{axes}] x {self.gang_size} proc(s)"

    @classmethod
    def from_args(cls, args) -> "GangSpec":
        return cls(axes=dict(args.get("serve_mesh") or {}),
                   gang_size=args.get("serve_gang_size"))


def gang_of(executor_id: int, gang_size: int) -> tuple[int, int]:
    """``(leader_eid, rank)`` for a worker in an aligned gang block —
    gangs are contiguous, gang_size-aligned executor-id ranges, computed
    identically by the driver's scheduler and every worker."""
    rank = int(executor_id) % int(gang_size)
    return int(executor_id) - rank, rank


def build_gang_mesh(spec: GangSpec):
    """The gang's device mesh over this process's local devices, with a
    clear error when the host cannot provide them."""
    import jax

    from tensorflowonspark_tpu.parallel.mesh import MeshSpec, make_mesh

    devs = jax.devices()
    if len(devs) < spec.devices:
        raise RuntimeError(
            f"sharded replica needs {spec.devices} local devices for "
            f"{spec.describe()}, found {len(devs)} — on CPU simulate them "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{spec.devices} in worker_env")
    return make_mesh(MeshSpec(**{**{"dp": 1}, **spec.axes}),
                     devices=devs[:spec.devices])


def default_shard_params(cfg, params, mesh):
    """The dense-GPT parameter layout: shard via the model's own
    ``nn.with_partitioning`` annotations (Megatron tp — attention heads,
    FFN, and vocab shards over ``tp``), replicate the rest.  Fails
    loudly when the mesh has a >1 model axis but NOTHING ended up
    sharded — a silently replicated "sharded" replica would burn
    ``devices x`` memory and serve tp=1 numbers."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import GPT
    from tensorflowonspark_tpu.parallel.sharding import flax_shardings

    if getattr(cfg, "has_state", False) \
            or getattr(cfg, "num_experts", None) is not None:
        # the expert weights [E, H, F] and the conv kernel carry no
        # partitioning annotations, and a retention or mamba2 layer's
        # state (its step is a pallas_call, which is not partitioned) none
        # by head: a mesh would replicate most of such a model and call it
        # sharded
        raise ValueError(
            "default_shard_params lays out the dense-GPT leaves only; a "
            "configuration with conv, retention or mamba2 layers or "
            "experts (layer_types / num_experts) has no sharded layout "
            "yet — serve it on one chip, or pass serve_shard_params= with "
            f"its own layout; this configuration keeps {cfg.cache_kinds}"
            + ("" if getattr(cfg, "num_experts", None) is None else
               f" and {cfg.num_experts_held} of {cfg.num_experts} experts"))
    model = GPT(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 4), jnp.int32)))
    shardings = flax_shardings(mesh, abstract)["params"]
    params = jax.device_put(params, shardings)
    model_axes = {a: n for a, n in mesh.shape.items()
                  if n > 1 and a not in ("dp", "fsdp")}
    n_sharded = sum(
        any(e is not None for e in s.spec)
        for s in jax.tree.leaves(shardings))
    if model_axes and n_sharded == 0:
        raise RuntimeError(
            f"sharded replica mesh has model axes {model_axes} but no "
            "parameter was sharded — this model carries no partitioning "
            "annotations for them; pass serve_shard_params= with the "
            "model's own layout (pipeline stages, MoE expert specs)")
    logger.info("sharded replica params: %d/%d leaves sharded over %s",
                n_sharded, len(jax.tree.leaves(shardings)),
                dict(mesh.shape))
    return params


class GangBarrier:
    """Leader-side step barrier over the members' node queue plane.

    One short-timeout :class:`QueueClient` per member (``shm=False`` —
    control messages must not consume zero-copy ring slots).  ``hello``
    collects each member's boot ``ready`` ack; ``step`` posts one
    barrier message per member and collects their acks, raising
    :class:`GangShardLost` naming the first shard that failed to answer.
    """

    def __init__(self, member_infos: list[dict], *,
                 boot_timeout: float = 120.0, step_timeout: float = 30.0):
        self._members = list(member_infos)
        self._clients: dict[int, QueueClient] = {}
        self.boot_timeout = float(boot_timeout)
        self.step_timeout = float(step_timeout)
        reg = _metrics.get_registry()
        self._m_barriers = reg.counter(
            "tfos_gang_barriers_total",
            "Step barriers completed by this gang leader.")
        self._h_barrier = reg.histogram(
            "tfos_gang_barrier_seconds",
            "Post-to-last-ack latency of one gang step barrier.")

    def _client(self, info: dict) -> QueueClient:
        eid = int(info["executor_id"])
        if eid not in self._clients:
            self._clients[eid] = QueueClient(info["addr"], info["authkey"],
                                             timeout=30.0, shm=False)
        return self._clients[eid]

    def _ack(self, info: dict, event: str, timeout: float) -> dict:
        eid = int(info["executor_id"])
        deadline = _time.monotonic() + timeout
        booting = event == "ready"
        while True:
            remaining = deadline - _time.monotonic()
            if remaining <= 0:
                raise GangShardLost(
                    f"gang shard {eid} did not ack '{event}' within "
                    f"{timeout:.0f}s")
            try:
                msg = self._client(info).get(RESPONSE_QUEUE,
                                             timeout=min(remaining, 5.0))
            except TimeoutError:
                continue
            except ConnectionRefusedError as e:
                # the boot hello may race a member whose queue server is
                # still binding; a refused connect mid-SERVICE is a dead
                # shard
                if not booting:
                    raise GangShardLost(
                        f"gang shard {eid} lost ({event}): {e!r}") from e
                with contextlib.suppress(Exception):
                    self._clients.pop(eid).close()
                _time.sleep(0.2)
                continue
            except Exception as e:
                raise GangShardLost(
                    f"gang shard {eid} lost ({event}): {e!r}") from e
            if isinstance(msg, dict) and msg.get("op") == "gang" \
                    and msg.get("event") == event:
                return msg

    def hello(self) -> None:
        """Collect every member's boot ack — fail fast on a sick gang
        before the leader advertises itself as routable."""
        for info in self._members:
            self._ack(info, "ready", self.boot_timeout)
        logger.info("gang barrier up: %d member(s) ready",
                    len(self._members))

    def step(self, steps: int, load: int) -> None:
        """One barrier round: post, then collect every ack."""
        if not self._members:
            return
        t0 = _time.monotonic()
        for info in self._members:
            eid = int(info["executor_id"])
            try:
                self._client(info).put(
                    REQUEST_QUEUE,
                    {"op": "gang", "event": "barrier", "seq": steps,
                     "steps": steps, "load": int(load)}, timeout=10)
            except Exception as e:
                raise GangShardLost(
                    f"gang shard {eid} lost (barrier post at step "
                    f"{steps}): {e!r}") from e
        for info in self._members:
            self._ack(info, "ack", self.step_timeout)
        self._m_barriers.inc()
        self._h_barrier.record(_time.monotonic() - t0)

    def stop(self) -> None:
        """Best-effort gang stop + client close (leader exit, clean or
        crashing): surviving members stop idling on their barrier queue
        without waiting for the driver's reap."""
        for info in self._members:
            with contextlib.suppress(Exception):
                self._client(info).put(
                    REQUEST_QUEUE, {"op": "gang", "event": "stop"},
                    timeout=2)
        for cli in self._clients.values():
            with contextlib.suppress(Exception):
                cli.close()
        self._clients.clear()


def serve_sharded_replica(args, ctx) -> None:
    """The gang map_fun: rank 0 leads (mesh + model + serve loop),
    other ranks serve the step barrier (module docstring)."""
    spec = GangSpec.from_args(args)
    leader_eid, rank = gang_of(ctx.executor_id, spec.gang_size)
    if rank != 0:
        _member_loop(args, ctx, spec, leader_eid, rank)
        return
    # leader: jax/model imports stay inside the worker process
    from tensorflowonspark_tpu.serving.replica import (arm_draft,
                                                       serving_aot_cache)
    from tensorflowonspark_tpu.models.serving import ContinuousBatcher

    mesh = build_gang_mesh(spec)
    cfg, params = args["serve_model_builder"](args)
    shard_fn = args.get("serve_shard_params") or default_shard_params
    members = sorted(
        (n for n in ctx.cluster_info
         if leader_eid < n["executor_id"] < leader_eid + spec.gang_size),
        key=lambda n: n["executor_id"])
    if len(members) != spec.gang_size - 1:
        raise RuntimeError(
            f"gang {leader_eid} expected {spec.gang_size - 1} member "
            f"reservation(s), found {len(members)} — cluster size must be "
            f"a multiple of gang_size={spec.gang_size}")
    reg = _metrics.get_registry()
    reg.gauge("tfos_gang_shards_count",
              "Processes in this sharded replica's gang.").set(spec.gang_size)
    reg.gauge("tfos_gang_devices_count",
              "Devices in this sharded replica's mesh.").set(spec.devices)
    logger.info("gang %d leader (%s): sharding model over %s", leader_eid,
                spec.describe(), dict(mesh.shape))
    with mesh:
        params = shard_fn(cfg, params, mesh)
        batcher = ContinuousBatcher(
            cfg, params,
            max_batch=int(args.get("serve_max_batch", 4)),
            eos_id=args.get("serve_eos_id"),
            aot_cache=serving_aot_cache(args),
            **serving_batcher_kwargs(args))
        # inside the mesh context: the draft's params stay REPLICATED
        # (a tiny model has nothing worth sharding) and its propose
        # dispatches ride the same mesh as the target's verify
        arm_draft(batcher, args)
        barrier = GangBarrier(
            members,
            boot_timeout=float(args.get("serve_gang_boot_timeout", 120.0)),
            step_timeout=float(args.get("serve_gang_step_timeout", 30.0)))
        try:
            barrier.hello()
            run_serve_loop(args, ctx, batcher, step_hook=barrier.step,
                           label=f"gang-{leader_eid} leader",
                           role=args.get("serve_role"))
        finally:
            # clean exit or GangShardLost alike: tell surviving members
            # to stop idling on their barrier queue
            barrier.stop()


def _member_loop(args, ctx, spec: GangSpec, leader_eid: int,
                 rank: int) -> None:
    """Shard member: ack step barriers, mirror the leader's step count
    into this process's heartbeat, exit on gang stop / ``EndOfFeed``."""
    mgr = ctx.mgr
    if mgr is None:
        raise RuntimeError("the serving loop needs the node queue server "
                           "(InputMode.SPARK)")
    # on one host the leader's process owns every chip: a member that
    # touched jax would take the libtpu lock from under it (checked on
    # the way in and on the way out)
    who = f"gang {leader_eid} member rank {rank}"
    device_info.assert_off_accelerator(who)
    reg = _metrics.get_registry()
    m_acks = reg.counter("tfos_gang_member_acks_total",
                         "Step barriers acked by this gang member.")
    logger.info("gang %d member rank %d (executor %d) up", leader_eid,
                rank, ctx.executor_id)
    mgr.queue_put(RESPONSE_QUEUE,
                  {"op": "gang", "event": "ready", "rank": rank,
                   "eid": ctx.executor_id})
    guard = PreemptionGuard()
    announced = False
    with guard:
        while True:
            try:
                item = mgr.queue_get(REQUEST_QUEUE, timeout=0.5)
            except (_queue.Empty, TimeoutError):
                if guard.preempted and not announced:
                    # an idle member's reclaim still has to reach the
                    # driver: flip the phase so the tier drains and
                    # replaces the gang (steps stay at the leader's)
                    announced = True
                    ctx.report_step(max(1, _last_step(ctx)),
                                    phase="preempted")
                continue
            if isinstance(item, EndOfFeed):
                break
            if isinstance(item, dict) and item.get("op") == "gang":
                event = item.get("event")
                if event == "stop":
                    break
                if event == "barrier":
                    # ack FIRST: a chaos kill inside report_step must
                    # land between barriers, not while the leader waits
                    mgr.queue_put(RESPONSE_QUEUE,
                                  {"op": "gang", "event": "ack",
                                   "seq": item.get("seq"), "rank": rank})
                    m_acks.inc()
                    steps = int(item.get("steps", 0))
                    _set_last_step(ctx, steps)
                    if guard.preempted:
                        announced = True
                    ctx.report_step(
                        steps,
                        phase="preempted" if guard.preempted else "serving")
                continue
            if isinstance(item, Marker):
                continue
            logger.warning("gang member %d: ignoring unexpected item %r",
                           ctx.executor_id, type(item))
    device_info.assert_off_accelerator(who)
    logger.info("gang %d member rank %d stopped%s", leader_eid, rank,
                " (preempted)" if guard.preempted else "")


def _set_last_step(ctx, steps: int) -> None:
    ctx._gang_last_step = int(steps)


def _last_step(ctx) -> int:
    return int(getattr(ctx, "_gang_last_step", 0))
