"""Distribution strategies: the ``tf.distribute`` surface, TPU-native.

In the reference, a user's ``map_fun`` does::

    strategy = tf.distribute.MultiWorkerMirroredStrategy()   # NCCL allreduce
    with strategy.scope():
        model = build_model()
    model.fit(dataset)

(TFoS's only role is having exported ``TF_CONFIG`` first —
``TFSparkNode.py::run``.)  The TPU rebuild keeps the same shape::

    strategy = MultiWorkerMirroredStrategy()        # = DataParallelStrategy
    state = strategy.init_state(model, optimizer, sample_batch)
    step = strategy.build_train_step(loss_fn)
    state, metrics = step(state, strategy.shard_batch(batch))

but the strategy is a thin veneer over a Mesh + jit shardings: gradients
are averaged by XLA-inserted collectives over ICI, parameters live wherever
the strategy's partition rules put them, and the same code runs on 1 chip or
a multi-host pod.
"""

from __future__ import annotations

import dataclasses
import logging
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tensorflowonspark_tpu import observability as _obs
from tensorflowonspark_tpu.parallel import sharding as sh
from tensorflowonspark_tpu.parallel.mesh import MeshSpec, make_mesh
from tensorflowonspark_tpu.parallel.sharding import PartitionRules

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class TrainState:
    """Minimal train state (params + opt state + step), pytree-registered."""

    params: object
    opt_state: object
    step: jnp.ndarray
    extras: dict = dataclasses.field(default_factory=dict)  # e.g. batch_stats


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt_state", "step", "extras"], meta_fields=[])


class MeshStrategy:
    """Base strategy: explicit mesh + optional parameter partition rules."""

    def __init__(self, mesh=None, rules: PartitionRules | None = None,
                 seed: int = 0, **axis_sizes):
        self.mesh = mesh if mesh is not None else make_mesh(**axis_sizes)
        self.rules = rules
        # base key for per-step rng (dropout etc.): folded with state.step
        # inside the compiled step, so resume-from-checkpoint reproduces
        # the exact rng stream
        self._base_rng = jax.random.key(seed)

    # -- state -------------------------------------------------------------
    def init_state(self, init_fn, tx, *init_args) -> TrainState:
        """Initialize params via ``init_fn(*init_args)``, created sharded.

        ``tx`` is an optax transform.  Parameters are *born* on their target
        shards — ``init_fn`` is jitted with ``out_shardings`` from the
        strategy's rules, so the full tree is never materialized on one
        device (critical for FSDP models bigger than one chip's HBM).  The
        optimizer state mirrors the parameter tree, so its leaves inherit
        each parameter's placement.
        """
        abstract = jax.eval_shape(init_fn, *init_args)
        if self.rules is None:
            shardings = jax.tree.map(lambda _: sh.replicated(self.mesh), abstract)
        else:
            shardings = self.rules.tree_shardings(self.mesh, abstract)
        params = jax.jit(init_fn, out_shardings=shardings)(*init_args)
        opt_state = jax.jit(tx.init)(params)
        self._tx = tx
        # step lives on the mesh too: a committed single-device scalar would
        # conflict with mesh-committed params after a checkpoint restore
        step = jax.device_put(jnp.zeros((), jnp.int32), sh.replicated(self.mesh))
        return TrainState(params=params, opt_state=opt_state, step=step)

    # -- data --------------------------------------------------------------
    def shard_batch(self, batch):
        with _obs.span(_obs.TRAIN_SHARD_BATCH):
            return sh.shard_batch(self.mesh, batch)

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, sh.batch_pspec())

    def anchor_activations(self, x):
        """Constrain activations (any pytree) to stay batch-sharded over
        the data axes — leading dim over ``dp×fsdp``, rest replicated.

        Drop this on intermediate activations inside ``loss_fn`` when
        parameters are sharded (FSDP/rules): without an anchor, XLA's
        sharding propagation may flow the WEIGHT sharding into the
        activations instead — contracting the sharded feature dim and
        all-reducing activation-sized partials every layer (accidental
        tensor parallelism over the fsdp axis).  Measured on BERT-base
        fsdp=8 by ``scripts/scaling_model.py``: 47 GB → 1.1 GB of
        per-step collective traffic from one anchor at the loss head
        (see ``__graft_entry__.build_bert_train_step``).
        """
        def one(a):
            if jnp.ndim(a) == 0:  # scalars incl. python numbers pass through
                return a
            spec = P(sh.batch_pspec()[0], *([None] * (jnp.ndim(a) - 1)))
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(self.mesh, spec))

        return jax.tree.map(one, x)

    # -- step --------------------------------------------------------------
    def build_train_step(self, loss_fn, tx=None, donate: bool = True,
                         accum_steps: int = 1):
        """Compile ``state, batch -> state, metrics``.

        ``loss_fn(params, batch) -> scalar`` or ``(scalar, aux)``.  A
        three-argument ``loss_fn(params, batch, extras)`` also receives
        ``state.extras`` (mutable collections like BatchNorm statistics);
        returning an ``"extras"`` key in ``aux`` stores it back into the next
        state — the ``mutable=["batch_stats"]`` pattern without threading the
        stats through the batch (which would alias donated buffers).

        A ``rng`` keyword parameter in ``loss_fn``'s signature receives a
        per-step ``jax.random`` key (``fold_in(base, state.step)`` — the
        dropout plumbing; deterministic given the strategy ``seed``, and
        resume-safe because it derives from the step counter)::

            def loss_fn(params, batch, rng=None):
                logits = model.apply({"params": params}, batch["x"],
                                     train=True, rngs={"dropout": rng})

        ``accum_steps > 1`` enables gradient accumulation: the batch's
        leading dim splits into that many microbatches, a ``lax.scan``
        averages their gradients (one set of gradient buffers, activations
        sized by the microbatch), and ONE optimizer update applies — the
        standard way to train an effective batch larger than activations
        allow.  Identical numerics to the single big batch for
        mean-reduced losses; each microbatch gets its own derived ``rng``.

        Gradient averaging across data shards is *not* written here — the
        batch is sharded over dp/fsdp and the loss is a mean over the global
        batch, so XLA inserts the reduce-scatter/all-reduce it needs (the
        NCCL allreduce of ``MultiWorkerMirroredStrategy``, compiled).
        """
        import inspect

        tx = tx or getattr(self, "_tx", None)
        assert tx is not None, "pass tx= or call init_state first"
        has_aux = getattr(loss_fn, "has_aux", False)
        takes_extras = getattr(loss_fn, "takes_extras", None)
        if takes_extras is None:
            # infer only from an explicit third *positional* param named
            # 'extras' — a bare arg-count check would misroute state.extras
            # into **kwargs or a defaulted third arg (e.g. rng=...)
            try:
                params = list(inspect.signature(loss_fn).parameters.values())
            except (TypeError, ValueError):
                params = []
            takes_extras = (
                len(params) >= 3 and params[2].name == "extras"
                and params[2].kind in (inspect.Parameter.POSITIONAL_ONLY,
                                       inspect.Parameter.POSITIONAL_OR_KEYWORD))
        try:
            sig_params = inspect.signature(loss_fn).parameters
        except (TypeError, ValueError):
            sig_params = {}
        takes_rng = "rng" in sig_params
        base_rng = self._base_rng

        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        mesh = self.mesh

        def one_grad(params, extras, batch, rng):
            grad_fn = jax.value_and_grad(loss_fn, has_aux=has_aux)
            args = (params, batch, extras) if takes_extras else (params, batch)
            kwargs = {"rng": rng} if takes_rng else {}
            with jax.named_scope("loss_and_grad"):
                if has_aux:
                    (loss, aux), grads = grad_fn(*args, **kwargs)
                else:
                    loss, grads = grad_fn(*args, **kwargs)
                    aux = {}
            return loss, aux, grads

        # the compiled program's name: the profiler reads
        # ``jit_tfos_train_step`` (docs/observability.md "Profiler spans")
        def tfos_train_step(state: TrainState, batch):
            import optax

            step_rng = jax.random.fold_in(base_rng, state.step) \
                if takes_rng else None
            if accum_steps == 1:
                loss, aux, grads = one_grad(state.params, state.extras,
                                            batch, step_rng)
                extras = aux.pop("extras", state.extras) \
                    if isinstance(aux, dict) else state.extras
            else:
                # [B, ...] -> [accum, B/accum, ...]; the microbatch dim
                # stays sharded over the data axes
                def split(x):
                    if x.shape[0] % accum_steps:
                        raise ValueError(
                            f"batch size {x.shape[0]} not divisible by "
                            f"accum_steps={accum_steps}")
                    y = x.reshape((accum_steps, -1) + x.shape[1:])
                    return jax.lax.with_sharding_constraint(
                        y, NamedSharding(mesh, sh.batch_pspec(extra_leading=1)))

                micro = jax.tree.map(split, batch)

                def body(carry, inputs):
                    extras = carry["extras"]
                    mb, i = inputs
                    rng = jax.random.fold_in(step_rng, i) \
                        if takes_rng else None
                    loss, aux, grads = one_grad(state.params, extras, mb, rng)
                    extras = aux.pop("extras", extras) \
                        if isinstance(aux, dict) else extras
                    carry = {
                        "grads": jax.tree.map(jnp.add, carry["grads"], grads),
                        "loss": carry["loss"] + loss,
                        "extras": extras,
                    }
                    return carry, aux

                zero_grads = jax.tree.map(jnp.zeros_like, state.params)
                carry0 = {"grads": zero_grads, "loss": jnp.zeros(()),
                          "extras": state.extras}
                carry, aux_stack = jax.lax.scan(
                    body, carry0, (micro, jnp.arange(accum_steps)))
                grads = jax.tree.map(lambda g: g / accum_steps, carry["grads"])
                loss = carry["loss"] / accum_steps
                # extras threaded through the carry; body already stripped
                # "extras" from the per-microbatch aux, so the stacked aux
                # is pure metrics — report the last microbatch's
                extras = carry["extras"]
                aux = jax.tree.map(lambda a: a[-1], aux_stack)

            with jax.named_scope("optimizer_update"):
                updates, opt_state = tx.update(grads, state.opt_state,
                                               state.params)
                params = optax.apply_updates(state.params, updates)
            new_state = TrainState(params=params, opt_state=opt_state,
                                   step=state.step + 1, extras=extras)
            metrics = {"loss": loss, **aux}
            return new_state, metrics

        donate_argnums = (0,) if donate else ()
        return jax.jit(tfos_train_step, donate_argnums=donate_argnums)

    def run(self, fn, *args):
        """Execute ``fn`` under this strategy's mesh context (for explicit
        ``PartitionSpec``-annotated code using ``shard_map`` / axis names)."""
        with self.mesh:
            return fn(*args)

    @property
    def num_replicas_in_sync(self) -> int:
        """tf.distribute parity: total data-parallel degree."""
        return (self.mesh.shape["dp"] * self.mesh.shape["fsdp"])


class DataParallelStrategy(MeshStrategy):
    """Pure sync data parallelism over every device (1 axis: dp).

    The reference's ``MultiWorkerMirroredStrategy``/``MirroredStrategy``
    equivalent (SURVEY.md §2c "Data parallel, sync all-reduce").
    """

    def __init__(self, devices=None):
        super().__init__(mesh=make_mesh(MeshSpec(dp=-1), devices=devices))


class FSDPStrategy(MeshStrategy):
    """Data parallelism with parameters fully sharded over the same devices.

    No reference analogue (TFoS mirrors variables); this is the TPU-idiomatic
    way to fit models larger than one chip's HBM while keeping the
    data-parallel programming model.  Parameters shard on their largest axis
    over ``fsdp``; XLA all-gathers them per layer (and frees after use).
    """

    def __init__(self, devices=None, min_shard_size: int = 2 ** 12):
        super().__init__(mesh=make_mesh(MeshSpec(dp=1, fsdp=-1), devices=devices))
        self.min_shard_size = min_shard_size
        self.rules = _fsdp_rules(self.mesh, min_shard_size)


def _fsdp_rules(mesh, min_shard_size: int) -> PartitionRules:
    """Shard every large-enough parameter on its first divisible axis."""

    class _AutoFSDP(PartitionRules):
        def __init__(self):
            self.n = mesh.shape["fsdp"]

        def tree_specs(self, params):
            def spec_for_leaf(leaf):
                if getattr(leaf, "size", 0) < min_shard_size:
                    return P()
                shape = getattr(leaf, "shape", ())
                for dim, extent in enumerate(shape):
                    if extent % self.n == 0 and extent >= self.n:
                        parts = [None] * len(shape)
                        parts[dim] = "fsdp"
                        return P(*parts)
                return P()

            return jax.tree.map(spec_for_leaf, params)

    return _AutoFSDP()


# tf.distribute-parity alias: the strategy name reference users know.
MultiWorkerMirroredStrategy = DataParallelStrategy


def cross_replica_mean(x, axis_name: str = "dp"):
    """``psum/size`` helper for code running under ``shard_map`` (the manual
    analogue of NCCL allreduce-mean)."""
    return jax.lax.pmean(x, axis_name)


def all_gather_batch(x, axis_name: str = "dp"):
    return jax.lax.all_gather(x, axis_name, tiled=True)
