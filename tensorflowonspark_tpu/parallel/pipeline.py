"""Pipeline parallelism: GPipe-style microbatched execution over the ``pp`` axis.

The reference has no pipeline parallelism (SURVEY.md §2c: "Pipeline parallel
(PP): No"); its nearest notion of model distribution is variable placement
over parameter servers.  On TPU, pipelining is how a model taller than one
chip's HBM (or one ICI domain) scales across slices: each ``pp`` mesh shard
holds a contiguous block of layers ("stage"), microbatches stream through the
stages, and stage-to-stage activation transfer is a single neighbour
``ppermute`` riding ICI/DCN — never host memory.

Design (TPU-first, not a port of any GPU schedule runner):

- The model's repeated trunk is expressed as ONE ``stage_fn(params, x) -> y``
  plus a *stacked* parameter tree whose leading axis is the stage index.
  This is the same "scan over layers" layout XLA already favours for big
  models; stacking is what lets a single SPMD program hold every stage.
- :func:`pipeline_apply` wraps the schedule in ``shard_map`` over ``pp``:
  each device slices out its own stage's parameters, runs the classic GPipe
  fill/steady/drain loop as a ``lax.scan`` over ``num_microbatches +
  num_stages - 1`` ticks, and rotates activations with a circular
  ``ppermute``.  Everything is compiled — no host-side scheduler process,
  no per-microbatch Python (contrast: GPU frameworks' runtime schedulers).
- The wrapped function is **differentiable**: ``jax.grad`` through
  ``shard_map``/``ppermute``/``scan`` yields exactly the reverse schedule
  (activation grads ppermute backwards through the stages), so the strategy
  layer reuses the ordinary ``value_and_grad`` + optax train step.  Each
  device materialises gradients only for its own stage block.
- Composes with data parallelism outside the ``shard_map``: the batch stays
  sharded over ``dp``/``fsdp`` and XLA inserts the gradient all-reduce for
  the mean loss as usual (GSPMD resumes at the shard_map boundary).

Bubble fraction is the GPipe bound (S-1)/(M+S-1); pick
``num_microbatches >= 4 * num_stages`` to keep it under ~20%.

Two schedules share this layout:

- :func:`pipeline_apply` + ``jax.grad`` — GPipe: simplest composition,
  but differentiating the forward scan retains one boundary activation
  per tick, O(M + S) per stage, so memory caps the microbatch count.
- :func:`pipeline_value_and_grad` — interleaved (1F1B-style): one
  forward AND one backward microbatch per tick with the loss head
  evaluated in-schedule, so a stage holds at most ``2S-1`` saved inputs
  regardless of M.  Raise M to shrink the bubble without growing
  activation memory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tensorflowonspark_tpu import compat
from tensorflowonspark_tpu.parallel import sharding as sh
from tensorflowonspark_tpu.parallel.mesh import MeshSpec, make_mesh
from tensorflowonspark_tpu.parallel.strategy import MeshStrategy, TrainState


def stack_stage_params(param_list):
    """Stack per-stage parameter trees into one tree with a leading stage axis.

    ``param_list`` is a list of identically-structured pytrees (one per
    stage); the result's every leaf gains dim 0 of size ``num_stages`` — the
    axis :func:`pipeline_apply` shards over ``pp``.
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *param_list)


def pipeline_spec(tree) -> object:
    """PartitionSpecs sharding every leaf's leading (stage) axis over ``pp``."""
    return jax.tree.map(lambda leaf: P("pp", *([None] * (leaf.ndim - 1))), tree)


def pipeline_apply(mesh, stage_fn, stage_params, x, *,
                   num_microbatches: int, axis_name: str = "pp",
                   remat: bool = True, param_specs=None, data_spec=None):
    """Run ``x`` through all pipeline stages; returns the final activations.

    Args:
      mesh: a mesh whose ``axis_name`` dimension is the stage count ``S``.
      stage_fn: ``(params, x) -> y`` for ONE stage, with ``y.shape ==
        x.shape`` (stages are homogeneous, as in a transformer trunk).
        Runs *inside* ``shard_map`` — any tensor parallelism within the
        stage must use explicit collectives over other mesh axes.
      stage_params: pytree whose leaves have leading axis ``S``
        (see :func:`stack_stage_params`).
      x: batch ``[B, ...]``; ``B`` must divide by ``num_microbatches``.
      remat: rematerialise each stage application on the backward pass
        (GPipe's per-microbatch checkpointing; memory ~O(M·act) → O(M·act)
        for boundaries only, stage internals recomputed).
      param_specs: optional pytree of ``PartitionSpec`` matching
        ``stage_params`` *without* the leading stage axis — how each leaf
        shards over the non-pp mesh axes inside a stage (e.g. Megatron
        ``P(None, "tp")`` column sharding; :mod:`.transformer` provides a
        ready-made stage).  Default: replicated within the stage.
      data_spec: optional ``PartitionSpec`` for ``x``'s non-batch dims,
        e.g. ``P(("dp","fsdp"), "sp", None)`` to keep the sequence sharded
        over ``sp`` through the pipeline (ring attention inside the stage).
        Default: batch over dp/fsdp, rest replicated.

    Differentiable; grads of ``stage_params`` come back with the same
    stacked layout (and the same within-stage sharding).
    """
    n_stages = mesh.shape[axis_name]
    if num_microbatches < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {num_microbatches}")
    batch = x.shape[0]
    data_shards = 1
    for ax in sh.DATA_AXES:
        data_shards *= mesh.shape.get(ax, 1)
    if batch % (num_microbatches * data_shards):
        raise ValueError(
            f"global batch {batch} must divide by num_microbatches "
            f"({num_microbatches}) x data shards ({data_shards}); each "
            f"dp/fsdp shard pipelines its own microbatches")
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    if param_specs is None:
        params_spec = pipeline_spec(stage_params)
    else:
        # prepend the stage axis to each within-stage spec
        params_spec = jax.tree.map(lambda s: P(axis_name, *s), param_specs,
                                   is_leaf=lambda s: isinstance(s, P))
    # Batch stays sharded over the data axes and replicated over pp: every
    # stage sees the full (local) batch but only stage 0 reads it.
    x_spec = data_spec if data_spec is not None \
        else P(sh.DATA_AXES, *([None] * (x.ndim - 1)))

    def schedule(block, x_local):
        # block: this device's [1, ...] slice of the stacked params.
        my_params = jax.tree.map(lambda p: jnp.squeeze(p, 0), block)
        stage = jax.lax.axis_index(axis_name)
        mb = x_local.shape[0] // num_microbatches
        x_mb = x_local.reshape((num_microbatches, mb) + x_local.shape[1:])
        n_ticks = num_microbatches + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            act, out = carry
            # Stage 0 injects microbatch t (clamped: ticks past the last
            # injection feed garbage that drains before the collect window).
            inject = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.minimum(t, num_microbatches - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, inject, act)
            y = fn(my_params, inp)
            # Last stage collects: tick t completes microbatch t-(S-1).
            out_idx = jnp.clip(t - (n_stages - 1), 0, num_microbatches - 1)
            valid = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
            written = jax.lax.dynamic_update_index_in_dim(out, y, out_idx, 0)
            out = jnp.where(valid, written, out)
            # Rotate activations one stage forward (stage 0's incoming value
            # is drain garbage, overwritten by the next inject).
            act = jax.lax.ppermute(y, axis_name, perm)
            return (act, out), None

        # Initial carries derive from x (device-varying over the data axes)
        # and are marked pp-varying explicitly: each stage's carry holds
        # different values, and shard_map's varying-axes check (vma) requires
        # the scan carry to declare that up front.
        act0 = compat.pcast(jnp.zeros_like(x_mb[0]), (axis_name,), to="varying")
        out0 = compat.pcast(jnp.zeros_like(x_mb), (axis_name,), to="varying")
        (_, out), _ = jax.lax.scan(tick, (act0, out0), jnp.arange(n_ticks))
        # Only the last stage holds real outputs; broadcast over pp so the
        # result is well-defined on every shard (and GSPMD can resume).
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
            axis_name)
        return out.reshape(x_local.shape)

    mapped = compat.shard_map(
        schedule, mesh=mesh,
        in_specs=(params_spec, x_spec), out_specs=x_spec)
    return mapped(stage_params, x)


def pipeline_value_and_grad(mesh, stage_fn, head_fn, stage_params,
                            head_params, x, targets, *,
                            num_microbatches: int, axis_name: str = "pp",
                            param_specs=None, data_spec=None,
                            head_specs=None, target_spec=None):
    """Interleaved (1F1B-style) pipelined train pass: loss AND grads in
    one schedule, with O(num_stages) in-flight activation residuals
    instead of :func:`pipeline_apply` + ``jax.grad``'s O(num_microbatches).

    Why a second schedule exists: differentiating the GPipe forward
    saves one boundary activation per tick — O(M + S) per stage — so
    the microbatch count that amortises the bubble is capped by memory.
    Here every tick runs ONE forward and ONE backward microbatch per
    stage (the 1F1B interleaving), so a stage only holds the inputs of
    microbatches whose backward hasn't caught up yet: a static circular
    buffer of ``2S-1`` — the lockstep-SPMD bound; the textbook S comes
    from asynchronous stage timing that a single compiled program cannot
    express — regardless of M.  Raising M then shrinks the bubble,
    (2S-2)/(M+2S-2), without growing activation memory.  Backward
    recomputes the stage forward from the saved input (the same remat
    GPipe mode uses), so compute per microbatch is identical.

    Masking is free by linearity: out-of-range ticks run the stage on
    garbage with a ZERO gradient seed, and ``vjp(0) == 0`` means they
    contribute nothing to parameter grads — no per-leaf ``where``.

    Args:
      stage_fn: ``(params, x) -> y`` for one stage, ``y.shape == x.shape``
        (runs inside ``shard_map``; tensor parallelism inside the stage
        uses explicit collectives, as in :func:`pipeline_apply`).
      head_fn: ``(head_params, y, target) -> scalar`` — the per-
        microbatch loss head, evaluated ON the last stage (its gradient
        seeds the backward).  The returned loss/grads are the MEAN over
        microbatches.
      stage_params: stacked per-stage tree (leading axis S).
      x: ``[B, ...]`` activations entering stage 0 (e.g. embedded ids);
        ``B`` must divide by ``num_microbatches`` x data shards.
      targets: ``[B, ...]`` per-sample targets consumed by ``head_fn``.

    Returns ``(loss, stage_grads, head_grads, dx)``: ``stage_grads``
    stacked like ``stage_params``, ``head_grads`` like ``head_params``
    (summed over the pipeline — replicated head), ``dx`` like ``x``
    (the gradient entering stage 0, for the embedding backward).
    """
    n_stages = mesh.shape[axis_name]
    if num_microbatches < 1:
        raise ValueError(
            f"num_microbatches must be >= 1, got {num_microbatches}")
    M = num_microbatches
    batch = x.shape[0]
    data_shards = 1
    for ax in sh.DATA_AXES:
        data_shards *= mesh.shape.get(ax, 1)
    if batch % (M * data_shards):
        raise ValueError(
            f"global batch {batch} must divide by num_microbatches "
            f"({M}) x data shards ({data_shards})")

    if param_specs is None:
        params_spec = pipeline_spec(stage_params)
    else:
        params_spec = jax.tree.map(lambda s: P(axis_name, *s), param_specs,
                                   is_leaf=lambda s: isinstance(s, P))
    x_spec = data_spec if data_spec is not None \
        else P(sh.DATA_AXES, *([None] * (x.ndim - 1)))
    # targets must shard like the activations they are compared against
    # in the in-schedule head (e.g. sequence over sp when data_spec
    # shards it); default: batch over the data axes only
    t_spec = target_spec if target_spec is not None \
        else P(sh.DATA_AXES, *([None] * (targets.ndim - 1)))
    h_spec = head_specs if head_specs is not None \
        else jax.tree.map(lambda _: P(), head_params)

    S = n_stages
    BUF = 2 * S - 1

    def schedule(block, hp, x_local, tgt_local):
        my_params = jax.tree.map(lambda p: jnp.squeeze(p, 0), block)
        stage = jax.lax.axis_index(axis_name)
        mb = x_local.shape[0] // M
        x_mb = x_local.reshape((M, mb) + x_local.shape[1:])
        t_mb = tgt_local.reshape((M, mb) + tgt_local.shape[1:])
        n_ticks = M + 2 * (S - 1)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [(i, (i - 1) % S) for i in range(S)]
        last = S - 1

        def head_loss(hp, y, t):
            return head_fn(hp, y, t) / M

        # pp (the schedule), the data axes (the batch), the axes the
        # activations are DECLARED sharded over (e.g. sp from a
        # sequence-sharding data_spec — the per-shard loss then averages
        # over them), and every SIZE-1 axis: forcing a size-1 axis
        # varying is semantically free and lets a stage's internal
        # collectives (e.g. the ring-attention scan's ppermute over sp
        # at sp=1) type-check — their carries inherit the input's vma.
        declared = set()
        for s in (x_spec, t_spec):
            for e in s:
                if isinstance(e, tuple):
                    declared |= set(e)
                elif e is not None:
                    declared.add(e)
        vary_axes = (axis_name,) + tuple(
            a for a in mesh.axis_names
            if a != axis_name and (a in sh.DATA_AXES or a in declared
                                   or mesh.shape[a] == 1))

        def pvary(z):
            # mark values varying over the axes the schedule makes them
            # vary on — pp plus the data axes — skipping axes a leaf
            # already varies over (the scan's vma check requires carry
            # input/output types to match exactly)
            def one(a):
                have = compat.vma_of(a)
                need = tuple(ax for ax in vary_axes if ax not in have)
                return compat.pcast(a, need, to="varying") if need else a
            return jax.tree.map(one, z)

        # differentiate w.r.t. FULLY-VARYING copies of the parameters:
        # the vma transpose rule for an unvarying input consumed in a
        # varying computation is an implicit psum over the missing axes,
        # which would (a) mix every stage's (mostly-garbage) head
        # gradient into each device's dhp before the seed_ok mask can
        # gate it, and (b) pre-SUM stage grads over the data shards,
        # turning the explicit pmean below into a no-op on already-equal
        # values (an n_data-times-too-large gradient)
        hp = pvary(hp)
        my_params = pvary(my_params)

        def tick(carry, t):
            act, grad, buf, dp, dhp, dx_out, loss = carry
            f = t - stage                       # fwd microbatch index
            b = t - 2 * (S - 1) + stage         # bwd microbatch index
            f_ok = jnp.logical_and(f >= 0, f < M)
            b_ok = jnp.logical_and(b >= 0, b < M)
            f_c = jnp.clip(f, 0, M - 1)
            b_c = jnp.clip(b, 0, M - 1)

            # ---- forward: stage 0 injects, others take the ppermuted act
            inp = jnp.where(stage == 0,
                            jax.lax.dynamic_index_in_dim(
                                x_mb, f_c, 0, keepdims=False), act)
            y = stage_fn(my_params, inp)
            # guard the residual write: drain ticks (f >= M, clipped to
            # M-1) would otherwise clobber slot (M-1) % BUF before its
            # backward has read it
            buf = jnp.where(
                f_ok,
                jax.lax.dynamic_update_index_in_dim(buf, inp, f_c % BUF, 0),
                buf)

            # ---- last stage: loss + gradient seed for THIS microbatch
            tgt = jax.lax.dynamic_index_in_dim(t_mb, f_c, 0, keepdims=False)
            (l_mb, (dhp_mb, dy)) = jax.value_and_grad(
                head_loss, argnums=(0, 1))(hp, y, tgt)
            seed_ok = jnp.logical_and(stage == last, f_ok)
            loss = loss + jnp.where(seed_ok, l_mb, 0.0)
            dhp = jax.tree.map(
                lambda a, g: a + jnp.where(seed_ok, g, 0), dhp, dhp_mb)

            # ---- backward: vjp of the recomputed stage forward on the
            # saved input; zero gradient seed on invalid ticks makes the
            # whole contribution vanish (linearity)
            x_in = jax.lax.dynamic_index_in_dim(buf, b_c % BUF, 0,
                                                keepdims=False)
            g_in = jnp.where(stage == last, dy, grad)
            g_in = jnp.where(b_ok, g_in, jnp.zeros_like(g_in))
            _, vjp_fn = jax.vjp(stage_fn, my_params, x_in)
            dp_mb, dx_mb = vjp_fn(g_in)
            dp = jax.tree.map(jnp.add, dp, dp_mb)
            write_dx = jnp.logical_and(stage == 0, b_ok)
            dx_out = jnp.where(
                write_dx,
                jax.lax.dynamic_update_index_in_dim(dx_out, dx_mb, b_c, 0),
                dx_out)

            act = jax.lax.ppermute(y, axis_name, fwd_perm)
            grad = jax.lax.ppermute(dx_mb, axis_name, bwd_perm)
            out = (act, grad, buf, dp, dhp, dx_out, loss)
            # normalize carry types: a stage collective can mark an
            # output varying over an axis the carry does not declare
            # (e.g. the ring-attention leg's ppermute marks sp-varying
            # even at sp=1, where no psum restores invariance).  A
            # size-1 psum is the identity and exactly cancels the vma
            # artifact; a size>1 leak is a REAL unreduced partial and
            # must be declared instead.
            return jax.tree.map(_norm, out, ref_vma), None

        def _norm(o, ref):
            extra = tuple(a for a in compat.vma_of(o) if a not in ref)
            for a in extra:
                if mesh.shape[a] != 1:
                    raise ValueError(
                        f"1f1b carry became varying over mesh axis {a!r} "
                        f"(size {mesh.shape[a]}) — a stage collective "
                        "produced an unreduced partial; declare the axis "
                        "in param_specs/data_spec or reduce it inside "
                        "stage_fn")
            return jax.lax.psum(o, extra) if extra else o

        carry0 = (
            pvary(jnp.zeros_like(x_mb[0])),                    # act
            pvary(jnp.zeros_like(x_mb[0])),                    # grad
            pvary(jnp.zeros((BUF, mb) + x_local.shape[1:],
                            x_local.dtype)),                   # buf
            pvary(jax.tree.map(jnp.zeros_like, my_params)),    # dp
            pvary(jax.tree.map(lambda h: jnp.zeros(h.shape, h.dtype),
                               hp)),                           # dhp
            pvary(jnp.zeros_like(x_mb)),                       # dx_out
            pvary(jnp.zeros((), jnp.float32)),                 # loss
        )
        ref_vma = jax.tree.map(
            lambda a: compat.vma_of(a), carry0)
        (_, _, _, dp, dhp, dx_out, loss), _ = jax.lax.scan(
            tick, carry0, jnp.arange(n_ticks))

        # loss lives on the last stage, dx on stage 0, head grads on the
        # last stage; psum the masked values over pp so every shard
        # agrees.  The reductions the outer autodiff would normally
        # insert are explicit here: every output is pmean'd over exactly
        # the axes it still varies on beyond what its out_spec shards
        # over — the data axes (the global batch mean); any OTHER leaked
        # axis (a stage collective's vma artifact) must be size 1, where
        # the pmean is a no-op.  dx stays per-shard (each shard's own
        # rows) but scales by the same 1/n_data the global mean applies.
        def spec_axes(s):
            axes = set()
            for e in s:
                if isinstance(e, tuple):
                    axes |= set(e)
                elif e is not None:
                    axes.add(e)
            return axes

        def fit(g, allowed):
            have = compat.vma_of(g)
            extra = tuple(a for a in have if a not in allowed)
            for a in extra:
                # data axes and declared activation axes average away
                # (equal-sized shards of a row-mean loss); anything else
                # of size > 1 is an unreduced partial — a bug
                if (a not in sh.DATA_AXES and a not in declared
                        and mesh.shape[a] != 1):
                    raise ValueError(
                        f"1f1b output varies over mesh axis {a!r} "
                        f"(size {mesh.shape[a]}) that its out_spec does "
                        "not shard over — declare it in param_specs/"
                        "data_spec/head_specs, or keep that axis out of "
                        "the stage")
            return jax.lax.pmean(g, extra) if extra else g

        def fit_tree(tree, specs, extra_allowed=frozenset()):
            flat_g, tdef = jax.tree.flatten(tree)
            flat_s = jax.tree.flatten(
                specs, is_leaf=lambda s: isinstance(s, P))[0]
            return jax.tree.unflatten(
                tdef, [fit(g, spec_axes(s) | extra_allowed)
                       for g, s in zip(flat_g, flat_s)])

        loss = fit(jax.lax.psum(
            jnp.where(stage == last, loss, 0.0), axis_name), set())
        dhp = fit_tree(
            jax.tree.map(
                lambda g: jax.lax.psum(
                    jnp.where(stage == last, g, jnp.zeros_like(g)),
                    axis_name),
                dhp),
            h_spec)
        dp = jax.tree.map(
            lambda g: g[None],
            fit_tree(dp, jax.tree.map(
                lambda s: P(*s[1:]), params_spec,
                is_leaf=lambda s: isinstance(s, P)),   # specs sans pp...
                extra_allowed=frozenset((axis_name,))))  # ...but pp stays
        # dx keeps every axis x is declared sharded over, so unlike the
        # pmean'd grads it must apply the FULL global-mean divisor
        # itself: data shards times any declared non-data shards (e.g.
        # sp sequence shards — the per-shard head is a local mean and
        # the global loss averages over those shards too)
        dx_div = data_shards
        for a in spec_axes(x_spec):
            if a not in sh.DATA_AXES and a != axis_name \
                    and a in mesh.axis_names:
                dx_div *= mesh.shape[a]
        dx = fit(jax.lax.psum(
            jnp.where(stage == 0, dx_out, jnp.zeros_like(dx_out)),
            axis_name), spec_axes(x_spec)).reshape(x_local.shape) / dx_div
        return loss, dp, dhp, dx

    mapped = compat.shard_map(
        schedule, mesh=mesh,
        in_specs=(params_spec, h_spec, x_spec, t_spec),
        out_specs=(P(), params_spec, h_spec, x_spec))
    return mapped(stage_params, head_params, x, targets)


class _PipelineRules:
    """Partition rules: leaves under the ``stages`` subtree shard their
    leading (stage) axis over ``pp``; everything else replicates."""

    def tree_specs(self, params):
        def spec(path, leaf):
            keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
            if "stages" in keys and getattr(leaf, "ndim", 0) >= 1:
                return P("pp", *([None] * (leaf.ndim - 1)))
            return P()

        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        return jax.tree_util.tree_unflatten(
            treedef, [spec(p, l) for p, l in flat])

    def tree_shardings(self, mesh, params):
        return jax.tree.map(lambda s: NamedSharding(mesh, s),
                            self.tree_specs(params),
                            is_leaf=lambda x: isinstance(x, P))


class PipelineStrategy(MeshStrategy):
    """Train a stage-stacked model with GPipe pipelining (+ optional DP).

    Usage::

        strat = PipelineStrategy(stage_fn, num_stages=4, num_microbatches=16)
        state = strat.init_state(init_fn, tx)     # init_fn returns
                                                  # {"stages": stacked, ...}
        step = strat.build_train_step(loss_fn)    # loss_fn uses strat.apply

    ``init_fn`` must return a dict with a ``"stages"`` entry holding the
    stacked per-stage parameters (leading axis = ``num_stages``); any other
    entries (embedders, heads) are replicated.  Inside ``loss_fn``, run the
    trunk with ``strategy.apply(params["stages"], x)``.

    Reference parity note: this is net-new capability (SURVEY.md §2c reserves
    the ``pp`` axis); the API mirrors the other strategies so it slots into
    the same ``map_fun`` contract.
    """

    def __init__(self, stage_fn, *, num_stages: int, num_microbatches: int | None = None,
                 devices=None, remat: bool = True, **axis_sizes):
        if "pp" in axis_sizes:
            raise ValueError("pass num_stages=, not pp= (they are the same axis)")
        axis_sizes.setdefault("dp", -1)
        mesh = make_mesh(MeshSpec(**{"pp": num_stages, **axis_sizes}),
                         devices=devices)
        super().__init__(mesh=mesh, rules=_PipelineRules())
        self.stage_fn = stage_fn
        self.num_stages = num_stages
        self.num_microbatches = (num_microbatches if num_microbatches is not None
                                 else 4 * num_stages)
        self.remat = remat

    def apply(self, stage_params, x):
        return pipeline_apply(self.mesh, self.stage_fn, stage_params, x,
                              num_microbatches=self.num_microbatches,
                              remat=self.remat)

    def build_train_step_1f1b(self, head_fn, tx=None, donate: bool = True,
                              *, param_specs=None, data_spec=None,
                              head_specs=None, target_spec=None):
        """Compile ``state, (x, targets) -> state, metrics`` on the
        interleaved (1F1B-style) schedule.

        Unlike :meth:`build_train_step` (GPipe trunk + free-form
        ``loss_fn`` differentiated by AD), the interleaved schedule must
        evaluate the loss IN-SCHEDULE, so the loss factors as
        ``head_fn(head_params, y, targets)`` on the final activations —
        ``head_params`` is every entry of ``state.params`` except
        ``"stages"``.  The payoff: O(2S-1) in-flight residuals instead
        of O(M+S), so ``num_microbatches`` scales at fixed memory.
        The batch is the tuple ``(x, targets)`` with leading batch
        dims; returned grads update stages AND head through the usual
        optax transform."""
        import optax

        tx = tx or getattr(self, "_tx", None)
        assert tx is not None, "pass tx= or call init_state first"
        if param_specs is None and any(
                self.mesh.shape.get(a, 1) > 1 for a in ("tp", "sp", "ep")):
            raise ValueError(
                "the mesh has within-stage axes "
                f"({dict(self.mesh.shape)}) but no param_specs/data_spec "
                "were given: a stage's collectives would run on replicated "
                "parameters and silently overcount — pass the stage's "
                "specs (e.g. make_transformer_stage's param_specs)")

        def step(state, batch):
            x, targets = batch

            def split(params):
                head = {k: v for k, v in params.items() if k != "stages"}
                return params["stages"], head

            stages, head = split(state.params)
            loss, d_stages, d_head, _ = pipeline_value_and_grad(
                self.mesh, self.stage_fn, head_fn, stages, head, x,
                targets, num_microbatches=self.num_microbatches,
                param_specs=param_specs, data_spec=data_spec,
                head_specs=head_specs, target_spec=target_spec)
            grads = {"stages": d_stages, **d_head}
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
            new_state = TrainState(params=params, opt_state=opt_state,
                                   step=state.step + 1,
                                   extras=state.extras)
            return new_state, {"loss": loss}

        return jax.jit(step, donate_argnums=(0,) if donate else ())

    @property
    def bubble_fraction(self) -> float:
        """GPipe idle fraction: (S-1)/(M+S-1)."""
        s, m = self.num_stages, self.num_microbatches
        return (s - 1) / (m + s - 1)
