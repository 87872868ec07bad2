"""The Mamba-2 state-space recurrence (Dao & Gu, arXiv:2405.21060) with a
fixed-size float32 state per row: the decode step as a Pallas TPU kernel,
and the chunked scan for a block of tokens.

For one head ``h`` of ``P`` values in group ``g = h // (H / G)`` with a
state ``S_h [P, N]``, a token brings ``x_h [P]``, a step size ``dt_h > 0``,
and the group's ``B_g``, ``C_g [N]``; with ``A_h < 0`` one scalar a head::

    a_h = exp(dt_h * A_h)
    S_h = a_h * S_h + (dt_h * x_h) B_g^T
    y_h = S_h C_g                                   (+ D_h x_h, the caller's)

THE STATE'S LAYOUT is ``[B, G, N, (H/G) * P]``: the state axis ``N`` down
the sublanes, and along the lanes a group's heads side by side, each with
its ``P`` values (``[B, 8, 128, 512]`` for 64 heads of 64 in 8 groups: 2.1
MB a row).  So ``x``, ``dt``, ``a`` and ``y`` are lane rows ``[G, (H/G)*P]``
exactly as the projections make them (``[B, H*P]`` reshaped), the update is
``B_g`` down the sublanes times a row, and ``S C`` is a sum over sublanes:
vreg adds, no lane reduction.  ``B_g`` and ``C_g`` are the only columns,
one pair a group.

Two entry points:

- :func:`ssm_step`: one decode step of every row.  On the TPU a Pallas
  kernel (device operation ``tfos_ssm_step``) that reads each row's state
  ONCE, decays and updates it, contracts it with ``C`` and writes it back
  IN PLACE (``input_output_aliases``); elsewhere
  :func:`ssm_step_reference`, the same arithmetic in ``jax.numpy``.
- :func:`ssm_chunked`: a block of tokens (a prefill, a full forward):
  inside a chunk the quadratic form, across chunks the state, float32::

      c_t   = sum_{l <= t in chunk} dt_l A              (running log-decay)
      y_t   = sum_{s <= t in chunk} e^{c_t - c_s} (C_t . B_s) dt_s x_s
            + e^{c_t} S_in C_t
      S_out = e^{c_Q} S_in + sum_s e^{c_Q - c_s} dt_s x_s B_s^T

  ``lengths [B]``: valid tokens of each right-padded row; a padded
  position has ``dt = 0``, so it neither decays nor feeds the state, and
  the state that comes back is the one after each row's last valid token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu.ops.flash_attention import _on_tpu

_HIGHEST = lax.Precision.HIGHEST

#: bytes of a state value
STATE_BYTES = 4


def state_shape(batch: int, heads: int, head_dim: int, groups: int,
                state_size: int) -> tuple:
    """``[B, G, N, (H/G) * P]`` (the module docstring has the reason)."""
    if heads % groups:
        raise ValueError(f"ssm heads ({heads}) must be a multiple of the "
                         f"groups ({groups})")
    return (batch, groups, state_size, heads // groups * head_dim)


def state_bytes(rows: int, heads: int, head_dim: int, state_size: int) -> int:
    """Bytes of the state of ``rows`` rows of one layer."""
    return STATE_BYTES * rows * heads * head_dim * state_size


# ------------------------------------------------------------- decode step

def _lane_rows(x, dt, a, groups: int):
    """``(decay, dt * x)`` as the state's lane rows ``[B, G, (H/G) * P]``:
    each head's decay repeated over its ``P`` values."""
    B, H, P = x.shape
    dx = (dt[..., None] * x.astype(jnp.float32)).reshape(B, groups, -1)
    decay = jnp.repeat(a.astype(jnp.float32), P, axis=-1)
    return decay.reshape(B, groups, -1), dx


def ssm_step_reference(state, x, dt, a, Bm, Cm):
    """One step of every row in ``jax.numpy``: ``state [B, G, N, L]`` (``L
    = (H/G) * P``), ``x [B, H, P]``, ``dt``/``a [B, H]`` (the step size
    and the decay ``exp(dt A)``), ``Bm``/``Cm [B, G, N]`` -> ``(y [B, H,
    P] float32, state)``."""
    B, H, P = x.shape
    decay, dx = _lane_rows(x, dt, a, Bm.shape[1])
    state = decay[:, :, None] * state \
        + Bm.astype(jnp.float32)[..., None] * dx[:, :, None]
    y = jnp.sum(state * Cm.astype(jnp.float32)[..., None], axis=2)
    return y.reshape(B, H, P), state


def _step_kernel(s_ref, a_ref, dx_ref, bc_ref, s_out, y_ref, *, groups: int,
                 lane_tiles: int):
    """One row: for each group, ``B_g`` and ``C_g`` as columns broadcast
    along the lanes once, then the group's state a lane tile ``[N, 128]``
    at a time: decay, rank-1 update, write back, and the sum over the
    sublanes of its product with ``C_g``.  All vector arithmetic; the
    copies in and out set the time."""
    n = bc_ref.shape[0]
    for g in range(groups):
        bcol = jnp.broadcast_to(bc_ref[:, g:g + 1], (n, 128))
        ccol = jnp.broadcast_to(bc_ref[:, groups + g:groups + g + 1],
                                (n, 128))
        for j in range(lane_tiles):
            sl = pl.ds(j * 128, 128)
            s = a_ref[g:g + 1, sl] * s_ref[g, :, sl] \
                + bcol * dx_ref[g:g + 1, sl]
            s_out[g, :, sl] = s
            y_ref[g:g + 1, sl] = jnp.sum(s * ccol, axis=0, keepdims=True)


def _step_pallas(state, x, dt, a, Bm, Cm, *, interpret: bool):
    B, H, P = x.shape
    G, N = Bm.shape[1:]
    L = H // G * P
    if state.shape != (B, G, N, L) or L % 128 or N % 8:
        raise ValueError(
            f"the ssm step kernel wants a state [B, G, N, (H/G)*P] with "
            f"whole lane tiles a group and whole sublane tiles of state; "
            f"got state {state.shape} for x {x.shape}, B {Bm.shape}")
    decay, dx = _lane_rows(x, dt, a, G)
    # the columns: [B, N, 2G], B_g at lane g and C_g at lane G + g
    bc = jnp.concatenate([Bm, Cm], axis=1).astype(jnp.float32) \
        .transpose(0, 2, 1)

    def row(*block):
        return pl.BlockSpec((None,) + block,
                            lambda b: (b,) + (0,) * len(block))

    block_bytes = STATE_BYTES * G * N * L
    state, y = pl.pallas_call(
        functools.partial(_step_kernel, groups=G, lane_tiles=L // 128),
        grid=(B,),
        in_specs=[row(G, N, L), row(G, L), row(G, L), row(N, 2 * G)],
        out_specs=[row(G, N, L), row(G, L)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, G, L), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # a row's state in and out, each double-buffered, and room
            # for the small operands and what the compiler keeps
            vmem_limit_bytes=max(32 << 20, 6 * block_bytes)),
        name="tfos_ssm_step", interpret=interpret,
    )(state, decay, dx, bc)
    return y.reshape(B, H, P), state


def ssm_step(state, x, dt, a, Bm, Cm, *, use_kernel: bool | None = None,
             interpret: bool | None = None):
    """One decode step of every row (shapes as :func:`ssm_step_reference`).
    The kernel on the TPU (or where ``use_kernel`` asks for it: its own
    tests run it under the Pallas interpreter), the ``jax.numpy``
    arithmetic elsewhere.  Either way the state is read once and written
    once."""
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        return ssm_step_reference(state, x, dt, a, Bm, Cm)
    return _step_pallas(state.astype(jnp.float32), x, dt, a, Bm, Cm,
                        interpret=not _on_tpu() if interpret is None
                        else interpret)


# ----------------------------------------------------------- blocks of tokens

def ssm_chunked(state, x, dt, A, Bm, Cm, chunk: int, lengths=None):
    """A block of tokens (one call): ``state [B, G, N, L]`` as in the
    step, ``x [B, T, H, P]``, ``dt [B, T, H]`` (step sizes, > 0), ``A
    [H]`` (< 0), ``Bm``/``Cm [B, T, G, N]``.  ``T`` is cut into chunks of
    ``chunk`` tokens (the last is padded with ``dt = 0``); ``lengths
    [B]``: valid tokens of each right-padded row.  Returns ``(y [B, T, H,
    P] float32, state)``; everything float32 at highest precision."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    x, dt, Bm, Cm = (v.astype(jnp.float32) for v in (x, dt, Bm, Cm))
    if lengths is not None:
        dt = jnp.where((jnp.arange(T)[None, :] < lengths[:, None])[..., None],
                       dt, 0.0)
    Q = min(chunk, T)
    n = -(-T // Q)
    pad = n * Q - T
    if pad:
        x, dt, Bm, Cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (v.ndim - 2)) for v in (x, dt, Bm, Cm))

    def chunks(v):      # [B, n*Q, ...] -> [n, B, Q, ...]
        return jnp.moveaxis(v.reshape((B, n, Q) + v.shape[2:]), 1, 0)

    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]

    def one(S, xs):
        xc, dtc, Bc, Cc = xs              # [B,Q,H,P] [B,Q,H] [B,Q,G,N] x2
        c = jnp.cumsum(dtc * A.astype(jnp.float32), axis=1)     # [B, Q, H]
        ch = c.transpose(0, 2, 1).reshape(B, G, R, Q)
        dx = (dtc[..., None] * xc).reshape(B, Q, G, R, P)
        # inside the chunk: e^{c_t - c_s} (C_t . B_s), s <= t
        cb = jnp.einsum("btgn,bsgn->bgts", Cc, Bc, precision=_HIGHEST)
        w = jnp.where(causal, jnp.exp(jnp.where(
            causal, ch[..., :, None] - ch[..., None, :], 0.0)), 0.0)
        y = jnp.einsum("bgrts,bsgrp->btgrp", w * cb[:, :, None], dx,
                       precision=_HIGHEST)
        # what the tokens before the chunk left
        Sg = S.reshape(B, G, N, R, P)
        y = y + jnp.exp(c).reshape(B, Q, G, R)[..., None] * jnp.einsum(
            "btgn,bgnrp->btgrp", Cc, Sg, precision=_HIGHEST)
        # the state at the chunk's end
        last = ch[..., -1]                                      # [B, G, R]
        left = jnp.exp(last[..., None] - ch).transpose(0, 3, 1, 2)
        Sg = jnp.exp(last)[:, :, None, :, None] * Sg + jnp.einsum(
            "bsgn,bsgrp->bgnrp", Bc, left[..., None] * dx,
            precision=_HIGHEST)
        return Sg.reshape(S.shape), y.reshape(B, Q, H, P)

    state, ys = lax.scan(one, state.astype(jnp.float32),
                         tuple(chunks(v) for v in (x, dt, Bm, Cm)))
    return jnp.moveaxis(ys, 0, 1).reshape(B, n * Q, H, P)[:, :T], state
