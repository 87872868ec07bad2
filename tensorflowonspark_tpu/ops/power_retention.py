"""Power retention (Manifest AI, arXiv:2507.04239): attention whose weight
is an even power of the query-key product, gated by a learned per-token
decay, in its exact recurrent form with a fixed-size state.

For one key/value head with keys ``k_j``, values ``v_j``, log-decays ``g_j
<= 0`` and a query ``q_t`` (power 2, head size ``d``)::

    a_tj = exp(sum_{l=j+1..t} g_l) * (q_t . k_j / sqrt(d))**2,   j <= t
    y_t  = sum_j a_tj v_j / (sum_j a_tj + eps)

Since ``(q . k)**2 / d = phi(q) . phi(k)`` for the symmetric square
``phi``, the sums over ``j`` are a state that is carried::

    S_t = e^{g_t} S_{t-1} + v_t phi(k_t)^T        [d_v, F]
    z_t = e^{g_t} z_{t-1} + phi(k_t)              [F]
    y_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)

``phi`` here is the symmetric square TILED in blocks of :data:`BLOCK`
(:func:`phi`): of the ``d x d`` products ``a_i a_j`` it keeps, for each
block of rows, the columns from that block on, the diagonal block whole
(weight 1) and the blocks right of it once (weight sqrt 2), so ``F = BLOCK
* BLOCK * nb * (nb + 1) / 2`` (8704 for ``d = 128``: 5.4 % over the
``d (d + 1) / 2 = 8256`` of the untiled square) and every piece is a
contiguous slice: no gather.  The state keeps the VALUE axis on sublanes
and the FEATURE axis on lanes, ``[B, Hkv, d_v, F]`` float32, so that
``phi(k)`` and ``phi(q)`` are lane rows as XLA lays them and the update is
a broadcast multiply-add.

Three entry points:

- :func:`retention_step`: one decode step of every row.  On the TPU a
  Pallas kernel (device operation ``tfos_retention_step``) that reads each
  state tile ONCE, decays and updates it, multiplies it by the ``G`` query
  heads that share the key/value head, and writes it back IN PLACE
  (``input_output_aliases``); elsewhere :func:`retention_step_reference`,
  the same arithmetic in ``jax.numpy`` (three passes over the state).
- :func:`retention_chunked`: a block of tokens (a prefill, a training
  call): inside one call the attention form, the state only across calls.
  With ``c_t`` the running sum of ``g`` inside the call and ``(S_in,
  z_in)`` what the tokens before it left::

      y_t   = (sum_{j<=t in call} a_tj v_j + e^{c_t} S_in phi(q_t))
            / (sum_{j<=t in call} a_tj + e^{c_t} z_in . phi(q_t) + eps)
      S_out = e^{c_T} S_in + sum_s e^{c_T - c_s} v_s phi(k_s)^T

  The ``S_in``/``z_in`` terms, the only use of ``phi(q)``, are computed
  only when the incoming normaliser holds something; ``chunk`` bounds
  memory (the block of queries that attends the call's keys at once, and
  the block in which ``phi(k)`` is made and added into the state).
- :func:`retention_attention`: the attention form over a whole block with
  no state: the definition, which the other two are tested against.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu.ops.flash_attention import _on_tpu

#: side of the blocks the symmetric square is tiled in
BLOCK = 8

#: feature lanes one grid step of the kernel takes (whole 128-lane tiles;
#: must divide F).  A [128, 4352] float32 tile is 2.2 MB: in and out, each
#: double-buffered, stay under the 16 MiB a kernel may use by default
TILE_LANES = 4352

#: query heads per key/value head are padded to whole sublane tiles
_G_PAD = 8

_HIGHEST = lax.Precision.HIGHEST


def feature_dim(head_dim: int, block: int = BLOCK) -> int:
    """``F``: the length of :func:`phi` of a ``head_dim`` vector."""
    if head_dim % block:
        raise ValueError(f"head_dim ({head_dim}) must be a multiple of the "
                         f"retention block ({block})")
    nb = head_dim // block
    return block * block * nb * (nb + 1) // 2


def phi(a, block: int = BLOCK):
    """The tiled symmetric square of ``a [..., d]`` (float32): ``phi(a) .
    phi(b) == (a . b)**2 / d``.  Piece ``I`` holds ``a_i * a_j * w`` for
    ``i`` in block ``I`` and every ``j`` from that block's start on, ``w =
    1`` inside the block (both orders of a pair are there) and ``sqrt 2``
    right of it (each pair once)."""
    d = a.shape[-1]
    a = a.astype(jnp.float32)
    pieces = []
    for lo in range(0, d, block):
        w = jnp.concatenate([jnp.ones((block,), jnp.float32),
                             jnp.full((d - lo - block,), math.sqrt(2.0),
                                      jnp.float32)])
        piece = a[..., lo:lo + block, None] * (a[..., lo:] * w)[..., None, :]
        pieces.append(piece.reshape(a.shape[:-1] + (-1,)))
    return jnp.concatenate(pieces, axis=-1) * (d ** -0.5)


def init_state(batch: int, num_kv_heads: int, head_dim: int):
    """Zero state of ``batch`` rows: ``(S [B, Hkv, d, F], z [B, Hkv, F])``
    float32."""
    F = feature_dim(head_dim)
    return (jnp.zeros((batch, num_kv_heads, head_dim, F), jnp.float32),
            jnp.zeros((batch, num_kv_heads, F), jnp.float32))


def state_bytes(rows: int, num_kv_heads: int, head_dim: int) -> int:
    """Bytes of the state (S and z) of ``rows`` rows of one layer."""
    return 4 * rows * num_kv_heads * (head_dim + 1) * feature_dim(head_dim)


def state_passes() -> int:
    """Times :func:`retention_step` passes over the state, as this process
    runs it: read and written once through the kernel; the ``jax.numpy``
    arithmetic reads the updated state once more for the product."""
    return 2 if _on_tpu() else 3


# ------------------------------------------------------------- decode step

def retention_step_reference(state, z, q, k, v, g):
    """One step of every row in ``jax.numpy``: ``state [B, Hkv, d, F]``,
    ``z [B, Hkv, F]``, ``q [B, H, d]``, ``k``/``v [B, Hkv, d]``, ``g [B,
    Hkv]`` (log-decay) -> ``(num [B, H, d], den [B, H], state, z)``; the
    output is ``num / (den + eps)``."""
    B, H, d = q.shape
    Hkv = k.shape[1]
    fk = phi(k)                                           # [B, Hkv, F]
    fq = phi(q).reshape(B, Hkv, H // Hkv, -1)             # [B, Hkv, G, F]
    decay = jnp.exp(g.astype(jnp.float32))
    state = decay[..., None, None] * state \
        + v.astype(jnp.float32)[..., :, None] * fk[..., None, :]
    z = decay[..., None] * z + fk
    num = jnp.einsum("bmgf,bmdf->bmgd", fq, state, precision=_HIGHEST)
    den = jnp.einsum("bmgf,bmf->bmg", fq, z, precision=_HIGHEST)
    return num.reshape(B, H, d), den.reshape(B, H), state, z


def _step_kernel(decay_ref, s_ref, z_ref, fq_ref, fk_ref, vb_ref,
                 s_out, z_out, num_ref, den_ref, acc_ref, dacc_ref, *,
                 groups: int, num_kv_heads: int, lane_tiles: int):
    """One (row, key/value head, feature tile): decay and update the state
    tile, write it back, and add its part of the ``groups`` query heads'
    products to lane-wise accumulators; the last tile of a (row, head)
    reduces them over the lanes.  All of it is vector arithmetic (a matrix
    unit would see 5 columns), and the tile's copies in and out, not the
    arithmetic, set its time (my chip runs, PR 32: the same 2.08 ms a call
    however the tile is walked)."""
    b, m, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    decay = decay_ref[b * num_kv_heads + m]

    @pl.when(f == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        dacc_ref[...] = jnp.zeros_like(dacc_ref)

    vb = vb_ref[...]                        # [d, 128]: v down the sublanes
    parts = [None] * groups
    dpart = None
    for j in range(lane_tiles):
        sl = pl.ds(j * 128, 128)
        fk = fk_ref[:, sl]                  # [1, 128]
        s = decay * s_ref[:, sl] + vb * fk
        s_out[:, sl] = s
        zt = decay * z_ref[:, sl] + fk
        z_out[:, sl] = zt
        fq = fq_ref[:, sl]                  # [G_PAD, 128]
        for g in range(groups):
            p = s * fq[g:g + 1, :]
            parts[g] = p if parts[g] is None else parts[g] + p
        dp = fq * zt
        dpart = dp if dpart is None else dpart + dp
    for g in range(groups):
        acc_ref[g] += parts[g]
    dacc_ref[...] += dpart

    @pl.when(f == pl.num_programs(2) - 1)
    def _():
        lane = lax.broadcasted_iota(jnp.int32, num_ref.shape, 1)
        out = jnp.zeros(num_ref.shape, jnp.float32)
        for g in range(groups):
            col = jnp.sum(acc_ref[g], axis=1, keepdims=True)   # [d, 1]
            out = jnp.where(lane == g, col, out)
        num_ref[...] = out
        den_ref[...] = jnp.broadcast_to(
            jnp.sum(dacc_ref[...], axis=1, keepdims=True), den_ref.shape)


def _step_pallas(state, z, q, k, v, g, *, tile_lanes: int, interpret: bool):
    B, H, d = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    F = state.shape[-1]
    if d != 128 or G > _G_PAD or F % tile_lanes or tile_lanes % 128:
        raise ValueError(
            f"the retention step kernel wants head_dim 128, at most "
            f"{_G_PAD} query heads a key/value head and whole feature "
            f"tiles; got head_dim {d}, {G} a head, F {F} in tiles of "
            f"{tile_lanes}")
    fk = phi(k)[:, :, None, :]                              # [B, Hkv, 1, F]
    fq = phi(q).reshape(B, Hkv, G, F)
    fq = jnp.pad(fq, ((0, 0), (0, 0), (0, _G_PAD - G), (0, 0)))
    vb = jnp.broadcast_to(v.astype(jnp.float32)[..., None], (B, Hkv, d, 128))
    decay = jnp.exp(g.astype(jnp.float32)).reshape(B * Hkv)
    nf = F // tile_lanes
    kernel = functools.partial(_step_kernel, groups=G, num_kv_heads=Hkv,
                               lane_tiles=tile_lanes // 128)

    def tile(rows):
        return pl.BlockSpec((None, None, rows, tile_lanes),
                            lambda b, m, f, *_: (b, m, 0, f))

    def whole(rows):
        return pl.BlockSpec((None, None, rows, 128),
                            lambda b, m, f, *_: (b, m, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, Hkv, nf),
        in_specs=[tile(d), tile(1), tile(_G_PAD), tile(1), whole(d)],
        out_specs=[tile(d), tile(1), whole(d), whole(_G_PAD)],
        scratch_shapes=[pltpu.VMEM((G, d, 128), jnp.float32),
                        pltpu.VMEM((_G_PAD, 128), jnp.float32)])
    state, z4, num, den = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, 1, F), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, d, 128), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, _G_PAD, 128), jnp.float32)],
        # operands count the scalar-prefetch argument: 1 = state, 2 = z
        input_output_aliases={1: 0, 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="tfos_retention_step", interpret=interpret,
    )(decay, state, z[:, :, None, :], fq, fk, vb)
    num = num[..., :G].swapaxes(-1, -2).reshape(B, H, d)
    return num, den[:, :, :G, 0].reshape(B, H), state, z4[:, :, 0, :]


def retention_step(state, z, q, k, v, g, *, use_kernel: bool | None = None,
                   tile_lanes: int | None = None,
                   interpret: bool | None = None):
    """One decode step of every row (shapes as
    :func:`retention_step_reference`).  The kernel on the TPU (or where
    ``use_kernel`` asks for it: its own tests run it under the Pallas
    interpreter), the ``jax.numpy`` arithmetic elsewhere."""
    if use_kernel is None:
        use_kernel = _on_tpu()      # state_passes() counts on this rule
    if not use_kernel:
        return retention_step_reference(state, z, q, k, v, g)
    if tile_lanes is None:
        tile_lanes = TILE_LANES if state.shape[-1] % TILE_LANES == 0 \
            else state.shape[-1]
    return _step_pallas(state, z, q, k, v, g, tile_lanes=tile_lanes,
                        interpret=not _on_tpu() if interpret is None
                        else interpret)


# ----------------------------------------------------------- blocks of tokens

def _powers(q, k):
    """``(q_t . k_j / sqrt(d))**2`` for grouped heads: ``q [B, T, Hkv, G,
    d]``, ``k [B, S, Hkv, d]`` -> ``[B, Hkv, G, T, S]`` float32."""
    s = jnp.einsum("btmgd,bsmd->bmgts", q, k, precision=_HIGHEST) \
        * (q.shape[-1] ** -0.5)
    return s * s


def _decay(cq, ck, causal):
    """``exp(c_t - c_s)`` where ``causal [T, S]`` holds and 0 elsewhere:
    ``cq [B, Hkv, T]``, ``ck [B, Hkv, S]`` running sums of ``g`` ->
    ``[B, Hkv, T, S]``."""
    return jnp.where(causal, jnp.exp(jnp.where(
        causal, cq[..., :, None] - ck[..., None, :], 0.0)), 0.0)


def retention_attention(q, k, v, g, eps: float):
    """The attention form over a whole block, no state: ``q [B, T, H, d]``,
    ``k``/``v [B, T, Hkv, d]``, ``g [B, T, Hkv]`` -> ``[B, T, H, d]``
    float32."""
    B, T, H, d = q.shape
    Hkv = k.shape[2]
    q = q.astype(jnp.float32).reshape(B, T, Hkv, H // Hkv, d)
    k, v, g = (x.astype(jnp.float32) for x in (k, v, g))
    c = jnp.cumsum(g, axis=1).transpose(0, 2, 1)            # [B, Hkv, T]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    a = _powers(q, k) * _decay(c, c, causal)[:, :, None]
    num = jnp.einsum("bmgts,bsmd->btmgd", a, v, precision=_HIGHEST)
    den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)         # [B, T, Hkv, G]
    return (num / (den[..., None] + eps)).reshape(B, T, H, d)


def _from_state(state, z, qc, into):
    """What a carried state adds to a query block's sums: ``state``/``z``
    as in the step, ``qc [B, C, Hkv, G, d]``, ``into [B, Hkv, 1, C]`` the
    decay from the call's start to each query -> ``(num [B, Hkv, G, C, d],
    den [B, Hkv, G, C])``.  The one place a block of tokens makes
    ``phi(q)``."""
    fq = phi(qc)                                            # [B,C,Hkv,G,F]
    num = jnp.einsum("btmgf,bmdf->bmgtd", fq, state, precision=_HIGHEST)
    den = jnp.einsum("btmgf,bmf->bmgt", fq, z, precision=_HIGHEST)
    return into[..., None] * num, into * den


def retention_chunked(state, z, q, k, v, g, eps: float, chunk: int,
                      lengths=None):
    """A block of tokens (one call): ``state``/``z`` as in the step, ``q
    [B, T, H, d]``, ``k``/``v [B, T, Hkv, d]``, ``g [B, T, Hkv]``.  The
    call's tokens reach each other by the attention form; the tokens
    before the call reach them through ``state``/``z``, which are queried
    only where they hold something: the normaliser is a decayed sum of
    ``phi(k)``, whose diagonal entries are squares, so it is all zero
    exactly when no key has entered, and then (a fresh prompt, a training
    call) no ``phi(q)`` is made at all.  ``chunk`` bounds memory: ``T`` is
    cut into blocks of ``chunk`` tokens (the last is padded), a block of
    queries attends the call's keys at once (weights ``[B, Hkv, G, chunk,
    T]``), and ``phi(k)`` is made and added into the state a block at a
    time (``[B, chunk, Hkv, F]``).  ``lengths [B]``: valid tokens of each
    right-padded row; a pad token neither decays nor enters the state, so
    the state that comes back is the one after each row's last valid
    token.  Returns ``(y [B, T, H, d] float32, state, z)``."""
    B, T, H, d = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    q = q.astype(jnp.float32).reshape(B, T, Hkv, G, d)
    k, v, g = (x.astype(jnp.float32) for x in (k, v, g))
    if lengths is not None:
        valid = jnp.arange(T)[None, :] < lengths[:, None]   # [B, T]
        g = jnp.where(valid[..., None], g, 0.0)
        k = jnp.where(valid[..., None, None], k, 0.0)
    C = min(chunk, T)
    n = -(-T // C)
    pad = n * C - T
    if pad:
        # whole blocks for the scan: pad tokens as above (g 0, k 0)
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                   for x in (q, k, v))
        g = jnp.pad(g, ((0, 0), (0, pad), (0, 0)))
    c = jnp.cumsum(g, axis=1)               # [B, n*C, Hkv]: from the start
    ck = c.transpose(0, 2, 1)                               # [B, Hkv, n*C]
    end = c[:, -1]                                          # [B, Hkv]
    carried = jnp.any(z != 0)
    pos = jnp.arange(n * C)

    def blocks(x):      # [B, n*C, ...] -> [n, B, C, ...]
        return jnp.moveaxis(x.reshape((B, n, C) + x.shape[2:]), 1, 0)

    def one(carry, xs):
        S, zz = carry
        at, qc, kc, vc, cc = xs
        cq = cc.transpose(0, 2, 1)                          # [B, Hkv, C]
        w = _decay(cq, ck, at[:, None] >= pos[None, :])     # [B,Hkv,C,n*C]
        a = _powers(qc, k) * w[:, :, None]                  # [B,Hkv,G,C,n*C]
        num = jnp.einsum("bmgts,bsmd->bmgtd", a, v, precision=_HIGHEST)
        den = jnp.sum(a, axis=-1)
        into = jnp.exp(cq)[:, :, None, :]                   # [B,Hkv,1,C]
        snum, sden = lax.cond(
            carried, lambda: _from_state(state, z, qc, into),
            lambda: (jnp.zeros_like(num), jnp.zeros_like(den)))
        y = (num + snum) / ((den + sden)[..., None] + eps)  # [B,Hkv,G,C,d]
        # what each key still weighs at the call's end
        fk = phi(kc) * jnp.exp(end[:, None] - cc)[..., None]   # [B,C,Hkv,F]
        S = S + jnp.einsum("bsmd,bsmf->bmdf", vc, fk, precision=_HIGHEST)
        zz = zz + jnp.sum(fk, axis=1)
        return (S, zz), y.transpose(0, 3, 1, 2, 4)          # [B,C,Hkv,G,d]

    last = jnp.exp(end)
    (state, z), ys = lax.scan(
        one, (last[..., None, None] * state, last[..., None] * z),
        (pos.reshape(n, C),) + tuple(blocks(x) for x in (q, k, v, c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, n * C, H, d)[:, :T]
    return y, state, z
