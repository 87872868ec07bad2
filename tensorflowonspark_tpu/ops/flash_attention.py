"""Flash attention as a Pallas TPU kernel (forward + custom-VJP backward).

The reference has no attention code at all (its models are MNIST/ResNet
class — SURVEY.md §5 "long-context: absent"); this kernel is part of the
rebuild's TPU-first long-context story, alongside
``parallel.ring_attention``.  Design, per the Pallas guide:

- grid ``(batch, heads, q_blocks, k_blocks)``; the query block and its
  accumulators live in VMEM while the K/V blocks stream past with an
  online (numerically stable, one-pass) softmax, so the O(T²) score
  matrix is never materialised in HBM;
- scores/accumulators in float32 (MXU ``preferred_element_type``),
  activations bf16-friendly;
- causal masking skips the K blocks a query block cannot see (no
  compute, and no DMA: the block index is clamped to one already held);
- the backward pass recomputes probabilities from the saved logsumexp
  (flash-attention-2 style): one kernel for dQ (grid over query blocks),
  one for dK/dV (grid over key blocks) — no O(T²) residuals;
- K/V (and, in the dK/dV kernel, Q/dO) stream through the innermost grid
  axis one block at a time, so fast-memory use is independent of the
  sequence length; logsumexp/delta travel as lane-dense ``[B, H, 1, T]``
  rows;
- off-TPU the same kernels run under ``interpret=True`` so CPU tests
  exercise the identical code path.

Public entry point :func:`flash_attention` takes ``[batch, seq, heads,
head_dim]`` arrays — the same layout as ``models.bert.SelfAttention`` and
``parallel.ring_attention`` — plus an optional ``[batch, seq]`` key-padding
mask, and pads ragged sequence lengths to block multiples internally.
"""

from __future__ import annotations

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30  # large-negative mask value (avoids -inf − -inf = nan)
_EPS = 1e-30

#: committed on-chip block-size sweep (scripts/tpu_sweep.py stage_flash);
#: module-level so tests can point it elsewhere
_FLASH_SWEEP_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "bench_artifacts", "flash_sweep.json")


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


@functools.lru_cache(maxsize=1)
def _tuned_blocks() -> tuple[int, int]:
    """Default ``(block_q, block_k)``: the best point of the committed
    on-chip block sweep when one exists, else (512, 512).  Read once per
    process at first trace (``lru_cache``), so a sweep captured later
    takes effect on the next start — the same artifact-anchoring pattern
    as the scaling model's MFU table.

    Deliberately a single-point heuristic: the sweep tunes ONE shape
    (the artifact's ``shape`` field — B8 T2048 H16 D64 bf16 forward) and
    that best block is applied process-wide to every shape, window, and
    the backward pass.  ``_pick_block`` clamps it for shorter sequences,
    and callers with a known-different regime pass ``block_q``/``block_k``
    explicitly; a per-(seq, mode) table is not worth the compile-cache
    fragmentation until a measured shape shows the single point losing."""
    try:
        with open(_FLASH_SWEEP_PATH) as f:
            best = json.load(f).get("best_block")
        bq, bk = (int(x) for x in best.split("x"))
        assert bq > 0 and bk > 0
        return bq, bk
    # tfos: ignore[broad-except] — a missing/malformed sweep artifact falls
    # back to the measured default block sizes; never an error
    except Exception:
        return 512, 512


def _causal_mask(s, q0, k0, window=None, q_axis=0):
    """Mask ``s`` by absolute position; ``q0``/``k0`` are the first query
    and key position of the tile, ``q_axis`` the axis queries run along
    (0 for ``[q, k]`` scores, 1 for the dK/dV kernel's ``[k, q]``)."""
    q_pos = q0 + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    keep = q_pos >= k_pos
    if window is not None:  # sliding window: only the last `window` keys
        keep &= k_pos > q_pos - window
    return jnp.where(keep, s, NEG_INF)


def _k_span(Tk, causal, window, block_k):
    """Average keys actually visited per query (for cost estimates)."""
    if window is not None:
        return min(Tk, window + block_k)
    return max(block_k, Tk // 2) if causal else Tk


def _k_range(qi, block_q, block_k, nk, causal, window):
    """``(first, last)`` K block a query block attends to (inclusive):
    causal trims from above at the diagonal, a sliding window from
    below.  ``first > last`` means the query block sees no key at all."""
    if not causal:
        return 0, nk - 1
    last = jnp.minimum(nk - 1, (qi * block_q + block_q - 1) // block_k)
    if window is None:
        return 0, last
    return jnp.maximum(0, (qi * block_q - (window - 1)) // block_k), last


def _q_range(ki, block_q, block_k, nq, causal, window):
    """``(first, last)`` Q block that can see key block ``ki``."""
    if not causal:
        return 0, nq - 1
    first = (ki * block_k) // block_q
    if window is None:
        return first, nq - 1
    # queries beyond k_pos + window - 1 can't see this key block
    return first, jnp.minimum(
        nq - 1, (ki * block_k + block_k - 1 + window - 1) // block_q)


def _stream(rng):
    """Index of the block to fetch at inner grid step ``j``: ``j`` clamped
    into the visited range, so the steps a tile skips re-name a block that
    is already in fast memory and cost no DMA."""
    def index(j, outer):
        first, last = rng(outer)
        # last is always a valid block; an empty range (first > last)
        # clamps onto it
        return jnp.minimum(jnp.maximum(j, first), last)
    return index


_GRID_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


def _compiler_params(interpret):
    # K/V (and Q/dO in the dK/dV kernel) stream through the innermost grid
    # axis, which carries the accumulators and so must run in order
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=_GRID_SEMANTICS)}


def _scratch(*shapes):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM(s, jnp.float32) for s in shapes]


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
                nk, has_bias, window):
    bias_ref, o_ref, lse_ref, acc, m_s, l_s = \
        rest if has_bias else (None, *rest)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    first, last = _k_range(qi, block_q, block_k, nk, causal, window)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    def step():
        q = q_ref[0, 0]                                   # (bq, D)
        k_blk = k_ref[0, 0]                               # (bk, D)
        v_blk = v_ref[0, 0]
        s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:  # key-padding mask: one VPU pass over s
            s = s + bias_ref[0]                           # (1, bk)
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k, window)
        m = m_s[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_s[...] = alpha * l_s[...] + p.sum(axis=-1, keepdims=True)
        m_s[...] = m_new
        acc[...] = acc[...] * alpha + lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:  # K blocks above the diagonal / below the window: skipped
        pl.when((j >= first) & (j <= last))(step)
    else:
        step()

    @pl.when(j == nk - 1)
    def _finish():
        # A row whose keys are ALL masked keeps m pinned at NEG_INF (any
        # real score sits far above NEG_INF/2): without this check the
        # online softmax degenerates to p=exp(0)=1 on the masked scores and
        # the row silently returns the mean of V.  Emit zeros instead, and
        # push the row's lse to -NEG_INF so the backward's exp(s - lse)
        # underflows to exact zeros (delta is also 0 there since out==0, so
        # dq/dk/dv get no garbage).
        m = m_s[...]
        valid = m > NEG_INF * 0.5
        l = jnp.maximum(l_s[...], _EPS)
        o_ref[0, 0] = jnp.where(valid, acc[...] / l, 0.0).astype(o_ref.dtype)
        lse = jnp.where(valid, m + jnp.log(l), -NEG_INF)  # (bq, 1)
        lse_ref[0, 0] = lse.reshape(1, block_q)           # lane-dense row


def _fwd_impl(q, k, v, bias, causal, scale, block_q, block_k, interpret,
              window=None):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    nq, nk = Tq // block_q, Tk // block_k
    kj = _stream(lambda qi: _k_range(qi, block_q, block_k, nk, causal,
                                     window))
    blk = lambda bs, im: pl.BlockSpec(bs, im)  # noqa: E731
    in_specs = [
        blk((1, 1, block_q, D), lambda b, h, qi, j: (b, h, qi, 0)),
        blk((1, 1, block_k, D), lambda b, h, qi, j: (b, h, kj(j, qi), 0)),
        blk((1, 1, block_k, D), lambda b, h, qi, j: (b, h, kj(j, qi), 0)),
    ]
    args = (q, k, v)
    if bias is not None:
        in_specs.append(blk((1, 1, block_k),
                            lambda b, h, qi, j: (b, 0, kj(j, qi))))
        args += (bias,)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          has_bias=bias is not None, window=window),
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=[
            blk((1, 1, block_q, D), lambda b, h, qi, j: (b, h, qi, 0)),
            blk((1, 1, 1, block_q), lambda b, h, qi, j: (b, h, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
            # logsumexp rides the lane axis: a [..., T, 1] operand is
            # padded 128x in fast memory
            jax.ShapeDtypeStruct((B, H, 1, Tq), jnp.float32),
        ],
        scratch_shapes=_scratch((block_q, D), (block_q, 1), (block_q, 1)),
        cost_estimate=pl.CostEstimate(
            # banded paths do O(Tq·(window+block)) work, not O(Tq·Tk);
            # causal halves it — keep the scheduler's intensity model honest
            flops=4 * B * H * Tq * _k_span(Tk, causal, window, block_k) * D,
            transcendentals=B * H * Tq * _k_span(Tk, causal, window, block_k),
            bytes_accessed=q.dtype.itemsize * B * H * (Tq + Tk) * D * 2),
        interpret=interpret,
        **_compiler_params(interpret),
    )(*args)
    return out, lse


# --------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
               nk, has_bias, window):
    (bias_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc) = \
        rest if has_bias else (None, *rest)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    first, last = _k_range(qi, block_q, block_k, nk, causal, window)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step():
        q = q_ref[0, 0]
        k_blk = k_ref[0, 0]
        v_blk = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0].reshape(block_q, 1)           # row -> column
        delta = delta_ref[0, 0].reshape(block_q, 1)
        s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0]
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k, window)
        p = jnp.exp(s - lse)                               # (bq, bk)
        dp = lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[...] += lax.dot_general(ds.astype(k_blk.dtype), k_blk,
                                       (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    if causal:
        pl.when((j >= first) & (j <= last))(step)
    else:
        step()

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, *rest, scale, causal, block_q, block_k,
                nq, has_bias, window):
    (bias_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc,
     dv_acc) = rest if has_bias else (None, *rest)
    ki = pl.program_id(2)
    i = pl.program_id(3)
    first, last = _q_range(ki, block_q, block_k, nq, causal, window)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step():
        # scores are held transposed, [k, q]: logsumexp and delta arrive
        # as lane-dense rows and every product is a plain matmul
        k_blk = k_ref[0, 0]
        v_blk = v_ref[0, 0]
        q_blk = q_ref[0, 0]
        do_blk = do_ref[0, 0]
        st = lax.dot_general(k_blk, q_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            st = st + bias_ref[0].reshape(block_k, 1)
        if causal:
            st = _causal_mask(st, i * block_q, ki * block_k, window,
                              q_axis=1)
        pt = jnp.exp(st - lse_ref[0, 0])                   # (bk, bq)
        dv_acc[...] += lax.dot_general(
            pt.astype(do_blk.dtype), do_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_blk, do_blk, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, 0])
        dk_acc[...] += lax.dot_general(
            dst.astype(q_blk.dtype), q_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when((i >= first) & (i <= last))(step)
    else:
        step()

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, bias, out, lse, g, causal, scale, block_q, block_k,
              interpret, window=None):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    nq, nk = Tq // block_q, Tk // block_k
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1)[:, :, None, :]               # (B, H, 1, Tq)
    blk = lambda bs, im: pl.BlockSpec(bs, im)  # noqa: E731
    has_bias = bias is not None

    kj = _stream(lambda qi: _k_range(qi, block_q, block_k, nk, causal,
                                     window))
    dq_specs = [
        blk((1, 1, block_q, D), lambda b, h, qi, j: (b, h, qi, 0)),
        blk((1, 1, block_k, D), lambda b, h, qi, j: (b, h, kj(j, qi), 0)),
        blk((1, 1, block_k, D), lambda b, h, qi, j: (b, h, kj(j, qi), 0)),
    ]
    dq_args = (q, k, v)
    if has_bias:
        dq_specs.append(blk((1, 1, block_k),
                            lambda b, h, qi, j: (b, 0, kj(j, qi))))
        dq_args += (bias,)
    dq_specs += [
        blk((1, 1, block_q, D), lambda b, h, qi, j: (b, h, qi, 0)),
        blk((1, 1, 1, block_q), lambda b, h, qi, j: (b, h, 0, qi)),
        blk((1, 1, 1, block_q), lambda b, h, qi, j: (b, h, 0, qi)),
    ]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          has_bias=has_bias, window=window),
        grid=(B, H, nq, nk),
        in_specs=dq_specs,
        out_specs=blk((1, 1, block_q, D), lambda b, h, qi, j: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
        scratch_shapes=_scratch((block_q, D)),
        interpret=interpret,
        **_compiler_params(interpret),
    )(*dq_args, g, lse, delta)

    qi_ = _stream(lambda ki: _q_range(ki, block_q, block_k, nq, causal,
                                      window))
    dkv_specs = [
        blk((1, 1, block_k, D), lambda b, h, ki, i: (b, h, ki, 0)),
        blk((1, 1, block_k, D), lambda b, h, ki, i: (b, h, ki, 0)),
        blk((1, 1, block_q, D), lambda b, h, ki, i: (b, h, qi_(i, ki), 0)),
    ]
    dkv_args = (k, v, q)
    if has_bias:
        dkv_specs.append(blk((1, 1, block_k), lambda b, h, ki, i: (b, 0, ki)))
        dkv_args += (bias,)
    dkv_specs += [
        blk((1, 1, block_q, D), lambda b, h, ki, i: (b, h, qi_(i, ki), 0)),
        blk((1, 1, 1, block_q), lambda b, h, ki, i: (b, h, 0, qi_(i, ki))),
        blk((1, 1, 1, block_q), lambda b, h, ki, i: (b, h, 0, qi_(i, ki))),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          has_bias=has_bias, window=window),
        grid=(B, H, nk, nq),
        in_specs=dkv_specs,
        out_specs=[
            blk((1, 1, block_k, D), lambda b, h, ki, i: (b, h, ki, 0)),
            blk((1, 1, block_k, D), lambda b, h, ki, i: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tk, D), v.dtype),
        ],
        scratch_shapes=_scratch((block_k, D), (block_k, D)),
        interpret=interpret,
        **_compiler_params(interpret),
    )(*dkv_args, g, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------- custom-VJP plumbing

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, bias, causal, scale, block_q, block_k, interpret,
           window):
    out, _ = _fwd_impl(q, k, v, bias, causal, scale, block_q, block_k,
                       interpret, window)
    return out


def _flash_fwd(q, k, v, bias, causal, scale, block_q, block_k, interpret,
               window):
    out, lse = _fwd_impl(q, k, v, bias, causal, scale, block_q, block_k,
                         interpret, window)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res, g):
    q, k, v, bias, out, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, bias, out, lse, g, causal, scale,
                           block_q, block_k, interpret, window)
    return dq, dk, dv, None if bias is None else jnp.zeros_like(bias)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------------------- public API

def flash_attention(q, k, v, mask=None, causal: bool = False,
                    scale: float | None = None, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool | None = None,
                    window: int | None = None):
    """Fused attention over ``[batch, seq, heads, head_dim]`` arrays.

    Drop-in for the dense path of ``models.bert.SelfAttention`` (pass it as
    ``BertConfig.attention_fn``) and numerically equivalent to
    ``parallel.ring_attention.reference_attention``.

    Args:
      q, k, v: ``[B, T, H, D]`` (q's T may differ from k/v's).
      mask: optional ``[B, Tk]`` bool key-padding mask (True = attend).  A
        row with *no* True keys yields zeros (and zero gradients), matching
        the "fully padded row" convention.
      causal: causal masking by absolute position.
      window: sliding-window (local) attention — each query attends to
        its last ``window`` keys only (itself included); requires
        ``causal=True``.  K blocks wholly outside the band are skipped,
        so compute is O(T·window) instead of O(T²/2).
      scale: score scale, default ``1/sqrt(D)``.
      block_q, block_k: kernel tile sizes (clamped to the padded seq len).
        Default None = the best point of the committed on-chip block
        sweep (``bench_artifacts/flash_sweep.json``) when one exists,
        else 512x512.  (That sweep and ``bench_artifacts/
        flash_attention.json`` predate the block-streaming kernel and the
        attached chip; the tile has not been re-tuned — ROADMAP C10.)
      interpret: run under the Pallas interpreter.  Default: only where
        the default backend is not a TPU (CPU tests); on a TPU the kernel
        is compiled by Mosaic or the call fails — there is no dense
        fallback.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if window is not None:
        if not causal:
            raise ValueError("window (sliding-window attention) requires "
                             "causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        window = int(window)
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    interpret = (not _on_tpu()) if interpret is None else interpret
    if block_q is None:
        block_q = _tuned_blocks()[0]
    if block_k is None:
        block_k = _tuned_blocks()[1]

    # BTHD → BHTD, pad both sequence axes to block multiples.
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    block_q, Tq_p = _pick_block(Tq, block_q)
    block_k, Tk_p = _pick_block(Tk, block_k)
    if Tq_p != Tq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Tq_p - Tq), (0, 0)))
    if Tk_p != Tk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Tk_p - Tk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Tk_p - Tk), (0, 0)))

    # Key-padding mask → additive f32 bias row (padded keys masked out).
    # No mask and no K padding → bias=None: the kernels skip the bias DMA
    # and the per-block VPU pass over the score matrix entirely.
    if mask is not None:
        bias = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
        bias = jnp.pad(bias, ((0, 0), (0, Tk_p - Tk)),
                       constant_values=NEG_INF)
    elif Tk_p != Tk:
        bias = jnp.zeros((B, Tk_p), jnp.float32).at[:, Tk:].set(NEG_INF)
    else:
        bias = None
    if bias is not None:
        bias = bias[:, None, :]                            # (B, 1, Tk)

    out = _flash(qt, kt, vt, bias, causal, scale, block_q, block_k,
                 interpret, window)
    return jnp.transpose(out[:, :, :Tq], (0, 2, 1, 3))


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pick_block(T: int, requested: int) -> tuple[int, int]:
    """Choose ``(block, padded_T)`` bounding pad waste to one 128-tile.

    Padding straight to a multiple of a large block nearly doubles compute
    for lengths just past a block boundary (T=520 → 1024 with 512-blocks);
    instead pad T to the next 128 multiple and take the largest block ≤
    ``requested`` that divides it.  Every block this yields is a multiple
    of 128, so the bias and logsumexp rows tile the lane axis exactly
    (a short ragged T=100 pads to one 128 block).  Only an explicit
    ``requested < 128`` gets a smaller, 8-aligned tile — what the
    interpret-mode tests use to walk several blocks of a tiny sequence.
    """
    if requested < 128:
        block = min(requested, _round_up(T, 8))
        return block, _round_up(T, block)
    T_p = _round_up(T, 128)
    for block in (requested, 512, 256, 128):
        if block <= requested and T_p % block == 0:
            return block, T_p
    return 128, T_p  # T_p is always a 128 multiple
