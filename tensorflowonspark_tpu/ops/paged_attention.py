"""Paged-attention decode as a Pallas TPU kernel: one query token per row
against the row's K/V pages where they lie in the pool.

The paged decode cache (``models.gpt``, ``kv_page_tokens``) keeps each
layer's K and V as a pool ``[P*pt, W]``: one token's ``Hkv`` heads of
``D`` side by side in a row of ``W = kv_row_width(Hkv, D)`` lanes, a page
of ``pt`` tokens being ``pt`` consecutive rows, and a per-row block table
``[B, npg]`` naming the physical page of each logical page (``P`` =
unallocated).  The plain path gathers every row's whole ``npg * pt``
position view, widens it to float32, re-lays it into ``[Hkv, D]`` tiles
and multiplies all of it, whatever the row's length.  This kernel reads
only what is live, as stored:

- the pools stay in HBM; for row ``b`` the kernel copies pages ``0 ..
  ceil(len_b / pt) - 1`` itself (``make_async_copy``, a chunk of pages per
  step, double-buffered, the next row's first chunk started under the
  current row's last), skipping a table entry equal to the sentinel;
- **no relayout per head.**  A head is ``D`` lanes of a ``W``-lane row, so
  each query head is laid at its K/V head's lanes of an otherwise zero
  row: ``Qt [R, W]``.  ``Qt . K^T`` on the MXU is then every head's scores
  at once, ``[R, tokens]`` (the zero lanes add nothing; bf16 products are
  exact in the float32 accumulator), and ``P . V`` gives ``[R, W]`` of
  which each row keeps its own head's ``D`` lanes.  Grouped heads (``G``
  query heads per K/V head) are ``G`` such blocks of rows against the
  same pages;
- one-pass (online) softmax with float32 running max, sum and accumulator
  as in :mod:`.flash_attention`; scale, mask and softmax in float32.  The
  probabilities meet a bfloat16 ``V`` as a high and a low bfloat16 half
  (two exact products, float32 accumulation), so nothing is rounded that
  the plain path does not round;
- positions ``>= len_b``, sentinel pages and the pad lanes ``>= Hkv*D`` are
  masked by selection, never by multiplication, so garbage there (NaN
  included) cannot reach the output; a row of length 0 (a parked row, or
  one with no page) costs no copy and returns zeros.

Off the TPU the kernel runs under ``interpret=True`` (its own tests; the
model takes the gather path there: ``models.gpt.attends_pages_in_place``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu.ops.flash_attention import _on_tpu

NEG_INF = -1e30  # as flash_attention: finite, so no -inf - -inf

#: tokens one step of the kernel attends over (whole pages; a lane tile)
CHUNK_TOKENS = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def sublane_tile(dtype) -> int:
    """Rows of one ``(sublane, 128)`` tile of ``dtype`` on the device:
    8 for float32, 16 for bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def pages_are_tiles(dtype, page_tokens: int) -> bool:
    """Whether a page of ``page_tokens`` rows of the pool is whole tiles,
    so a page can be copied out of the pool as it is stored."""
    return page_tokens % sublane_tile(dtype) == 0


def _kernel(len_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, acc_ref, sem, *, B, G, Hkv, D, pt, cp, P, scale):
    npg = bt_ref.shape[1]
    W = q_ref.shape[1]
    T = cp * pt
    R = acc_ref.shape[0]
    Hp = _round_up(Hkv, 8)
    dtype = kbuf.dtype
    live_lanes = Hkv * D
    body = live_lanes // 128 * 128      # lanes of whole live tiles

    # row h of a block of Hp rows owns head h's D lanes
    lane = lax.broadcasted_iota(jnp.int32, (Hp, W), 1)
    head = lax.broadcasted_iota(jnp.int32, (Hp, W), 0)
    own = (lane >= head * D) & (lane < (head + 1) * D) & (head < Hkv)

    def pages(b, c):
        """``(live, allocated, first pool row)`` per page of chunk ``c`` of
        row ``b``: live = inside the row's length and allocated."""
        n_pages = (len_ref[b] + pt - 1) // pt
        out = []
        for j in range(cp):
            pg = c * cp + j
            entry = bt_ref[b, jnp.minimum(pg, npg - 1)]
            ok = (entry >= 0) & (entry < P)
            out.append(((pg < n_pages) & ok, ok, pl.multiple_of(
                jnp.clip(entry, 0, P - 1) * pt, pt)))
        return out

    def copies(b, c, slot, act):
        """Start or await (``act``) the copies of chunk ``c`` of row ``b``
        into buffer ``slot``, a page's K and V each."""
        for j, (live, _, at) in enumerate(pages(b, c)):
            @pl.when(live)
            def _():
                for i, (pool, buf) in enumerate(((k_hbm, kbuf),
                                                 (v_hbm, vbuf))):
                    act(pltpu.make_async_copy(
                        pool.at[pl.ds(at, pt), :],
                        buf.at[slot, pl.ds(j * pt, pt), :],
                        sem.at[i, slot]))

    def start(b, c, slot):
        copies(b, c, slot, lambda copy: copy.start())

    def wait(b, c, slot):
        copies(b, c, slot, lambda copy: copy.wait())

    def scores(qt, slot):
        """``Qt . K^T`` over the live lanes: the whole live lane tiles as
        they are, the tile that holds pad lanes with those selected out."""
        nt = (((1,), (1,)), ((), ()))
        s = None
        if body:
            s = lax.dot_general(qt[:, :body], kbuf[slot, :, :body], nt,
                                preferred_element_type=jnp.float32)
        if live_lanes > body:
            tail = kbuf[slot, :, body:body + 128]
            keep = lax.broadcasted_iota(jnp.int32, tail.shape, 1) \
                < live_lanes - body
            st = lax.dot_general(
                qt[:, body:body + 128],
                jnp.where(keep, tail, jnp.zeros_like(tail)), nt,
                preferred_element_type=jnp.float32)
            s = st if s is None else s + st
        return s

    def row(b, carry):
        slot0, primed = carry
        n_tok = len_ref[b]
        n_pages = (n_tok + pt - 1) // pt
        n_chunks = (n_pages + cp - 1) // cp
        nb = jnp.minimum(b + 1, B - 1)

        @pl.when((n_chunks > 0) & (primed == 0))
        def _():
            start(b, 0, slot0)

        blocks = [jnp.where(own, jnp.broadcast_to(
            q_ref[pl.ds(b * G + g, 1), :], (Hp, W)), 0.0) for g in range(G)]
        if R > G * Hp:
            blocks.append(jnp.zeros((R - G * Hp, W), jnp.float32))
        qt = jnp.concatenate(blocks, axis=0).astype(dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def chunk(c, ml):
            m, l = ml
            slot = (slot0 + c) % 2
            # the next chunk: this row's, or the next row's first
            last = c + 1 == n_chunks

            @pl.when(jnp.logical_not(last) | (b + 1 < B))
            def _():
                start(jnp.where(last, nb, b), jnp.where(last, 0, c + 1),
                      1 - slot)

            wait(b, c, slot)
            tok_row = c * T + lax.broadcasted_iota(jnp.int32, (1, T), 1)
            tok_col = c * T + lax.broadcasted_iota(jnp.int32, (T, 1), 0)
            valid = tok_row < n_tok
            valid_col = tok_col < n_tok
            holes = jnp.int32(0)
            for j, (_, ok, _) in enumerate(pages(b, c)):
                lo, hi = c * T + j * pt, c * T + (j + 1) * pt
                valid &= ok | (tok_row < lo) | (tok_row >= hi)
                valid_col &= ok | (tok_col < lo) | (tok_col >= hi)
                holes += jnp.where(ok, 0, 1)

            # a chunk with unlive rows: what lies there (stale buffer,
            # unwritten positions) must not meet a zero probability
            @pl.when(((c + 1) * T > n_tok) | (holes > 0))
            def _():
                v = vbuf[slot]
                vbuf[slot] = jnp.where(valid_col, v, jnp.zeros_like(v))

            s = jnp.where(valid, scores(qt, slot) * scale, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            v = vbuf[slot]
            if v.dtype == jnp.float32:
                pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
            else:
                hi = p.astype(v.dtype)
                lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
                pv = jnp.dot(jnp.concatenate([hi, lo], axis=0), v,
                             preferred_element_type=jnp.float32)
                pv = pv[:R] + pv[R:]
            acc_ref[...] = alpha * acc_ref[...] + pv
            return m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True)

        _, l = lax.fori_loop(
            0, n_chunks, chunk,
            (jnp.full((R, 1), NEG_INF, jnp.float32),
             jnp.zeros((R, 1), jnp.float32)))
        ctx = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
        for g in range(G):
            o_ref[pl.ds(b * G + g, 1), :] = jnp.sum(
                jnp.where(own, ctx[g * Hp:(g + 1) * Hp], 0.0), axis=0,
                keepdims=True)
        primed = (n_chunks > 0) & (b + 1 < B) & (len_ref[nb] > 0)
        return (slot0 + n_chunks) % 2, primed.astype(jnp.int32)

    lax.fori_loop(0, B, row, (jnp.int32(0), jnp.int32(0)))


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths, *,
                           num_kv_heads: int, page_tokens: int,
                           interpret: bool | None = None):
    """Attention of one query token per row over the row's live pages.

    Args:
      q: ``[B, H, D]``, the step's queries (rotated and normalised as the
        model has them).
      k_pool, v_pool: ``[P*pt, W]``, one layer's pools as stored, the
        step's own K/V already written.
      block_table: ``[B, npg]`` int32, logical page -> physical page;
        an entry outside ``[0, P)`` is unallocated and is skipped.
      lengths: ``[B]`` int32, positions each row attends (``0 ..
        len - 1``); 0 = the row costs nothing and returns zeros.
      num_kv_heads: ``Hkv``; ``H`` is a multiple of it.
      page_tokens: ``pt``; a page must be whole tiles of the pool's
        dtype (:func:`pages_are_tiles`).
      interpret: run under the Pallas interpreter.  Default: only where
        the default backend is not a TPU.

    Returns ``[B, H, D]`` float32.
    """
    B, H, D = q.shape
    Hkv, pt = int(num_kv_heads), int(page_tokens)
    rows, W = k_pool.shape
    if v_pool.shape != k_pool.shape or v_pool.dtype != k_pool.dtype:
        raise ValueError(f"K pool {k_pool.shape} {k_pool.dtype} and V pool "
                         f"{v_pool.shape} {v_pool.dtype} differ")
    if H % Hkv or Hkv * D > W or W % 128 or rows % pt:
        raise ValueError(
            f"q {q.shape} with {Hkv} K/V heads does not fit a pool of "
            f"{rows} rows of {W} lanes in pages of {pt}")
    if not pages_are_tiles(k_pool.dtype, pt):
        raise ValueError(
            f"a page of {pt} {k_pool.dtype} rows is not whole "
            f"({sublane_tile(k_pool.dtype)}, 128) tiles")
    return _attend(q, k_pool, v_pool, block_table, lengths, Hkv=Hkv, pt=pt,
                   interpret=(not _on_tpu()) if interpret is None
                   else bool(interpret))


# a program calls this once per attention layer with the same shapes: as
# a jitted function of its own it is traced and lowered once per program,
# not once per layer (and an eager caller compiles it once)
@functools.partial(jax.jit, static_argnames=("Hkv", "pt", "interpret"))
def _attend(q, k_pool, v_pool, block_table, lengths, *, Hkv, pt, interpret):
    B, H, D = q.shape
    W = k_pool.shape[1]
    G, P = H // Hkv, k_pool.shape[0] // pt
    cp = max(1, CHUNK_TOKENS // pt)
    R = _round_up(G * _round_up(Hkv, 8), 16)

    # head k*G+g of row b at lanes k*D .. of row b*G+g: for G == 1 this is
    # q as it lies, padded to W
    qr = q.reshape(B, Hkv, G, D).swapaxes(1, 2).reshape(B * G, Hkv * D)
    qr = jnp.pad(qr.astype(jnp.float32), ((0, 0), (0, W - Hkv * D)))

    kernel = functools.partial(_kernel, B=B, G=G, Hkv=Hkv, D=D, pt=pt,
                               cp=cp, P=P, scale=D ** -0.5)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * G, W), jnp.float32),
        in_specs=[smem, smem, vmem, hbm, hbm],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((2, cp * pt, W), k_pool.dtype),
            pltpu.VMEM((2, cp * pt, W), v_pool.dtype),
            pltpu.VMEM((R, W), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2))],
        name="tfos_paged_decode_attention",
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_table.astype(jnp.int32), qr,
      k_pool, v_pool)
    return out[:, :Hkv * D].reshape(B, G, Hkv, D).swapaxes(1, 2) \
        .reshape(B, H, D)
