"""Grouped matmul as a Pallas TPU kernel: rows sorted by group, each
touched group's weights streamed once a row tile.

The served expert layer (``models.moe.SparseMoE``) sorts its ``tokens x
k`` assignments by expert and multiplies the sorted rows ``[m, K]`` by the
experts' matrices ``[E, K, N]``, rows ``offset[e] .. offset[e] + counts[e]``
by matrix ``e``: ``jax.lax.ragged_dot``.  At a decode step's shapes (128
rows over 32 experts, ~4 rows each) that product is a pass over nearly
every expert's weights for a handful of rows, and XLA's ``ragged-dot``
makes it at about half the memory rate.  This kernel walks the *units* of
work, one per (row tile, group that has rows in it), in the order the rows
lie:

- the walk is made on the device from ``counts`` (:func:`_units`: a few
  integer operations, no host round trip) and reaches the kernel as
  scalar-prefetch operands: per unit the group whose weights it reads, its
  row tile, and its group's first row and end;
- a group with no rows is no unit, so its weights are never read; a
  touched group's matrix is streamed in tiles ``[K, tn]`` of the whole
  contraction dimension (the block pipeline double-buffers them: the next
  tile is copied under this one's products), once for every row tile the
  group has rows in: exactly once where the rows are one tile (a decode
  step), and the units after the last real one name its blocks again,
  which costs no copy;
- a unit multiplies the sub-blocks of ``ROWS`` rows of its tile that hold
  its group's rows against the weight tile on the matrix unit (operands as
  stored, float32 accumulation) and SELECTS its own rows into the output
  block; the first unit of a row tile zeroes the block.  Nothing is
  masked by multiplication: a row's product reads that row alone, so a
  neighbour's rows, the rows past ``sum(counts)`` (zeros, as
  ``ragged_dot`` gives) and the weights of an untouched group (NaN
  included) cannot reach the output;
- :func:`grouped_swiglu` is the same walk with two weight operands against
  the same rows and ``silu(gate) * up`` in float32 before the one cast:
  half the launches and row reads of two calls.  :func:`grouped_relu2` is
  the walk with one operand and ``relu(.)**2`` before the cast (an expert
  without a gate matrix): the same kernel, another epilogue.
- the weights enter AS THEY ARE STORED.  The device lays a parameter out
  from its shape alone, to waste the least padding, and a kernel's
  operand must be row-major: ``[E, K, N]`` is row-major as stored only
  where ``N`` is whole 128-lane tiles.  Where it is not (experts of width
  1856 = 14.5 tiles), the caller stores the matrices transposed, ``[E, N,
  K]`` with ``K`` whole tiles, and says ``transposed=True``: the product
  then contracts the last axis of both operands, which the matrix unit
  does as readily.  A program that passed ``[E, K, 1856]`` would re-lay
  all of a layer's experts on every call (a 160 MB copy a layer; what
  ``tests/test_chip_compile.py`` found).

Tiles are chosen from the shapes a call comes with (:func:`_tiles`).  Off
the TPU the kernel runs under ``interpret=True`` (its own tests; the model
keeps ``ragged_dot`` there: ``models.moe.streams_experts_once``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu.ops.flash_attention import _on_tpu, _round_up

#: rows one product of the kernel takes: the matrix unit's side
ROWS = 128

#: most sub-blocks of ``ROWS`` rows in a row tile: a group's weights are
#: read once for every row tile it has rows in, so a larger tile means
#: fewer passes, and its sub-blocks without rows of the group cost nothing
TILE_BLOCKS = 4

#: most bytes of weights one step streams (a tile ``[K, tn]`` of each
#: weight operand); the pipeline holds two steps' worth
WEIGHT_TILE_BYTES = 16 << 20


def _tiles(m: int, K: int, N: int, itemsize: int) -> tuple[int, int, int]:
    """``(rows, tm, tn)`` for a call of ``m`` rows against ``[K, N]``
    matrices of ``itemsize`` bytes an element over all the weight operands
    of a step: the sub-block of rows one product takes (``ROWS``, or all
    ``m`` rounded up to the packed sublane tile where there are fewer), the
    row tile (the most sub-blocks up to ``TILE_BLOCKS`` that divide the
    rows), and the widest weight tile of whole lane tiles that divides
    ``N`` within ``WEIGHT_TILE_BYTES`` (``N`` itself where it is not
    whole lane tiles: a block of the whole axis needs no alignment)."""
    rows = min(ROWS, _round_up(m, 16))
    blocks = -(-m // rows)
    per_tile = max(d for d in range(1, TILE_BLOCKS + 1) if blocks % d == 0)
    tn = N
    if N % 128 == 0:
        fits = [d * 128 for d in range(1, N // 128 + 1)
                if N % (d * 128) == 0
                and K * d * 128 * itemsize <= WEIGHT_TILE_BYTES]
        tn = max(fits, default=128)
    return rows, rows * per_tile, tn


def _units(counts, m_pad: int, tm: int):
    """The walk over ``(row tile, group)`` pairs in row order, from
    ``counts [E]``: per unit ``(weights, tile, first, end)``, each ``[U]``
    with ``U = m_pad // tm + E`` the most there can be: the group whose
    weights it reads, its row tile, and its group's first row and end.
    Rows past ``sum(counts)`` are a last group of their own so that every
    row tile is visited and zeroed; its units, and the units past the last
    real one (which repeat its tile), have no rows (``first == end``) and
    name the weights of the last group that has rows, so they copy and
    multiply nothing."""
    E = counts.shape[0]
    U = m_pad // tm + E
    counts = counts.astype(jnp.int32)
    end = jnp.cumsum(counts)
    lo = jnp.concatenate([end - counts, end[-1:]])
    hi = jnp.concatenate([end, jnp.full((1,), m_pad, jnp.int32)])
    tiles = jnp.where(hi > lo, (hi - 1) // tm - lo // tm + 1, 0)
    unit_end = jnp.cumsum(tiles)
    u = jnp.arange(U, dtype=jnp.int32)
    real = u < unit_end[-1]
    u = jnp.minimum(u, unit_end[-1] - 1)
    group = jnp.sum(unit_end[None, :] <= u[:, None], axis=1, dtype=jnp.int32)
    tile = lo[group] // tm + u - (unit_end - tiles)[group]
    rows = real & (group < E)
    touched = jnp.max(jnp.where(counts > 0, jnp.arange(E, dtype=jnp.int32),
                                0))
    return (jnp.where(rows, group, touched), tile,
            jnp.where(rows, lo[group], 0), jnp.where(rows, hi[group], 0))


def _kernel(_, tile_ref, first_ref, end_ref, x_ref, *refs, rows, tm,
            activation, transposed):
    w_refs, o_ref = refs[:-1], refs[-1]
    u = pl.program_id(1)
    t = tile_ref[u]

    @pl.when((u == 0) | (tile_ref[jnp.maximum(u - 1, 0)] != t))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # the group's rows inside this tile, and the sub-blocks that hold them
    lo = jnp.maximum(first_ref[u] - t * tm, 0)
    hi = jnp.minimum(end_ref[u] - t * tm, tm)

    def block(s, _):
        at = pl.multiple_of(s * rows, rows)
        x = x_ref[pl.ds(at, rows), :]
        y = [lax.dot_general(x, w[...],
                             (((1,), (1 if transposed else 0,)), ((), ())),
                             preferred_element_type=jnp.float32)
             for w in w_refs]
        if activation == "swiglu":
            y = jax.nn.silu(y[0]) * y[1]
        elif activation == "relu2":
            y = jnp.square(jnp.maximum(y[0], 0.0))
        else:
            y = y[0]
        row = at + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        o_ref[pl.ds(at, rows), :] = jnp.where(
            (row >= lo) & (row < hi), y.astype(o_ref.dtype),
            o_ref[pl.ds(at, rows), :])

    lax.fori_loop(lo // rows, (hi + rows - 1) // rows, block, None)


def _call(lhs, weights, counts, out_dtype, tiles, interpret,
          activation=None, transposed=False):
    """Refuse operands that do not fit, settle ``interpret`` (static under
    the jit below, so here) and make the call."""
    for w in weights:
        if w.ndim != 3 or w.shape != weights[0].shape \
                or w.dtype != lhs.dtype \
                or w.shape[2 if transposed else 1] != lhs.shape[1] \
                or counts.shape != (w.shape[0],):
            raise ValueError(
                f"rows {lhs.shape} {lhs.dtype} and counts {counts.shape} "
                f"do not fit weights {w.shape} {w.dtype}")
    return _grouped(lhs, tuple(weights), counts, activation=activation,
                    transposed=bool(transposed),
                    out_dtype=jnp.dtype(out_dtype), tiles=tiles,
                    interpret=(not _on_tpu()) if interpret is None
                    else bool(interpret))


def grouped_dot(lhs, rhs, counts, *, tiles=None, interpret=None):
    """``lhs [m, K]`` sorted by group times ``rhs [E, K, N]``, the first
    ``counts[0]`` rows by ``rhs[0]`` and so on; rows past ``sum(counts)``
    give zeros.  Operands as stored (one dtype), float32 accumulation,
    ``[m, N]`` float32: ``jax.lax.ragged_dot(lhs, rhs, counts,
    preferred_element_type=float32)`` up to the order of the float32 sums.

    ``tiles=(rows, tm, tn)`` overrides :func:`_tiles` (the tests' way to
    many tiles at small shapes); ``interpret`` runs the Pallas interpreter,
    by default only where the default backend is not a TPU."""
    return _call(lhs, (rhs,), counts, jnp.float32, tiles, interpret)


def grouped_swiglu(lhs, w_gate, w_up, counts, *, out_dtype,
                   transposed=False, tiles=None, interpret=None):
    """``silu(lhs . w_gate) * (lhs . w_up)`` group by group in one walk:
    both products as :func:`grouped_dot` makes them, the activation and
    the product in float32, then the one cast to ``out_dtype``.
    ``transposed``: both weights are ``[E, N, K]``."""
    return _call(lhs, (w_gate, w_up), counts, out_dtype, tiles, interpret,
                 "swiglu", transposed)


def grouped_relu2(lhs, w_up, counts, *, out_dtype, transposed=False,
                  tiles=None, interpret=None):
    """``relu(lhs . w_up)**2`` group by group: the product as
    :func:`grouped_dot` makes it, the activation in float32, then the one
    cast to ``out_dtype``.  ``transposed``: ``w_up`` is ``[E, N, K]``."""
    return _call(lhs, (w_up,), counts, out_dtype, tiles, interpret, "relu2",
                 transposed)


# a program calls this once or twice per expert layer with the same
# shapes: as a jitted function of its own it is traced and lowered once per
# program, not once per layer (``ops.paged_attention._attend``)
@functools.partial(jax.jit, static_argnames=("activation", "transposed",
                                             "out_dtype", "tiles",
                                             "interpret"))
def _grouped(lhs, weights, counts, *, activation, transposed, out_dtype,
             tiles, interpret):
    m, K = lhs.shape
    N = weights[0].shape[1 if transposed else 2]
    rows, tm, tn = tiles or _tiles(m, K, N,
                                   lhs.dtype.itemsize * len(weights))
    # whole row tiles (nothing to add at the served shapes)
    lhs = jnp.pad(lhs, ((0, _round_up(m, tm) - m), (0, 0)))
    meta = _units(counts, lhs.shape[0], tm)
    U = meta[0].shape[0]

    def x_map(j, u, weights, tile, first, end):
        return tile[u], 0

    def w_map(j, u, weights, tile, first, end):
        return (weights[u], j, 0) if transposed else (weights[u], 0, j)

    def o_map(j, u, weights, tile, first, end):
        return tile[u], j

    buffers = 2 * (tm * K * lhs.dtype.itemsize
                   + len(weights) * K * tn * lhs.dtype.itemsize
                   + tm * tn * out_dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, tm=tm,
                          activation=activation, transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((lhs.shape[0], N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=(N // tn, U),
            in_specs=[pl.BlockSpec((tm, K), x_map)]
            + [pl.BlockSpec((None, tn, K) if transposed else (None, K, tn),
                            w_map)] * len(weights),
            out_specs=pl.BlockSpec((tm, tn), o_map)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the pipeline's buffers, and as much again for the products
            # and what the compiler keeps beside them
            vmem_limit_bytes=max(32 << 20, 2 * buffers)),
        name="tfos_grouped_matmul",
        interpret=interpret,
    )(*meta, lhs, *weights)
    return out[:m]
