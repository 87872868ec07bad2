"""Ring attention: sequence/context parallelism over a mesh axis.

No reference analogue — the reference's models are MNIST/ResNet-class and
its max sequence length is "whatever fits one worker" (SURVEY.md §5).  This
rebuild treats long-context as first-class: the sequence dimension shards
over the ``sp`` mesh axis, each device holds its Q/K/V block, and K/V blocks
rotate around the ring via ``lax.ppermute`` while a numerically-stable
online softmax accumulates partial attention (the Ring Attention /
blockwise-attention construction).  Communication rides ICI neighbour links
— exactly what ``ppermute`` compiles to on a TPU torus — and overlaps with
the per-block attention compute.

Memory per device: O(T_local² · the block pair), so global sequence length
scales linearly with the number of ``sp`` devices.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu import compat

NEG_INF = -1e30  # large-negative mask value (avoids -inf − -inf = nan)


def ring_attention(q, k, v, mask=None, axis_name: str = "sp",
                   causal: bool = False, scale: float | None = None):
    """Attention over a sequence sharded on ``axis_name``.

    Call inside ``shard_map`` (or use :func:`ring_self_attention`).

    Args:
      q, k, v: local blocks ``[batch, seq_local, heads, head_dim]``.
      mask: optional key-padding mask block ``[batch, seq_local]`` (True =
        attend); it rotates around the ring together with its k/v block.
      causal: apply a causal mask using *global* positions.
    Returns:
      ``[batch, seq_local, heads, head_dim]`` — this device's output block.
    """
    n = compat.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    q32 = q.astype(jnp.float32)
    q_pos = my * Tq + jnp.arange(Tq)

    # The accumulators become axis-varying inside the loop (they mix with
    # this device's q/k blocks), so their init must carry q's varying axes
    # (sp plus any sharded batch axes) for shard_map's varying-axes check.
    # empty outside shard_map (single-device testing)
    vma = tuple(compat.vma_of(q))

    def _vary(x):
        return compat.pcast(x, vma, to="varying") if vma else x

    o0 = _vary(jnp.zeros((B, Tq, H, D), jnp.float32))
    m0 = _vary(jnp.full((B, H, Tq), NEG_INF, jnp.float32))
    l0 = _vary(jnp.zeros((B, H, Tq), jnp.float32))
    perm = [(j, (j + 1) % n) for j in range(n)]
    # the padding mask travels with its k/v block; use all-True when absent
    mask0 = mask if mask is not None else _vary(jnp.ones((B, Tk), bool))

    def body(i, carry):
        o, m, l, k_cur, v_cur, mask_cur = carry
        # After i rotations each device holds the block that originated at
        # ring position (my - i) mod n.
        src = (my - i) % n
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, k_cur.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * Tk + jnp.arange(Tk)
            visible = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(visible[None, None], s, NEG_INF)
        if mask is not None:
            s = jnp.where(mask_cur[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1)
        o_new = (o * alpha.transpose(0, 2, 1)[..., None]
                 + jnp.einsum("bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32)))
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        mask_next = lax.ppermute(mask_cur, axis_name, perm)
        return o_new, m_new, l_new, k_next, v_next, mask_next

    o, m, l, _, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v, mask0))
    out = o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(mesh, q, k, v, mask=None, causal: bool = False,
                        sp_axis: str = "sp", batch_axes=("dp", "fsdp")):
    """Global-array entry point: shards sequence over ``sp_axis`` (and batch
    over ``batch_axes``) and runs :func:`ring_attention` under ``shard_map``.

    ``q, k, v``: global ``[batch, seq, heads, head_dim]`` arrays (seq must be
    divisible by the ``sp`` axis size).  ``mask``: optional global
    ``[batch, seq]`` key-padding mask (True = attend).
    """
    spec = P(batch_axes, sp_axis, None, None)
    kernel = functools.partial(ring_attention, axis_name=sp_axis, causal=causal)
    if mask is None:
        fn = compat.shard_map(kernel, mesh=mesh,
                           in_specs=(spec, spec, spec), out_specs=spec)
        return fn(q, k, v)
    mask_spec = P(batch_axes, sp_axis)
    fn = compat.shard_map(kernel, mesh=mesh,
                       in_specs=(spec, spec, spec, mask_spec), out_specs=spec)
    return fn(q, k, v, mask)


def reference_attention(q, k, v, mask=None, causal: bool = False,
                        scale: float | None = None):
    """Dense single-device attention, used as the numerical oracle in tests."""
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        pos = jnp.arange(T)
        s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, NEG_INF)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
