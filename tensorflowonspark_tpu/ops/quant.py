"""Int8 weight-only quantization for memory-bound inference.

The reference has no quantization story (its SavedModel inference runs the
training graph as-is); this is a TPU-first extension for the decode-side
bottleneck: autoregressive generation reads every weight once per token, so
single-chip decode throughput is bounded by HBM bandwidth, not the MXU.
Storing kernels as int8 + per-output-channel fp scales halves the bytes per
token vs bf16 (4x vs fp32); XLA fuses the dequantize (convert + multiply)
into the matmul's operand read, so no full-precision copy of the weight
ever materialises in HBM.

Mechanism: :class:`Int8Array` is a registered pytree that carries ``(q:
int8, scale: float)`` and implements the ``__jax_array__`` protocol —
``jnp.asarray`` (which every flax ``nn.Dense`` applies to its kernel)
triggers the lazy dequantize expression.  Model code is untouched: quantize
the params pytree with :func:`quantize_params` and call the same
``model.apply`` / ``greedy_generate``.

Usage::

    from tensorflowonspark_tpu.ops import quantize_params
    qparams = quantize_params(params)          # kernels -> int8
    tokens = greedy_generate(cfg, qparams, prompt, 128)
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.tree_util import register_pytree_with_keys

try:  # flax is an optional import at this layer
    from flax.linen import meta as _nn_meta
# tfos: ignore[broad-except] — optional flax dependency probe
except Exception:  # pragma: no cover
    _nn_meta = None


class _QuantArray:
    """Quantized weight (``q``) + fp scale, dequantized lazily.

    Registered as a pytree (``q`` and ``scale`` are the children), so it
    flows through ``jit``/``device_put``/checkpoint trees like any other
    leaf pair.  ``jnp.asarray`` — the first thing flax layers do to a
    kernel — invokes ``__jax_array__`` and yields ``q * scale`` in
    ``scale.dtype``; under ``jit`` XLA fuses that into the consumer.
    Subclasses fix the storage dtype; consumers should test against this
    base class.
    """

    def __init__(self, q, scale):
        self.q, self.scale = q, scale

    def __jax_array__(self):
        return self.q.astype(self.scale.dtype) * self.scale

    # Enough array-protocol surface for flax's dtype promotion and the
    # model zoo's ``.astype`` call sites.
    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        return self.scale.dtype

    @property
    def nbytes(self) -> int:
        return self.q.size + self.scale.size * self.scale.dtype.itemsize

    def astype(self, dtype):
        return jnp.asarray(self).astype(dtype)

    def __repr__(self):
        return (f"{type(self).__name__}(shape={tuple(self.shape)}, "
                f"dtype={self.dtype})")


class Int8Array(_QuantArray):
    """Symmetric int8 weight + per-output-channel fp scale."""


def _register(cls):
    register_pytree_with_keys(
        cls,
        lambda t: ((("q", t.q), ("scale", t.scale)), None),
        lambda aux, children: cls(*children),
    )


_register(Int8Array)


def quantize_int8(w, contract_axis: int = -2) -> Int8Array:
    """Quantize one weight to symmetric int8 with per-channel scales.

    ``contract_axis`` is the axis summed over in the consuming matmul
    (``-2`` = the input dim of a ``[..., in, out]`` Dense kernel — scales
    then vary per output channel, the standard weight-only recipe).
    """
    w = jnp.asarray(w)
    amax = jnp.max(jnp.abs(w), axis=contract_axis, keepdims=True)
    scale = (amax / 127.0 + jnp.finfo(w.dtype).tiny).astype(w.dtype)
    q = jnp.round(w / scale).astype(jnp.int8)
    return Int8Array(q, scale)


class Int4Array(_QuantArray):
    """Symmetric int4 weight (native ``jnp.int4`` dtype) + fp scale.

    Quarter the weight bytes of bf16 (half of int8) — decode reads every
    weight once per token, so bytes/token is the throughput.  The
    ``jnp.int4`` element type keeps the FULL logical shape (so flax's
    existing-param shape check and sharding specs transfer unchanged)
    while XLA:TPU stores the buffer packed two-per-byte in HBM and fuses
    the unpack + dequantize into the consuming matmul's operand read.
    Values are clipped to [-7, 7] (symmetric grid).
    """

    @property
    def nbytes(self) -> int:
        # packed accounting: two int4 per byte (what TPU HBM stores),
        # regardless of the host/backend's in-memory representation
        return (self.q.size + 1) // 2 \
            + self.scale.size * self.scale.dtype.itemsize


_register(Int4Array)


if _nn_meta is not None:
    _AxisMetadataBase = _nn_meta.AxisMetadata
else:  # pragma: no cover — flax-free install: the box protocol is moot
    class _AxisMetadataBase:
        pass


class Int4PackedArray(_QuantArray, _AxisMetadataBase):
    """Symmetric int4 weight packed two-per-uint8-byte + fp scale.

    Same 0.5 byte/weight HBM footprint as the native ``jnp.int4``
    storage of :class:`Int4Array`, but carried as a plain ``uint8``
    buffer of shape ``[..., ceil(n/2)]`` — portable across every PJRT
    backend, including ones that cannot transfer S4 elements.  The unpack (nibble split, sign
    extend, dequantize) happens in-graph at ``__jax_array__`` time and
    XLA fuses it into the consuming matmul's operand read, so the
    memory win survives.  Element order: logical elements ``2i`` /
    ``2i+1`` of the LAST axis live in the low / high nibble of packed
    byte ``i`` (odd last dims are zero-padded at pack time and sliced
    off at unpack)."""

    def __init__(self, q, scale, logical_shape):
        super().__init__(q, scale)
        self.logical_shape = tuple(logical_shape)

    @property
    def shape(self):
        return self.logical_shape

    @property
    def ndim(self):
        return len(self.logical_shape)

    def __jax_array__(self):
        # repeat + parity-shift, NOT stack/reshape: pure elementwise on
        # the byte-repeated tensor (no layout-changing stack between the
        # bytes and the consumer).  Evidence is an end-to-end decode A/B
        # from the old sweep (decode_matrix int4 ~1.5x at kv4/kv1), not a
        # microbench; not re-measured on the attached chip
        p = self.q
        n = self.logical_shape[-1]
        rep = jnp.repeat(p, 2, axis=-1)[..., :n]
        shift = jnp.where(jnp.arange(n) % 2 == 0, jnp.uint8(0),
                          jnp.uint8(4))
        nib = ((rep >> shift) & jnp.uint8(0xF)).astype(jnp.int8)
        # sign-extend a two's-complement nibble (0..15 -> -8..7)
        nib = nib - jnp.int8(16) * (nib > jnp.int8(7)).astype(jnp.int8)
        return nib.astype(self.scale.dtype) * self.scale

    # nbytes: the inherited _QuantArray accounting is already exact here
    # (q.size counts packed bytes)

    # --- flax AxisMetadata protocol -----------------------------------
    # The packed ``q`` buffer halves the last dim, so flax's existing-
    # param shape check (scope.param: zip of tree leaves vs the
    # initializer's abstract leaves) would reject it.  Boxing as
    # AxisMetadata makes ``meta.unbox`` — which flax runs on every param
    # read — return the logical-shaped dequant expression instead; under
    # jit XLA fuses it into the consumer, so HBM still holds nibbles.
    def unbox(self):
        return jnp.asarray(self)

    def replace_boxed(self, val):
        return val

    # Lifted-transform protocol: a transform that actually adds/removes a
    # param axis (nn.scan / nn.vmap param lifting) would leave
    # ``logical_shape`` stale, and the unpack would silently dequantize
    # the wrong dim.  Quantize AFTER lifting instead (ADVICE r5 item 1).
    def add_axis(self, index, params):
        raise NotImplementedError(
            "Int4PackedArray cannot be lifted across an axis-adding "
            "transform (nn.scan/nn.vmap over params): its packed buffer "
            "and logical_shape are per-leaf static.  Quantize the params "
            "AFTER applying the lifted transform.")

    def remove_axis(self, index, params):
        raise NotImplementedError(
            "Int4PackedArray cannot be lifted across an axis-removing "
            "transform (nn.scan/nn.vmap over params): its packed buffer "
            "and logical_shape are per-leaf static.  Quantize the params "
            "AFTER applying the lifted transform.")


register_pytree_with_keys(
    Int4PackedArray,
    lambda t: ((("q", t.q), ("scale", t.scale)), t.logical_shape),
    lambda aux, children: Int4PackedArray(*children, aux),
)


def _pack_nibbles(qi):
    """``int8`` values in [-8, 7], any shape -> ``uint8`` two's-complement
    nibble pairs along the last axis (zero-padding an odd last dim)."""
    if qi.shape[-1] % 2:
        qi = jnp.pad(qi, [(0, 0)] * (qi.ndim - 1) + [(0, 1)])
    pairs = qi.astype(jnp.uint8).reshape(*qi.shape[:-1], -1, 2)
    return (pairs[..., 0] & jnp.uint8(0xF)) \
        | ((pairs[..., 1] & jnp.uint8(0xF)) << jnp.uint8(4))


def quantize_int4(w, contract_axis: int = -2,
                  storage: str = "packed") -> _QuantArray:
    """Quantize one weight to symmetric int4 with per-channel scales
    (same recipe as :func:`quantize_int8`, 15-level grid).

    ``storage="packed"`` (default) returns :class:`Int4PackedArray`
    (uint8 nibble pairs — works on every backend); ``"native"`` returns
    :class:`Int4Array` (``jnp.int4`` element type — needs a backend that
    transfers S4 elements)."""
    w = jnp.asarray(w)
    amax = jnp.max(jnp.abs(w), axis=contract_axis, keepdims=True)
    scale = (amax / 7.0 + jnp.finfo(w.dtype).tiny).astype(w.dtype)
    q = jnp.clip(jnp.round(w / scale), -7, 7)
    if storage == "native":
        return Int4Array(q.astype(jnp.int4), scale)
    if storage != "packed":
        raise ValueError(f"unknown int4 storage {storage!r}")
    return Int4PackedArray(_pack_nibbles(q.astype(jnp.int8)), scale,
                           w.shape)


def _default_predicate(path: tuple, leaf) -> bool:
    # Dense kernels only: >=2D leaves named 'kernel'.  Embedding tables,
    # layernorm scales, biases and position tables stay full precision
    # (they are small and/or feed fp32 logits).
    return (bool(path) and str(path[-1]) == "kernel"
            and getattr(leaf, "ndim", 0) >= 2
            and jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating))


def quantize_params(params, predicate: Callable | None = None,
                    bits: int = 8):
    """Quantize matching leaves of a params pytree to :class:`Int8Array`
    (``bits=8``) or :class:`Int4PackedArray` (``bits=4`` — uint8 nibble
    storage; pass ``storage="native"`` to :func:`quantize_int4` directly
    for ``jnp.int4`` elements).

    Flax ``Partitioned`` metadata boxes are unboxed first; to place the
    quantized tree on a mesh (tensor-parallel int8 decode), pass the
    result through :func:`shard_quantized` with the unquantized tree's
    shardings.  ``predicate(path, leaf) -> bool`` overrides the default
    "2D+ leaves named 'kernel'" rule.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if _nn_meta is not None:
        params = _nn_meta.unbox(params)
    pred = predicate or _default_predicate

    def visit(path, leaf):
        keys = tuple(getattr(k, "key", getattr(k, "idx", k)) for k in path)
        if not pred(keys, leaf):
            return leaf
        return quantize_int4(leaf) if bits == 4 else quantize_int8(leaf)

    return jax.tree_util.tree_map_with_path(visit, params)


def shard_quantized(params, shardings):
    """Place a quantized pytree on a mesh (tensor-parallel int8 decode).

    ``shardings`` is the tree ``parallel.sharding.flax_shardings`` builds
    for the *unquantized* params (``NamedSharding`` leaves).  ``q`` takes
    its kernel's sharding verbatim; ``scale`` takes the same spec with the
    contraction axis (−2, size 1 after quantization) dropped to ``None``.
    Plain leaves are ``device_put`` with their sharding unchanged.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    def place(leaf, sh):
        if sh is None:
            return leaf
        if not isinstance(leaf, _QuantArray):
            return jax.device_put(leaf, sh)
        spec = tuple(sh.spec) + (None,) * (leaf.ndim - len(tuple(sh.spec)))
        scale_spec = spec[:-2] + (None,) + spec[-1:]
        scale = jax.device_put(
            leaf.scale, NamedSharding(sh.mesh, PartitionSpec(*scale_spec)))
        q_spec = spec
        if isinstance(leaf, Int4PackedArray) and spec[-1] is not None:
            # the packed buffer's last dim is ceil(n/2) — a spec valid for
            # the logical shape may not divide it; replicate that axis
            # rather than fail (the dequant output still lands sharded via
            # the consumer's constraint)
            axes = spec[-1] if isinstance(spec[-1], tuple) else (spec[-1],)
            n_shards = 1
            for a in axes:
                n_shards *= sh.mesh.shape[a]
            if leaf.q.shape[-1] % n_shards:
                q_spec = spec[:-1] + (None,)
        q = jax.device_put(leaf.q, NamedSharding(sh.mesh,
                                                 PartitionSpec(*q_spec)))
        if isinstance(leaf, Int4PackedArray):
            return Int4PackedArray(q, scale, leaf.logical_shape)
        return type(leaf)(q, scale)

    return jax.tree.map(place, params, shardings,
                        is_leaf=lambda x: isinstance(x, _QuantArray))


def tree_nbytes(params) -> int:
    """Total parameter bytes (quantized-leaf-aware) — compression reports."""
    leaves = jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, _QuantArray))
    total = 0
    for leaf in leaves:
        if isinstance(leaf, _QuantArray):
            total += leaf.nbytes
        else:
            total += leaf.size * jnp.asarray(leaf).dtype.itemsize
    return total
