"""ResNet family (v1.5), bf16/MXU-friendly.

Reference workloads: ``examples/resnet`` (Keras custom-training-loop CIFAR-10
ResNet under MultiWorkerMirrored) and the ResNet-50 ImageNet north-star job
(``BASELINE.json`` configs[2], metric "images/sec/chip").

TPU-first choices: NHWC layout (XLA:TPU's native conv layout), bf16 compute
with fp32 BatchNorm statistics and fp32 logits, 3×3 stem option for CIFAR,
and ``axis_name``-aware BatchNorm for cross-replica statistics when desired
(the ``SyncBatchNorm`` analogue — under ``pjit`` the default per-device
stats are already the common practice).

What lies between two layers is stored in the model's ``dtype``: every
BatchNorm gives its result in it (``norm_dtype=None``; an explicit
``norm_dtype`` wins), so the residual sum, the ``relu``, the max-pool and
what autodiff saves of them are in it too, and a bf16 model moves half the
HBM bytes of a float32-normalised one on the bandwidth-bound path.  Float32
by construction whatever ``dtype`` says: ``param_dtype`` (kernels, scales,
biases), ``batch_stats``, the statistics' reductions and the normalisation
arithmetic (flax promotes the input to float32 inside the op and casts its
result once), the pooled features into the float32 classifier, the logits.
A ``dtype=float32`` model is float32 throughout.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp


def space_to_depth(x, block: int = 2):
    """NHWC space-to-depth: ``[B, H, W, C] -> [B, H/b, W/b, b*b*C]`` with
    channel order ``(dy, dx, c)``.  The MXU-feeding transform for the
    ImageNet stem: a 224×224×3 image becomes 112×112×12, so the stem
    conv's contraction dim grows 4× toward the MXU's 128 lanes."""
    B, H, W, C = x.shape
    if H % block or W % block:
        raise ValueError(f"space_to_depth needs H and W divisible by "
                         f"{block}, got {H}x{W} (pad or crop the input)")
    x = x.reshape(B, H // block, block, W // block, block, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, H // block, W // block, block * block * C)


def conv7_stem_to_s2d_kernel(k7):
    """EXACT weight transform from the standard 7×7/s2 ImageNet stem to
    the space-to-depth stem's 4×4/s1 kernel.

    A 7×7 stride-2 pad-3 conv equals an 8×8 stride-2 conv whose kernel is
    zero-padded one row/col at the top/left (padding (4,3)); on the
    2×2-space-to-depth image that is exactly a 4×4 stride-1 conv with
    padding (2,1) over 4C channels ordered ``(dy, dx, c)`` — the MLPerf
    ResNet trick.  ``k7`` is HWIO ``[7, 7, C, O]``; returns
    ``[4, 4, 4C, O]``.  ``tests/test_models.py`` locks bit-level parity.
    """
    C, O = k7.shape[2], k7.shape[3]
    k8 = jnp.pad(k7, ((1, 0), (1, 0), (0, 0), (0, 0)))
    k4 = k8.reshape(4, 2, 4, 2, C, O).transpose(0, 2, 1, 3, 4, 5)
    return k4.reshape(4, 4, 4 * C, O)


def _stored_as(norm_dtype, dtype):
    """The dtype a BatchNorm gives its result in: the model's ``dtype``
    unless the caller named one (``is None``, not truthiness: a
    ``numpy.dtype`` has length 0 and is falsy)."""
    return dtype if norm_dtype is None else norm_dtype


class BasicBlock(nn.Module):
    filters: int
    strides: int = 1
    dtype: jnp.dtype = jnp.bfloat16
    norm: type = nn.BatchNorm
    norm_dtype: jnp.dtype | None = None  # None: the block's dtype

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        norm = partial(self.norm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5,
                       dtype=_stored_as(self.norm_dtype, self.dtype))
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        residual = x
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides))(x)
        y = norm()(y)
        y = nn.relu(y)
        y = conv(self.filters, (3, 3))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)  # zero-init last BN
        if residual.shape != y.shape:
            residual = conv(self.filters, (1, 1),
                            strides=(self.strides, self.strides))(residual)
            residual = norm()(residual)
        return nn.relu(y + residual.astype(y.dtype))


class Bottleneck(nn.Module):
    filters: int
    strides: int = 1
    dtype: jnp.dtype = jnp.bfloat16
    norm: type = nn.BatchNorm
    norm_dtype: jnp.dtype | None = None  # None: the block's dtype

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        norm = partial(self.norm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5,
                       dtype=_stored_as(self.norm_dtype, self.dtype))
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        residual = x
        y = conv(self.filters, (1, 1))(x)
        y = norm()(y)
        y = nn.relu(y)
        # v1.5: stride lives on the 3x3, not the 1x1
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        y = norm()(y)
        y = nn.relu(y)
        y = conv(self.filters * 4, (1, 1))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters * 4, (1, 1),
                            strides=(self.strides, self.strides))(residual)
            residual = norm()(residual)
        return nn.relu(y + residual.astype(y.dtype))


class ResNet(nn.Module):
    """Configurable ResNet: ``stage_sizes`` blocks per stage."""

    stage_sizes: Sequence[int]
    block: type = Bottleneck
    num_classes: int = 1000
    num_filters: int = 64
    cifar_stem: bool = False  # 3x3/1 stem, no maxpool (CIFAR-10 inputs)
    # "s2d": MLPerf-style space-to-depth stem — 2×2 s2d then a 4×4/s1 conv
    # over 4C channels, mathematically EXACT vs the 7×7/s2 stem under the
    # conv7_stem_to_s2d_kernel weight transform.  The 7×7 stem contracts
    # only 3 input channels (the MXU's 128 contraction lanes mostly idle);
    # s2d contracts 12 over a 4× smaller spatial extent.  Ignored when
    # ``cifar_stem`` is set.
    stem: str = "conv7"
    dtype: jnp.dtype = jnp.bfloat16
    # BatchNorm result dtype; None: ``dtype`` (the module docstring says
    # what stays float32).  ``scripts/tpu_sweep.py --bn f32|bf16`` sets it.
    norm_dtype: jnp.dtype | None = None

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        if self.stem not in ("conv7", "s2d"):
            raise ValueError(f"unknown stem {self.stem!r} "
                             "(expected 'conv7' or 's2d')")
        x = x.astype(self.dtype)
        if self.cifar_stem:
            x = nn.Conv(self.num_filters, (3, 3), use_bias=False, dtype=self.dtype)(x)
        elif self.stem == "s2d":
            x = space_to_depth(x, 2)
            x = nn.Conv(self.num_filters, (4, 4), strides=(1, 1),
                        padding=[(2, 1), (2, 1)], use_bias=False,
                        dtype=self.dtype)(x)
        else:
            x = nn.Conv(self.num_filters, (7, 7), strides=(2, 2),
                        padding=[(3, 3), (3, 3)], use_bias=False, dtype=self.dtype)(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                         epsilon=1e-5,
                         dtype=_stored_as(self.norm_dtype, self.dtype))(x)
        x = nn.relu(x)
        if not self.cifar_stem:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for stage, num_blocks in enumerate(self.stage_sizes):
            for block_idx in range(num_blocks):
                strides = 2 if stage > 0 and block_idx == 0 else 1
                x = self.block(self.num_filters * 2 ** stage, strides=strides,
                               dtype=self.dtype,
                               norm_dtype=self.norm_dtype)(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


ResNet18 = partial(ResNet, stage_sizes=(2, 2, 2, 2), block=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=(3, 4, 6, 3), block=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=(3, 4, 6, 3), block=Bottleneck)
# The reference CIFAR-10 example's scale: ResNet-18-ish with a CIFAR stem.
CifarResNet = partial(ResNet, stage_sizes=(2, 2, 2, 2), block=BasicBlock,
                      num_classes=10, cifar_stem=True)
