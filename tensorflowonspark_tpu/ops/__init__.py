"""Pallas TPU kernels for the framework's hot ops.

The reference ships no kernels of its own (its compute layer is the TF
C++/CUDA runtime, SURVEY.md §2b); the rebuild's analogue of that native
layer is XLA:TPU plus the hand-written Pallas kernels here for the ops
where fusion beyond XLA's pays: attention (the O(T²) memory hog) first,
the paged decode step's attention over K/V pages where they lie, the
retention layers' decode-step state update (``power_retention``), and the
served expert layer's grouped products, each touched expert's weights
streamed once (``grouped_matmul``, in place of XLA's ``ragged-dot``).
"""

from tensorflowonspark_tpu.ops.flash_attention import flash_attention
from tensorflowonspark_tpu.ops.grouped_matmul import (grouped_dot,
                                                      grouped_relu2,
                                                      grouped_swiglu)
from tensorflowonspark_tpu.ops.paged_attention import paged_decode_attention
from tensorflowonspark_tpu.ops.quant import (Int4Array, Int4PackedArray,
                                             Int8Array, quantize_int4,
                                             quantize_int8, quantize_params,
                                             shard_quantized, tree_nbytes)
from tensorflowonspark_tpu.ops.xent import tied_softmax_xent

__all__ = ["flash_attention", "grouped_dot", "grouped_relu2",
           "grouped_swiglu", "paged_decode_attention", "Int4Array",
           "Int4PackedArray", "Int8Array", "quantize_int4", "quantize_int8",
           "quantize_params", "shard_quantized", "tree_nbytes",
           "tied_softmax_xent"]
