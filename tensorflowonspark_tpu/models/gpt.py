"""GPT-style causal decoder with mesh-aware sharding and compiled decoding.

The reference has no decoder family at all (its workloads are
MNIST/ResNet/U-Net/BERT-class, SURVEY.md §2d) — this extends the model zoo
the direction modern users expect, TPU-first:

- same Megatron GSPMD annotations as :mod:`.bert` (QKV/up shard output dim
  over ``tp``, out/down shard input dim; one XLA all-reduce per block);
- pre-LN blocks, bf16 activations, fp32 layernorm/softmax/logits, weight-
  tied LM head;
- pluggable attention (``ops.flash_attention`` with ``causal=True`` on
  TPU, ring/ulysses for sequence parallelism);
- **autoregressive decoding is a single compiled program**: a static-shape
  KV cache lives in a flax ``cache`` collection and
  :func:`greedy_generate` rolls the model with ``lax.scan`` — no
  per-token Python, no dynamic shapes, exactly what the XLA compilation
  model wants.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models.bert import _context_mesh, _dense
from tensorflowonspark_tpu.ops import paged_attention as _paged
from tensorflowonspark_tpu.ops import power_retention as _retention
from tensorflowonspark_tpu.ops import ssm as _ssm
from tensorflowonspark_tpu.ops.flash_attention import _on_tpu


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    # Grouped-query attention: number of K/V heads (None = num_heads, i.e.
    # plain MHA; 1 = MQA).  Shrinks the decode KV cache — and its HBM
    # traffic, the decode bound — by num_heads/num_kv_heads; composes with
    # ``kv_cache_int8``.  On the decode and dense paths query heads attend
    # in groups via a grouped einsum (repeated K/V never materialise); a
    # custom ``attention_fn`` gets K/V broadcast to num_heads once.
    num_kv_heads: int | None = None
    # Size of one attention head where it is not ``hidden_size //
    # num_heads`` (None = that): the projections are ``hidden_size ->
    # num_heads * head_dim`` and back.
    attn_head_dim: int | None = None
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    # "learned" = GPT-2-style absolute position table; "rope" = rotary
    # embeddings applied to q/k (no position table at all) — relative
    # positions by construction, the long-context-friendly default of
    # modern decoders.  K is cached post-rotation, so decode matches the
    # full forward exactly.  "none" = no table and no rotation: the
    # recurrent layers of a hybrid carry the order (Nemotron-H).
    pos_encoding: str = "learned"
    rope_base: float = 10000.0
    # "layernorm" (GPT-2) or "rmsnorm" (Llama-class: no mean-centering, no
    # bias — one fewer reduction on the VPU per sublayer).
    norm: str = "layernorm"
    # flax's LayerNorm default, so pre-existing layernorm configs keep
    # bit-identical numerics; Llama-class recipes typically pass 1e-5.
    norm_eps: float = 1e-6
    # "gelu" (GPT-2 2-matmul MLP) or "swiglu" (Llama-class gated MLP:
    # gate/up/down, silu(gate)*up).  rope+rmsnorm+swiglu+num_kv_heads
    # covers Llama-class architectures (rotate-half RoPE pairing, the
    # GPT-NeoX/HF convention; interleaved-pairing checkpoints need their
    # usual weight permutation at conversion).
    mlp: str = "gelu"
    # Optional attention override for the full-sequence TRAINING path
    # (``decode=False``), signature ``(q, k, v, mask=None, causal=...) ->
    # out``.  The decode path — including prefill through ``decode=True``
    # — always uses dense attention over the static cache (the cache
    # update and masked read are one fused program there).
    attention_fn: Callable | None = None
    emb_spec: tuple = ("tp", None)
    # Stack the decoder blocks with ``nn.scan`` (+ ``nn.remat``): one traced
    # block instead of ``num_layers`` copies — compile time O(1) in depth,
    # activations rematerialised per layer on the backward pass.  The XLA
    # layer-stacking idiom for deep models; params gain a leading ``layers``
    # axis (``layers/...`` instead of ``layer_{i}/...``).
    scan_layers: bool = False
    remat: bool = False
    # Sliding-window (local) attention: each token attends to its last
    # ``sliding_window`` positions only (Mistral-style).  Applied on the
    # dense/decode paths via the band mask and passed to a custom
    # ``attention_fn`` as ``window=`` (ops.flash_attention skips
    # out-of-band blocks entirely).  None = full causal attention.
    sliding_window: int | None = None
    # With ``sliding_window``, keep only the window in the decode cache
    # (Mistral-style rolling buffer, slots indexed position mod W): cache
    # size and per-token HBM traffic drop from max_position_embeddings to
    # W.  Generation is exact (each step's window is fully present);
    # intermediate PREFILL logits for positions other than the last are
    # not — prompt positions older than the final window are gone by the
    # time the block is scored.  greedy/beam/sample only consume the last
    # position's logits, so decoding is unaffected.
    rolling_kv_cache: bool = False
    # Store the decode KV cache as int8 with per-(position, head) scales:
    # at long context the cache — 2·L·B·T·H·D·2 bytes read per token —
    # outweighs the weights in HBM traffic, and decode is HBM-bound;
    # int8 halves it.  XLA fuses the dequantize into the attention reads.
    kv_cache_int8: bool = False
    # Per-ROW cache positions (``index``/``pos`` become ``[B]`` vectors):
    # each batch row decodes at its own offset, the substrate for
    # continuous batching (``models.serving.ContinuousBatcher`` admits and
    # retires requests mid-flight by operating on individual cache rows).
    # Decode-path only; mutually exclusive with rolling_kv_cache (the
    # rolling slot math assumes one shared write position).
    per_row_positions: bool = False
    # PAGED decode KV cache (vLLM-style): instead of a dense
    # ``[B, max_len]`` K/V block per layer, allocate a POOL of
    # ``kv_pool_pages`` fixed-size pages of ``kv_page_tokens`` tokens
    # (``[pool_pages * page_tokens, W]`` per layer, ``[L, ..]`` stacked
    # under scan_layers: one token's Hkv heads of D side by side in a
    # row of ``W = kv_row_width(Hkv, D)`` lanes, Hkv*D rounded up to
    # whole 128-lane tiles so the device's own layout for the pool is
    # row-major and the token scatter stores in place; an exported page
    # is ``[page_tokens, W]``) plus a per-row ``block_table`` cache
    # variable mapping logical page -> physical page.  Each step WRITES
    # through the table (positions past a row's allocated pages, or past
    # max_position_embeddings, are dropped — the unallocated sentinel
    # entry is ``kv_pool_pages``, out of pool range).  A DECODE step (one
    # token a row) then attends over the pages where they lie
    # (``ops.paged_attention``: only the pages a row's length covers, as
    # stored; :func:`attends_pages_in_place` says when); every other
    # step READS the full logical view back with one page gather, after
    # which attention is the identical per-row masked einsum — the same
    # mathematics either way.  Page accounting (allocation,
    # prefix sharing, refcounts) is host-side: ``models.kv_pages``.
    # Decode-path only; needs per_row_positions; incompatible with
    # rolling_kv_cache and kv_cache_int8.
    kv_page_tokens: int | None = None
    kv_pool_pages: int | None = None
    # Biases on the attention and MLP projections (GPT-2 has them; the
    # Llama and LFM2 classes have none).
    use_bias: bool = True
    # RMSNorm over each head's values of q and of k (learned scale per
    # head position), BEFORE the rotation.
    qk_norm: bool = False
    # Per-layer token mixer: ``"full_attention"``, ``"conv"``,
    # ``"retention"``, ``"mamba2"`` or ``"experts"``, one entry per layer
    # (None = attention everywhere).  A mamba2 layer is a MAMBA-2 mixer
    # (:class:`Mamba2Mixer`, ``ops.ssm``), which keeps TWO kinds of
    # per-row state on the decode path: ``ssm_state`` (float32, ``[B, G,
    # N, (H/G) * P]``) and ``ssm_conv``, the last ``ssm_conv_kernel - 1``
    # inputs of its depthwise convolution over ``ssm_conv_channels``
    # channels.  An experts layer is the expert layer (``models.moe``) as
    # the block's ONLY operator, which needs ``mixer_only``.
    # A retention layer is POWER RETENTION (:class:`PowerRetention`,
    # ``ops.power_retention``): attention weighted by the square of the
    # query-key product under a learned per-token decay, carried on the
    # decode path as a fixed-size float32 state per row (``ret_state [B,
    # Hkv, D, F]`` and its normaliser ``ret_norm [B, Hkv, F]``, ``F`` =
    # 8704 for heads of 128): no K/V, no pages.  A configuration with no
    # ``full_attention`` layer owns no K/V pool at all.  A conv layer is a
    # gated SHORT CONVOLUTION (LFM2): ``[b, c, v] = split3(W_in u)``, ``z = b *
    # v``, a depthwise causal convolution of ``conv_L_cache`` taps over
    # ``z``, ``W_out (c * conv)``.  On the decode path its per-sequence
    # state is the last ``conv_L_cache - 1`` values of ``z``: a
    # ``conv_state [B, conv_L_cache - 1, H]`` cache leaf, fixed size per
    # row, no pages, no position counter (``cache_kinds``).
    layer_types: tuple | None = None
    # True = a block is its one operator and nothing else, ``x +
    # Op(norm(x))``: no second norm, no feed-forward half (Nemotron-H: a
    # Mamba-2 mixer, the experts or attention, one a layer).
    mixer_only: bool = False
    conv_L_cache: int = 3
    # The Mamba-2 mixer's sizes: heads of ``ssm_head_dim`` values (the
    # inner width is their product, whatever ``hidden_size`` is), groups
    # that share B and C, the state size per value, the convolution's taps
    # and the tokens of one chunk of the scan over a block of tokens.
    ssm_num_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state_size: int = 128
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    # tokens per block inside a retention layer's call on a block of
    # tokens, on either path (``retention_chunked``: the queries that
    # attend the call's keys at once, and the keys whose feature map is
    # made at once), and the normaliser's epsilon
    retention_chunk: int = 128
    retention_eps: float = 1e-6
    # False = a separate output head ``lm_head [hidden, vocab]``, stored
    # as its product reads it (the tied head re-lays the embedding table
    # for the product on every step)
    tie_word_embeddings: bool = True
    # Sparse experts (``models.moe.SparseMoE``): with ``num_experts`` set,
    # layers ``>= num_dense_layers`` replace the SwiGLU MLP by
    # ``num_experts`` SwiGLU experts of width ``moe_intermediate_size``,
    # ``num_experts_per_tok`` per token, dropless.  The leading dense
    # layers keep ``intermediate_size``.
    num_dense_layers: int = 0
    num_experts: int | None = None
    num_experts_per_tok: int = 1
    moe_intermediate_size: int | None = None
    # The experts THIS CHIP HOLDS, ``(first, count)`` of the
    # ``num_experts`` the router scores (None = all of them): the layer
    # routes over all, computes the part of the result its own experts
    # give and leaves the rest out (the chip's share of a deployment that
    # divides every expert layer over several chips; no exchange here).
    experts_held: tuple | None = None
    # A shared expert of this width beside the routed ones, unweighted
    # (None = none); the experts' activation, ``"swiglu"`` (three
    # matrices) or ``"relu2"`` (two: ``W2 relu(W1 u)**2``); the factor on
    # the chosen experts' normalised weights.
    moe_shared_intermediate_size: int | None = None
    moe_activation: str = "swiglu"
    routed_scaling_factor: float = 1.0
    # The experts' first matrices (``w_up``, and a gated expert's
    # ``w_gate``) are stored ``[E, F, H]``, the hidden axis last, and not
    # ``[E, H, F]``.  Set it where ``moe_intermediate_size`` is not whole
    # 128-lane tiles (1856 = 14.5): ``[E, H, F]`` is then not row-major as
    # the device lays a parameter out, and the grouped kernel, which takes
    # its operands row-major, would have every layer's experts re-laid on
    # every call (``ops.grouped_matmul``).  It says how a checkpoint is
    # laid, so the width does not decide it by itself.
    moe_up_transposed: bool = False

    def __post_init__(self):
        if self.pos_encoding not in ("learned", "rope", "none"):
            raise ValueError(
                f"pos_encoding must be 'learned', 'rope' or 'none', "
                f"got {self.pos_encoding!r}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(
                f"norm must be 'layernorm' or 'rmsnorm', got {self.norm!r}")
        if self.mlp not in ("gelu", "swiglu"):
            raise ValueError(
                f"mlp must be 'gelu' or 'swiglu', got {self.mlp!r}")
        if self.sliding_window is not None and self.sliding_window < 1:
            raise ValueError(
                f"sliding_window must be >= 1, got {self.sliding_window}")
        if self.rolling_kv_cache and self.sliding_window is None:
            raise ValueError(
                "rolling_kv_cache requires sliding_window to be set")
        if self.per_row_positions and self.rolling_kv_cache:
            raise ValueError(
                "per_row_positions is incompatible with rolling_kv_cache "
                "(rolling slot arithmetic assumes one shared position)")
        if self.kv_page_tokens is not None:
            pt = self.kv_page_tokens
            if pt < 1 or pt & (pt - 1):
                raise ValueError(f"kv_page_tokens must be a positive "
                                 f"power of two, got {pt}")
            if self.max_position_embeddings % pt:
                raise ValueError(
                    f"kv_page_tokens ({pt}) must divide "
                    f"max_position_embeddings "
                    f"({self.max_position_embeddings}) — the block table "
                    "covers whole pages")
            if self.kv_pool_pages is None or self.kv_pool_pages < 1:
                raise ValueError(
                    f"kv_page_tokens needs kv_pool_pages >= 1, got "
                    f"{self.kv_pool_pages!r}")
            if not self.per_row_positions:
                raise ValueError(
                    "kv_page_tokens needs per_row_positions (the block "
                    "table is per-row; ContinuousBatcher sets both)")
            if self.rolling_kv_cache:
                raise ValueError("kv_page_tokens is incompatible with "
                                 "rolling_kv_cache")
            if self.kv_cache_int8:
                raise ValueError(
                    "kv_page_tokens is incompatible with kv_cache_int8 "
                    "(the paged pool stores full-precision K/V; drop one "
                    "of the two)")
        elif self.kv_pool_pages is not None:
            raise ValueError("kv_pool_pages needs kv_page_tokens")
        if self.pos_encoding == "rope" and self.head_dim % 2:
            raise ValueError(
                f"rope needs an even head_dim, got {self.head_dim} "
                f"(hidden_size {self.hidden_size} / num_heads {self.num_heads})")
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            bad = sorted(set(self.layer_types) - set(LAYER_TYPES))
            if bad or len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types needs num_layers ({self.num_layers}) "
                    f"entries from {LAYER_TYPES}, got "
                    f"{len(self.layer_types)} with unknown {bad}")
            if self.has_conv and self.conv_L_cache < 2:
                raise ValueError(
                    f"conv_L_cache must be >= 2, got {self.conv_L_cache}")
            if self._count("mamba2") and (
                    self.ssm_num_heads < 1 or self.ssm_conv_kernel < 2
                    or self.ssm_num_heads % self.ssm_groups
                    or self.ssm_chunk < 1):
                raise ValueError(
                    "a mamba2 layer needs ssm_num_heads >= 1 in whole "
                    "ssm_groups, ssm_conv_kernel >= 2 and ssm_chunk >= 1, "
                    f"got {self.ssm_num_heads} heads in {self.ssm_groups} "
                    f"groups, {self.ssm_conv_kernel} taps, chunks of "
                    f"{self.ssm_chunk}")
            if self._count("experts") and not (
                    self.mixer_only and self.num_experts is not None):
                raise ValueError(
                    "a layer_types 'experts' layer is the block's only "
                    "operator: it needs mixer_only=True and num_experts")
        if self.num_experts is not None:
            # the dense layers' ``mlp`` says nothing of the experts, which
            # carry their own activation
            if self.moe_activation not in ("swiglu", "relu2") \
                    or self.moe_intermediate_size is None \
                    or not 1 <= self.num_experts_per_tok <= self.num_experts \
                    or not 0 <= self.num_dense_layers <= self.num_layers:
                raise ValueError(
                    "num_experts needs moe_activation 'swiglu' or 'relu2', "
                    "moe_intermediate_size, 1 <= num_experts_per_tok <= "
                    "num_experts and 0 <= num_dense_layers <= num_layers, "
                    f"got moe_activation={self.moe_activation!r}, "
                    f"moe_intermediate_size={self.moe_intermediate_size}, "
                    f"{self.num_experts_per_tok} of {self.num_experts} "
                    f"experts, {self.num_dense_layers} dense layers")
            if self.experts_held is not None:
                object.__setattr__(self, "experts_held",
                                   tuple(int(v) for v in self.experts_held))
                first, count = self.experts_held
                if first < 0 or count < 1 \
                        or first + count > self.num_experts:
                    raise ValueError(
                        f"experts_held (first, count) = {self.experts_held} "
                        f"does not lie inside the {self.num_experts} experts "
                        "the router scores")
        if self.retention_chunk < 1:
            raise ValueError(f"retention_chunk must be >= 1, got "
                             f"{self.retention_chunk}")
        if self.scan_layers and (self.has_state or self.num_experts
                                 is not None or self.mixer_only):
            raise ValueError(
                "scan_layers stacks ONE uniform block; layer_types with "
                "conv, retention or mamba2 layers, mixer_only and "
                "num_experts (dense layers before expert layers) make the "
                "blocks differ — leave scan_layers off")

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads

    @property
    def ssm_inner(self) -> int:
        """Width of a Mamba-2 mixer's ``x``, ``z`` and ``y``."""
        return self.ssm_num_heads * self.ssm_head_dim

    @property
    def ssm_conv_channels(self) -> int:
        """Channels of a Mamba-2 mixer's convolution: ``x``, ``B`` and
        ``C`` side by side."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_size

    def layer_type(self, layer: int) -> str:
        return LAYER_TYPES[0] if self.layer_types is None \
            else self.layer_types[layer]

    @property
    def has_conv(self) -> bool:
        return self.layer_types is not None and "conv" in self.layer_types

    def _count(self, layer_type: str) -> int:
        return 0 if self.layer_types is None \
            else self.layer_types.count(layer_type)

    @property
    def num_attention_layers(self) -> int:
        """Layers that own K/V (``full_attention``)."""
        return self.num_layers if self.layer_types is None \
            else self._count("full_attention")

    @property
    def num_state_layers(self) -> int:
        """Layers that keep per-row recurrent state."""
        return sum(self._count(t) for t in ("conv", "retention", "mamba2"))

    @property
    def has_state(self) -> bool:
        """Whether some layer keeps per-row RECURRENT state on the decode
        path (:data:`STATE_LEAVES`): fixed size per row, no position to
        mask by, so nothing to rewind to, share or hand off."""
        return self.num_state_layers > 0

    def is_expert_layer(self, layer: int) -> bool:
        """Whether block ``layer`` holds the experts: as its feed-forward
        half from ``num_dense_layers`` on, or, under ``mixer_only``, as
        the one operator of an ``"experts"`` layer."""
        if self.num_experts is None:
            return False
        if self.mixer_only:
            return self.layer_type(layer) == "experts"
        return layer >= self.num_dense_layers

    @property
    def num_expert_layers(self) -> int:
        return sum(self.is_expert_layer(i) for i in range(self.num_layers))

    @property
    def num_experts_held(self) -> int:
        """Experts whose weights this chip holds."""
        return 0 if self.num_experts is None else self.num_experts \
            if self.experts_held is None else self.experts_held[1]

    @property
    def cache_kinds(self) -> str:
        """The kinds of per-sequence decode state this configuration
        keeps, for error messages: a refusal names the layer type that
        caused it."""
        kinds = []
        n_conv, n_ret = self._count("conv"), self._count("retention")
        n_ssm = self._count("mamba2")
        if self.num_attention_layers:
            kinds.append(f"K/V of {self.num_attention_layers} "
                         "full_attention layer(s) (positional, rewindable)")
        if n_conv:
            kinds.append(f"conv_state of {n_conv} conv layer(s) (the last "
                         f"{self.conv_L_cache - 1} gated inputs per row: "
                         "fixed size, no snapshot to rewind to or share)")
        if n_ret:
            kinds.append(f"ret_state of {n_ret} retention layer(s) (the "
                         "decayed sum of every token so far per row: fixed "
                         "size, no snapshot to rewind to or share)")
        if n_ssm:
            kinds.append(f"ssm_state and ssm_conv of {n_ssm} mamba2 layer(s) "
                         "(the decayed sum of every token so far and the "
                         f"last {self.ssm_conv_kernel - 1} convolution "
                         "inputs per row: fixed size, no snapshot to rewind "
                         "to or share)")
        return "; ".join(kinds)


#: the token mixers ``GPTConfig.layer_types`` may name
LAYER_TYPES = ("full_attention", "conv", "retention", "mamba2", "experts")

#: cache leaves that are per-row recurrent state: the batch on their
#: leading axis, a fixed size per row, written whole by a step.  What
#: moves a sequence (seat, park, chunked admission) moves these rows;
#: what needs a snapshot of them is refused (``GPTConfig.has_state``).
STATE_LEAVES = ("conv_state", "ret_state", "ret_norm", "ssm_state",
                "ssm_conv")


def is_state_leaf(path) -> bool:
    """Whether a cache leaf's tree path names one of :data:`STATE_LEAVES`."""
    return getattr(path[-1], "key", None) in STATE_LEAVES


def state_step_bytes(cfg: GPTConfig, rows: int) -> int:
    """Bytes of per-row state one decode step of ``rows`` rows reads and
    writes, as THIS process runs it: a conv state is read and written
    once; a retention state as often as
    ``ops.power_retention.state_passes`` says; a Mamba-2 mixer's two
    states once each (``ops.ssm.ssm_step``, kernel or not)."""
    def tail(taps: int, channels: int) -> int:
        # a convolution's last inputs: read and written once
        return 2 * rows * (taps - 1) * channels \
            * jnp.dtype(cfg.dtype).itemsize

    ret = _retention.state_passes() * _retention.state_bytes(
        rows, cfg.num_kv_heads or cfg.num_heads, cfg.head_dim) \
        if cfg._count("retention") else 0
    ssm = 2 * _ssm.state_bytes(rows, cfg.ssm_num_heads, cfg.ssm_head_dim,
                               cfg.ssm_state_size) \
        + tail(cfg.ssm_conv_kernel, cfg.ssm_conv_channels) \
        if cfg._count("mamba2") else 0
    return cfg._count("conv") * tail(cfg.conv_L_cache, cfg.hidden_size) \
        + cfg._count("retention") * ret + cfg._count("mamba2") * ssm


def kv_row_width(num_kv_heads: int, head_dim: int) -> int:
    """Width ``W`` of one token's row in the paged K/V pool: the heads
    side by side, rounded up to whole 128-lane tiles.  The device lays an
    array out from its shape alone, to waste the least padding; a last
    axis of whole lane tiles is what makes that layout row-major, the one
    the token scatter writes in place (gpt2-xl 25 x 64 = 1600 -> 1664)."""
    return -(-num_kv_heads * head_dim // 128) * 128


def attends_pages_in_place(cfg: GPTConfig, tokens_per_row: int = 1) -> bool:
    """Whether a cached step of ``tokens_per_row`` tokens attends through
    the paged-attention kernel (``ops.paged_attention``) and not through
    the whole-view gather.  Decided from what the step can see, at trace
    time: the cache is paged, the step is a decode step (one token a
    row), no sliding window bands the mask, a page is whole tiles of the
    pool's dtype (so a page is copied out as it is stored), no mesh of
    several devices is in scope (a ``pallas_call`` is not partitioned:
    under a ``tp`` gang GSPMD shards the pool's row by heads), and the
    backend is the TPU the kernel is written for (elsewhere it would run
    under the Pallas interpreter, which costs a CPU test suite minutes
    and gains nothing)."""
    if cfg.kv_page_tokens is None or tokens_per_row != 1 \
            or cfg.sliding_window is not None or not _on_tpu() \
            or not _paged.pages_are_tiles(cfg.dtype, cfg.kv_page_tokens):
        return False
    mesh = _context_mesh()
    return mesh is None or mesh.size == 1


def _rope(x, positions, base: float):
    """Rotary embedding: rotate feature pairs of ``x [B, T, H, D]`` by
    position-dependent angles (``positions [T]``, or ``[B, T]`` when rows
    decode at independent offsets — continuous batching).  fp32 trig,
    result in ``x.dtype``."""
    D = x.shape[-1]
    half = D // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        pos = pos[None]                                  # [1, T]
    angles = pos[:, :, None] * freq[None, None, :]       # [B|1, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


class CausalSelfAttention(nn.Module):
    cfg: GPTConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        cfg = self.cfg
        B, T, _ = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        Hkv = cfg.num_kv_heads or H
        if H % Hkv:
            raise ValueError(
                f"num_heads ({H}) must be divisible by num_kv_heads ({Hkv})")
        G = H // Hkv  # query heads per K/V head (1 = MHA, H = MQA)
        # named scopes (metadata only: the compiled code does not change)
        # name what flax's per-module scopes leave unnamed INSIDE attention:
        # qkv, kv_store, kv_gather, scores, context (docs/observability.md
        # "Profiler spans")
        with jax.named_scope("qkv"):
            q = _dense(H * D, (None, "tp"), cfg.dtype, "query",
                       cfg.use_bias)(x).reshape(B, T, H, D)
            k = _dense(Hkv * D, (None, "tp"), cfg.dtype, "key",
                       cfg.use_bias)(x).reshape(B, T, Hkv, D)
            v = _dense(Hkv * D, (None, "tp"), cfg.dtype, "value",
                       cfg.use_bias)(x).reshape(B, T, Hkv, D)
        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                q = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                               name="q_norm")(q).astype(cfg.dtype)
                k = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                               name="k_norm")(k).astype(cfg.dtype)

        per_row = cfg.per_row_positions and self.decode
        ci = self.variable(
            "cache", "index",
            lambda: jnp.zeros((B,) if per_row else (), jnp.int32)) \
            if self.decode else None
        if cfg.pos_encoding == "rope":
            # rotate q/k by absolute position; K is cached POST-rotation,
            # so incremental decode sees identical keys to the full forward
            if per_row:
                positions = ci.value[:, None] + jnp.arange(T)[None, :]
            else:
                positions = (ci.value if ci is not None else 0) + jnp.arange(T)
            q = _rope(q, positions, cfg.rope_base)
            k = _rope(k, positions, cfg.rope_base)

        def grouped_attention(q, k_all, v_all, mask):
            """``q [B,T,H,D]`` vs ``k/v [B,S,Hkv,D]``: query heads attend
            in groups of G per K/V head — the broadcast happens inside the
            einsum, so the repeated K/V never materialise."""
            with jax.named_scope("scores"):
                qg = q.reshape(B, T, Hkv, G, D).astype(jnp.float32)
                s = jnp.einsum("btkgd,bskd->bkgts", qg,
                               k_all.astype(jnp.float32)) * (D ** -0.5)
                # mask: [T, S] shared, or [B, T, S] per-row
                # (per_row_positions)
                m = mask[None, None, None] if mask.ndim == 2 \
                    else mask[:, None, None]
                s = jnp.where(m, s, -1e30)
                p = nn.softmax(s, axis=-1)
            if not self.decode:
                p = nn.Dropout(cfg.dropout_rate, deterministic=not train)(p)
            with jax.named_scope("context"):
                ctx = jnp.einsum("bkgts,bskd->btkgd", p,
                                 v_all.astype(jnp.float32))
                return ctx.reshape(B, T, H, D)

        if self.decode:
            # Static-shape KV cache: [B, C, Hkv, D] per layer; `index` is
            # the absolute write position.  C = max_position_embeddings,
            # or just the window with rolling_kv_cache (slot = pos mod C).
            L = cfg.max_position_embeddings
            rolling = cfg.rolling_kv_cache
            C = min(L, cfg.sliding_window) if rolling else L
            idx = ci.value
            paged = cfg.kv_page_tokens is not None
            in_place = attends_pages_in_place(cfg, T)
            if paged:
                # Paged pool: per-layer K/V is [P*pt, W] — one token's
                # heads side by side in a row of whole 128-lane tiles
                # (kv_row_width); the per-row block table (a cache
                # variable, written host-side by the batcher's admission
                # scatter) maps logical page -> physical page, sentinel
                # P = unallocated.  Writes route each position through
                # the table and DROP out-of-range ones (unallocated page,
                # or position >= max_len — e.g. a parked/finished row
                # whose counter sits at C, or a speculative verify
                # overshooting its budget).  A decode step (T == 1)
                # then attends over the pages in place
                # (attends_pages_in_place: the kernel walks the table,
                # stops at the row's length idx + 1 and reads the rows
                # as stored; a parked row reads nothing).  Any other
                # step gathers the row's full logical view
                # [B, C, Hkv, D] back in one page gather (the sentinel
                # clamps to garbage the positional mask hides), after
                # which the shared per-row mask + grouped attention
                # below apply unchanged.
                pt = cfg.kv_page_tokens
                P = cfg.kv_pool_pages
                npg = C // pt
                W = kv_row_width(Hkv, D)
                cbt = self.variable(
                    "cache", "block_table",
                    lambda: jnp.full((B, npg), P, jnp.int32))

                def store(ref, x):
                    Tw = x.shape[1]
                    with jax.named_scope("kv_store"):
                        pos = idx[:, None] + jnp.arange(Tw)[None, :]  # [B,Tw]
                        page = jnp.take_along_axis(
                            cbt.value, jnp.clip(pos // pt, 0, npg - 1),
                            axis=1)
                        phys = jnp.where(pos < C, page * pt + pos % pt,
                                         P * pt)
                        row = jnp.pad(
                            x.astype(ref.value.dtype).reshape(
                                B, Tw, Hkv * D),
                            ((0, 0), (0, 0), (0, W - Hkv * D)))
                        ref.value = ref.value.at[phys].set(row, mode="drop")
                    if in_place:
                        return None
                    with jax.named_scope("kv_gather"):
                        pool = ref.value.reshape(P, pt, W)
                        return pool[cbt.value][..., :Hkv * D].reshape(
                            B, C, Hkv, D)
            else:
                def dense_store(ref, x):
                    """Write positions idx..idx+T-1 (keeping only the last
                    C under rolling; slot indices stay unique so the
                    scatter is well-defined).  Per-row mode scatters each
                    row at its own offset."""
                    Tw = x.shape[1]
                    if per_row:
                        rows = jnp.arange(B)[:, None]
                        slots = idx[:, None] + jnp.arange(Tw)[None, :]
                        ref.value = ref.value.at[rows, slots].set(x)
                        return ref.value
                    if not rolling:
                        ref.value = jax.lax.dynamic_update_slice(
                            ref.value, x, (0, idx, 0, 0))
                        return ref.value
                    if Tw > C:
                        x = x[:, Tw - C:]
                        slots = (idx + Tw - C + jnp.arange(C)) % C
                    else:
                        slots = (idx + jnp.arange(Tw)) % C
                    ref.value = ref.value.at[:, slots].set(x)
                    return ref.value

                def store(ref, x):
                    with jax.named_scope("kv_store"):
                        return dense_store(ref, x)

            if paged:
                ck = self.variable("cache", "k", jnp.zeros,
                                   (P * pt, W), cfg.dtype)
                cv = self.variable("cache", "v", jnp.zeros,
                                   (P * pt, W), cfg.dtype)
                k_all = store(ck, k.astype(cfg.dtype))
                v_all = store(cv, v.astype(cfg.dtype))
            elif cfg.kv_cache_int8:
                # int8 values + fp32 scale per (batch, position, head);
                # symmetric over D.  Dequant happens inside the attention
                # einsum reads, so HBM sees int8 only.
                def write(vq_ref, vs_ref, x):
                    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) \
                        .astype(jnp.float32) / 127.0 + 1e-12
                    q8 = jnp.round(x.astype(jnp.float32) / s).astype(jnp.int8)
                    return store(vq_ref, q8).astype(jnp.float32) \
                        * store(vs_ref, s)

                ckq = self.variable("cache", "k_q", jnp.zeros,
                                    (B, C, Hkv, D), jnp.int8)
                cks = self.variable("cache", "k_s", jnp.zeros,
                                    (B, C, Hkv, 1), jnp.float32)
                cvq = self.variable("cache", "v_q", jnp.zeros,
                                    (B, C, Hkv, D), jnp.int8)
                cvs = self.variable("cache", "v_s", jnp.zeros,
                                    (B, C, Hkv, 1), jnp.float32)
                k_all = write(ckq, cks, k)
                v_all = write(cvq, cvs, v)
            else:
                ck = self.variable("cache", "k", jnp.zeros,
                                   (B, C, Hkv, D), cfg.dtype)
                cv = self.variable("cache", "v", jnp.zeros,
                                   (B, C, Hkv, D), cfg.dtype)
                k_all = store(ck, k.astype(cfg.dtype))
                v_all = store(cv, v.astype(cfg.dtype))
            ci.value = idx + T
            if in_place:
                with jax.named_scope("paged_decode"):
                    ctx = _paged.paged_decode_attention(
                        q[:, 0], ck.value, cv.value, cbt.value,
                        jnp.where(idx < C, idx + 1, 0), num_kv_heads=Hkv,
                        page_tokens=pt)[:, None]
            else:
                if per_row:
                    q_pos = idx[:, None] + jnp.arange(T)[None, :]    # [B, T]
                    k_pos = jnp.arange(L)
                    # [B, T, L]
                    visible = k_pos[None, None, :] <= q_pos[:, :, None]
                    if cfg.sliding_window is not None:
                        visible &= k_pos[None, None, :] \
                            > q_pos[:, :, None] - cfg.sliding_window
                elif rolling:
                    # slot s holds position p(s) = the latest pos == s
                    # (mod C); visible iff written, causal, and inside
                    # the window
                    q_pos = (idx + jnp.arange(T))[:, None]           # [T, 1]
                    p_end = idx + T - 1
                    p_slot = p_end - ((p_end - jnp.arange(C)[None, :]) % C)
                    visible = (p_slot >= 0) & (p_slot <= q_pos) \
                        & (p_slot > q_pos - cfg.sliding_window)
                else:
                    q_pos = (idx + jnp.arange(T))[:, None]           # [T, 1]
                    k_pos = jnp.arange(L)
                    visible = k_pos[None, :] <= q_pos                # [T, L]
                    if cfg.sliding_window is not None:
                        visible &= k_pos[None, :] \
                            > q_pos - cfg.sliding_window
                ctx = grouped_attention(q, k_all, v_all, visible)
        elif cfg.attention_fn is not None:
            if G > 1:  # kernels take equal head counts; broadcast K/V once
                k = jnp.repeat(k, G, axis=2)
                v = jnp.repeat(v, G, axis=2)
            if cfg.sliding_window is None:
                ctx = cfg.attention_fn(q, k, v, causal=True)
            else:
                import inspect

                sig = inspect.signature(cfg.attention_fn).parameters
                if "window" not in sig and not any(
                        p.kind == p.VAR_KEYWORD for p in sig.values()):
                    raise ValueError(
                        "sliding_window is set but attention_fn does not "
                        "accept a window= kwarg (the ring/ulysses wrappers "
                        "don't take one — for ulysses, pass "
                        "attn_fn=partial(flash_attention, window=W) to the "
                        "wrapper instead, or drop sliding_window)")
                ctx = cfg.attention_fn(q, k, v, causal=True,
                                       window=cfg.sliding_window)
        else:
            pos = jnp.arange(T)
            causal = pos[:, None] >= pos[None, :]
            if cfg.sliding_window is not None:
                causal &= pos[None, :] > pos[:, None] - cfg.sliding_window
            ctx = grouped_attention(q, k, v, causal)
        ctx = ctx.astype(cfg.dtype).reshape(B, T, H * D)
        return _dense(cfg.hidden_size, ("tp", None), cfg.dtype, "out",
                      cfg.use_bias)(ctx)


class ShortConv(nn.Module):
    """The gated short convolution of a ``"conv"`` layer (LFM2): ``[b, c,
    v] = split3(W_in u)``; ``z = b * v``; ``s_t = sum_j w[j] * z_{t - (L-1)
    + j}`` (depthwise, causal, ``L = conv_L_cache`` taps, zeros before the
    sequence's start); ``W_out (c * s)``.  No biases.

    ``decode=True`` carries the last ``L - 1`` values of ``z`` of every
    row in the ``conv_state [B, L-1, H]`` cache leaf.  ``lengths [B]`` is
    the number of VALID tokens of each right-padded row of this call: the
    state is taken there, not at the end of the padded block (a pad
    token's ``z`` is not the sequence's).  None = every token is valid."""

    cfg: GPTConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, lengths=None):
        cfg = self.cfg
        B, T, H = x.shape
        L = cfg.conv_L_cache
        with jax.named_scope("in_proj"):
            b, c, v = jnp.split(
                _dense(3 * H, (None, "tp"), cfg.dtype, "in_proj",
                       use_bias=False)(x), 3, axis=-1)
        w = self.param("conv_kernel", nn.initializers.normal(0.02), (L, H))
        with jax.named_scope("mix"):
            z = b * v
            if self.decode:
                state = self.variable("cache", "conv_state", jnp.zeros,
                                      (B, L - 1, H), cfg.dtype)
                zfull = jnp.concatenate([state.value, z], axis=1)
            else:
                zfull = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
            wf = w.astype(jnp.float32)
            s = sum(wf[j] * zfull[:, j:j + T].astype(jnp.float32)
                    for j in range(L))
            y = (c.astype(jnp.float32) * s).astype(cfg.dtype)
        if self.decode:
            with jax.named_scope("state_store"):
                if lengths is None:
                    state.value = zfull[:, T:]
                else:
                    at = lengths[:, None] + jnp.arange(L - 1)[None, :]
                    state.value = jnp.take_along_axis(
                        zfull, at[:, :, None], axis=1)
        with jax.named_scope("out_proj"):
            return _dense(H, ("tp", None), cfg.dtype, "out_proj",
                          use_bias=False)(y)


class PowerRetention(nn.Module):
    """The token mixer of a ``"retention"`` layer: power retention with
    power 2 (``ops.power_retention`` has the equations).  ``q``, ``k``,
    ``v`` as attention makes them (grouped heads, no biases, RMSNorm over
    each head's values of q and of k, rotation by absolute position), and
    one log-decay per key/value head and token, ``g = log sigmoid(W_g
    u)``; q, k, g, the state and the normaliser stay float32.

    ``decode=True`` carries ``ret_state [B, Hkv, D, F]`` and ``ret_norm
    [B, Hkv, F]`` (float32) and a position counter ``index``.  One token a
    row is the recurrent step (scope ``step``: the kernel on the TPU); a
    block of tokens is the chunked form (scope ``chunk``: the attention
    form inside the call, the carried state queried only where it holds
    something), which with ``lengths [B]`` leaves the state as it was
    after each right-padded row's last valid token.  ``decode=False`` is
    the chunked form from an empty state."""

    cfg: GPTConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, lengths=None):
        cfg = self.cfg
        B, T, _ = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        Hkv = cfg.num_kv_heads or H
        with jax.named_scope("qkvg"):
            q = _dense(H * D, (None, "tp"), cfg.dtype, "query",
                       False)(x).reshape(B, T, H, D)
            k = _dense(Hkv * D, (None, "tp"), cfg.dtype, "key",
                       False)(x).reshape(B, T, Hkv, D)
            v = _dense(Hkv * D, (None, "tp"), cfg.dtype, "value",
                       False)(x).reshape(B, T, Hkv, D)
            g = jax.nn.log_sigmoid(
                _dense(Hkv, (None, None), cfg.dtype, "gate",
                       False)(x).astype(jnp.float32))
        with jax.named_scope("qk_norm"):
            q = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                           name="q_norm")(q)
            k = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                           name="k_norm")(k)
        per_row = cfg.per_row_positions and self.decode
        ci = self.variable(
            "cache", "index",
            lambda: jnp.zeros((B,) if per_row else (), jnp.int32)) \
            if self.decode else None
        if cfg.pos_encoding == "rope":
            if per_row:
                positions = ci.value[:, None] + jnp.arange(T)[None, :]
            else:
                positions = (ci.value if ci is not None else 0) \
                    + jnp.arange(T)
            q = _rope(q, positions, cfg.rope_base)
            k = _rope(k, positions, cfg.rope_base)
        if self.decode:
            state = self.variable("cache", "ret_state", jnp.zeros,
                                  (B, Hkv, D, _retention.feature_dim(D)),
                                  jnp.float32)
            norm = self.variable("cache", "ret_norm", jnp.zeros,
                                 (B, Hkv, _retention.feature_dim(D)),
                                 jnp.float32)
            ci.value = ci.value + T
            s0, z0 = state.value, norm.value
        else:
            s0, z0 = _retention.init_state(B, Hkv, D)
        if self.decode and T == 1:
            with jax.named_scope("step"):
                num, den, s1, z1 = _retention.retention_step(
                    s0, z0, q[:, 0], k[:, 0], v[:, 0], g[:, 0])
                y = (num / (den[..., None] + cfg.retention_eps))[:, None]
        else:
            with jax.named_scope("chunk"):
                y, s1, z1 = _retention.retention_chunked(
                    s0, z0, q, k, v, g, cfg.retention_eps,
                    cfg.retention_chunk, lengths)
        if self.decode:
            state.value, norm.value = s1, z1
        with jax.named_scope("out"):
            return _dense(cfg.hidden_size, ("tp", None), cfg.dtype, "out",
                          False)(y.astype(cfg.dtype).reshape(B, T, H * D))


class Mamba2Mixer(nn.Module):
    """The token mixer of a ``"mamba2"`` layer (``ops.ssm`` has the
    recurrence): ``[z, xBC, dt] = W_in u`` of widths ``ssm_inner``,
    ``ssm_conv_channels`` and ``ssm_num_heads``; ``xBC = silu(conv(xBC) +
    b)``, depthwise and causal over ``ssm_conv_kernel`` taps, split into
    ``x [H, P]``, ``B`` and ``C [G, N]``; ``dt = softplus(dt + dt_bias)``,
    ``A = -exp(A_log)``; the recurrence, plus ``D_h x_h``; then the GATED
    GROUP NORM ``RMSNorm_groups(y * silu(z)) * w`` (the gate first, the
    mean square over each of the ``ssm_groups`` groups of channels) and
    ``W_out``.  No biases but the convolution's.  Projections are
    ``cfg.dtype`` with float32 accumulation; the convolution, ``dt``, the
    decay, the state and its update, and the norm are float32.

    ``decode=True`` carries ``ssm_state`` (float32, ``ops.ssm``'s layout)
    and ``ssm_conv [B, ssm_conv_kernel - 1, ssm_conv_channels]``, the last
    inputs of the convolution, BEFORE it.  One token a row is the
    recurrent step (scope ``step``: the kernel on the TPU); a block of
    tokens the chunked scan (scope ``scan``), which with ``lengths [B]``
    leaves both states as they were after each right-padded row's last
    valid token.  ``decode=False`` is the scan from an empty state."""

    cfg: GPTConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, lengths=None):
        cfg = self.cfg
        B, T, _ = x.shape
        H, P, G, N = (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state_size)
        inner, C, K = cfg.ssm_inner, cfg.ssm_conv_channels, \
            cfg.ssm_conv_kernel
        with jax.named_scope("in_proj"):
            z, xbc, dt = jnp.split(
                _dense(inner + C + H, (None, "tp"), cfg.dtype, "in_proj",
                       use_bias=False)(x), [inner, inner + C], axis=-1)
        w = self.param("conv_kernel", nn.initializers.normal(0.02), (K, C))
        b = self.param("conv_bias", nn.initializers.zeros, (C,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H,))
        A_log = self.param("A_log", nn.initializers.zeros, (H,))
        D = self.param("D", nn.initializers.ones, (H,))
        scale = self.param("norm_scale", nn.initializers.ones, (inner,))
        with jax.named_scope("conv"):
            if self.decode:
                tail = self.variable("cache", "ssm_conv", jnp.zeros,
                                     (B, K - 1, C), cfg.dtype)
                full = jnp.concatenate([tail.value, xbc], axis=1)
                if lengths is None:
                    tail.value = full[:, T:]
                else:
                    at = lengths[:, None] + jnp.arange(K - 1)[None, :]
                    tail.value = jnp.take_along_axis(full, at[:, :, None],
                                                     axis=1)
            else:
                full = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
            wf = w.astype(jnp.float32)
            xbc = jax.nn.silu(sum(
                wf[j] * full[:, j:j + T].astype(jnp.float32)
                for j in range(K)) + b.astype(jnp.float32))
            xs, Bm, Cm = jnp.split(xbc, [inner, inner + G * N], axis=-1)
            xs = xs.reshape(B, T, H, P)
            Bm, Cm = Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N)
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + dt_bias.astype(jnp.float32))
            A = -jnp.exp(A_log.astype(jnp.float32))
        if self.decode:
            state = self.variable(
                "cache", "ssm_state", jnp.zeros,
                _ssm.state_shape(B, H, P, G, N), jnp.float32)
            s0 = state.value
        else:
            s0 = jnp.zeros(_ssm.state_shape(B, H, P, G, N), jnp.float32)
        if self.decode and T == 1:
            with jax.named_scope("step"):
                y, s1 = _ssm.ssm_step(s0, xs[:, 0], dt[:, 0],
                                      jnp.exp(dt[:, 0] * A), Bm[:, 0],
                                      Cm[:, 0])
                y = y[:, None]
        else:
            with jax.named_scope("scan"):
                y, s1 = _ssm.ssm_chunked(s0, xs, dt, A, Bm, Cm,
                                         cfg.ssm_chunk, lengths)
        if self.decode:
            state.value = s1
        with jax.named_scope("gate_norm"):
            y = y + D.astype(jnp.float32)[:, None] * xs
            y = y.reshape(B, T, G, inner // G) * jax.nn.silu(
                z.astype(jnp.float32)).reshape(B, T, G, inner // G)
            y = y * jax.lax.rsqrt(
                jnp.mean(jnp.square(y), -1, keepdims=True) + cfg.norm_eps)
            y = (y.reshape(B, T, inner)
                 * scale.astype(jnp.float32)).astype(cfg.dtype)
        with jax.named_scope("out_proj"):
            return _dense(cfg.hidden_size, ("tp", None), cfg.dtype,
                          "out_proj", use_bias=False)(y)


def _norm(cfg: GPTConfig, name: str):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)


class DecoderBlock(nn.Module):
    """One pre-norm block: ``h = x + Op(norm(x))``, ``h + FFN(norm(h))``.
    ``layer`` picks the operator (``cfg.layer_types``: attention, the
    short convolution, power retention, a Mamba-2 mixer or the experts)
    and the FFN (the MLP, or the experts from ``cfg.num_dense_layers``
    on).  Under ``cfg.mixer_only`` the block ends after its operator."""

    cfg: GPTConfig
    decode: bool = False
    layer: int = 0

    @nn.compact
    def __call__(self, x, train: bool = False, lengths=None):
        # ``train`` is positional-or-keyword (not keyword-only) so the
        # remat wrapper below can mark it static via ``static_argnums``
        # — jax.checkpoint traces kwargs, and a traced ``train`` breaks
        # the ``not train`` dropout toggle (TracerBoolConversionError).
        from tensorflowonspark_tpu.models.moe import SparseMoE

        cfg = self.cfg
        kind = cfg.layer_type(self.layer)
        y = _norm(cfg, "ln1")(x).astype(cfg.dtype)
        if kind == "conv":
            y = ShortConv(cfg, self.decode, name="conv")(y, lengths)
        elif kind == "retention":
            y = PowerRetention(cfg, self.decode, name="ret")(y, lengths)
        elif kind == "mamba2":
            y = Mamba2Mixer(cfg, self.decode, name="ssm")(y, lengths)
        elif kind == "experts":
            y = SparseMoE(cfg, name="moe")(y)
        else:
            y = CausalSelfAttention(cfg, self.decode, name="attn")(
                y, train=train)
        y = nn.Dropout(cfg.dropout_rate, deterministic=not train)(y)
        x = x + y
        if cfg.mixer_only:
            return x
        y = _norm(cfg, "ln2")(x).astype(cfg.dtype)
        if cfg.is_expert_layer(self.layer):
            y = SparseMoE(cfg, name="moe")(y)
            y = nn.Dropout(cfg.dropout_rate, deterministic=not train)(y)
            return x + y
        if cfg.mlp == "swiglu":
            gate = _dense(cfg.intermediate_size, (None, "tp"), cfg.dtype,
                          "mlp_gate", cfg.use_bias)(y)
            up = _dense(cfg.intermediate_size, (None, "tp"), cfg.dtype,
                        "mlp_up", cfg.use_bias)(y)
            y = nn.silu(gate) * up
        else:
            y = _dense(cfg.intermediate_size, (None, "tp"), cfg.dtype,
                       "mlp_up", cfg.use_bias)(y)
            y = nn.gelu(y)
        y = _dense(cfg.hidden_size, ("tp", None), cfg.dtype, "mlp_down",
                   cfg.use_bias)(y)
        y = nn.Dropout(cfg.dropout_rate, deterministic=not train)(y)
        return x + y


class _ScanBlock(DecoderBlock):
    """Scan-body adapter: ``(carry, train) -> (carry, None)``."""

    @nn.compact
    def __call__(self, x, train):  # noqa: D102 (scan signature)
        return DecoderBlock.__call__(self, x, train=train), None


class GPT(nn.Module):
    """Causal LM: ``input_ids [B, T] -> logits [B, T, V]`` (the head is
    the embedding table, or ``lm_head`` with ``tie_word_embeddings=False``).

    ``decode=True`` builds the incremental path: each call consumes the
    next token(s), reads/writes the ``cache`` collection, and positions
    continue from the cache index.
    """

    cfg: GPTConfig
    decode: bool = False

    @nn.compact
    def hidden(self, input_ids, *, train: bool = False, lengths=None):
        """Trunk only: ``[B, T] -> [B, T, H]`` final hidden states (post
        ``ln_f``, fp32).  Pair with ``ops.tied_softmax_xent(h, table,
        labels)`` to train without materialising ``[B, T, V]`` logits.
        ``lengths [B]``: valid tokens per right-padded row, for the conv
        layers' state (:class:`ShortConv`)."""
        cfg = self.cfg
        B, T = input_ids.shape
        tok = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="tok_emb",
                       dtype=cfg.dtype,
                       embedding_init=nn.with_partitioning(
                           nn.initializers.normal(0.02), cfg.emb_spec))
        if cfg.pos_encoding != "learned":
            # rope: positions live in the attention rotations; none: in
            # the recurrent layers' order.  No table at all
            with jax.named_scope("embed"):
                x = tok(input_ids)
        else:
            if self.decode:
                per_row = cfg.per_row_positions
                start = self.variable(
                    "cache", "pos",
                    lambda: jnp.zeros((B,) if per_row else (), jnp.int32))
                if per_row:
                    positions = start.value[:, None] + jnp.arange(T)[None, :]
                else:
                    positions = start.value + jnp.arange(T)
                start.value = start.value + T
            else:
                positions = jnp.arange(T)
            pos_emb = self.param(
                "pos_emb",
                nn.with_partitioning(nn.initializers.normal(0.02),
                                     (None, None)),
                (cfg.max_position_embeddings, cfg.hidden_size))
            with jax.named_scope("embed"):
                x = tok(input_ids) + pos_emb[positions].astype(cfg.dtype)
        x = nn.Dropout(cfg.dropout_rate, deterministic=not train)(x)
        if cfg.scan_layers:
            block_cls = _ScanBlock
            if cfg.remat:
                block_cls = nn.remat(
                    _ScanBlock, static_argnums=(2,),
                    prevent_cse=False)  # scan bodies need no CSE barrier
            blocks = nn.scan(
                block_cls,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=nn.broadcast,  # `train` is config, not scanned data
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: None},
            )(cfg, self.decode, name="layers")
            x, _ = blocks(x, train)
        else:
            block_cls = DecoderBlock
            if cfg.remat:
                # remat is independent of the stacking choice: the loop
                # branch rematerialises per layer too; ``train`` must be
                # static (argnum 2, counting the module as 0) and passed
                # positionally — checkpoint kwargs are traced.  Default
                # prevent_cse=True: outside lax.scan, CSE would undo the
                # rematerialisation and restore no-remat peak memory
                block_cls = nn.remat(DecoderBlock, static_argnums=(2,))
            for i in range(cfg.num_layers):
                block = block_cls(cfg, self.decode, i, name=f"layer_{i}")
                # a dense model's call is the one it always was
                x = block(x, train) if lengths is None \
                    else block(x, train, lengths)
        if not cfg.tie_word_embeddings:
            # declared here, in the one compact method; __call__ reads it
            self.param("lm_head", nn.with_partitioning(
                nn.initializers.normal(0.02), (None, "tp")),
                (cfg.hidden_size, cfg.vocab_size))
        return _norm(cfg, "ln_f")(x)

    def __call__(self, input_ids, *, train: bool = False, lengths=None):
        """``lengths [B]`` (decode path, right-padded rows): the valid
        tokens of each row.  Conv layers take their state there, and the
        logits come back for each row's LAST VALID position only, ``[B, 1,
        V]``: a prefill wants no other, and ``[B, T, V]`` is its largest
        temporary."""
        x = self.hidden(input_ids, train=train, lengths=lengths)
        if lengths is not None:
            x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        cfg = self.cfg
        if not cfg.tie_word_embeddings:
            head = self.get_variable("params", "lm_head")
            head = getattr(head, "value", head)
            with jax.named_scope("lm_head"):
                return jnp.einsum("bth,hv->btv", x.astype(cfg.dtype),
                                  head.astype(cfg.dtype),
                                  preferred_element_type=jnp.float32)
        table = self.get_variable("params", "tok_emb")["embedding"]
        table = getattr(table, "value", table)  # unbox partitioned param
        with jax.named_scope("lm_head"):
            return jnp.einsum("bth,vh->btv", x.astype(jnp.float32),
                              table.astype(jnp.float32))


def init_cache(cfg: GPTConfig, params, batch: int):
    """Allocate the static KV cache by tracing one dummy decode step.
    Under ``kv_page_tokens`` the per-layer ``block_table`` leaves start
    at the unallocated sentinel (``kv_pool_pages``) — zeroing them would
    alias every row onto physical page 0."""
    model = GPT(cfg, decode=True)

    def trace(params):
        return model.apply({"params": params},
                           jnp.zeros((batch, 1), jnp.int32),
                           mutable=["cache"])[1]["cache"]

    # recurrent state can be the largest thing on the chip after the
    # weights (a retention layer's is 36 MB a row): its shapes are taken
    # without running the step, which would hold a second copy of it
    cache = jax.eval_shape(trace, params) if cfg.has_state \
        else trace(params)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.full(leaf.shape, cfg.kv_pool_pages,
                                    leaf.dtype)
        if any(getattr(k, "key", None) == "block_table" for k in path)
        else jnp.zeros(leaf.shape, leaf.dtype), cache)


def rewind_cache(cache, position):
    """Set every cache position counter to ``position``: the per-layer
    attention write ``index`` AND the top-level learned-position counter
    ``pos`` (stacked ``[num_layers]`` leaves under ``scan_layers`` are
    filled).  K/V payloads are untouched — callers rely on by-position
    causal masking plus their next block write to retire entries past the
    rewound position (see :func:`lookup_generate`).

    Refuses a cache with recurrent-state leaves (:data:`STATE_LEAVES`): a
    conv layer's state is the last gated inputs it saw, a retention
    layer's and a mamba2 layer's the decayed sum of every token so far
    (and the last inputs of its convolution), with no position to mask by — tokens written past ``position`` have already entered it,
    and there is no snapshot to go back to."""
    held = sorted({path[-1].key for path, _ in
                   jax.tree_util.tree_flatten_with_path(cache)[0]
                   if is_state_leaf(path)})
    if held:
        raise ValueError(
            f"rewind_cache: the cache holds {', '.join(held)} leaves (a "
            "layer_types 'conv', 'retention' or 'mamba2' layer); a "
            "recurrent state "
            "cannot be rewound without a snapshot — speculative decoding "
            "(lookup_generate, ContinuousBatcher speculative_k/set_draft) "
            "is refused for such a configuration")
    return set_cache_counters(cache, position)


def set_cache_counters(cache, position):
    """The counters half of :func:`rewind_cache`, for callers that have
    not run the cache past ``position`` in any state that is not masked
    by position (the batcher's padded prefill takes conv state at each
    row's true length: ``ShortConv``)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.full_like(leaf, position) if any(
            getattr(k, "key", None) in ("index", "pos") for k in path)
        else leaf, cache)


def _generate(cfg: GPTConfig, params, prompt_ids, max_new_tokens: int,
              next_token_fn):
    """Shared decode loop: prefill once, then ``lax.scan`` single-token
    steps against the KV cache; ``next_token_fn(logits, step_index) ->
    [B] tokens`` picks each next token.  ONE compiled program."""
    B, T0 = prompt_ids.shape
    if max_new_tokens <= 0:
        return prompt_ids
    total = T0 + max_new_tokens
    if total > cfg.max_position_embeddings:
        raise ValueError(
            f"prompt ({T0}) + max_new_tokens ({max_new_tokens}) = {total} "
            f"exceeds max_position_embeddings ({cfg.max_position_embeddings});"
            " the static cache/position table cannot hold the sequence")
    model = GPT(cfg, decode=True)

    def step(carry, i):
        tok, cache = carry
        logits, vars_ = model.apply({"params": params, "cache": cache},
                                    tok[:, None], mutable=["cache"])
        nxt = next_token_fn(logits[:, -1], i)
        return (nxt, vars_["cache"]), nxt

    cache = init_cache(cfg, params, B)
    logits, vars_ = model.apply({"params": params, "cache": cache},
                                prompt_ids, mutable=["cache"])
    first = next_token_fn(logits[:, -1], jnp.zeros((), jnp.int32))
    (_, _), rest = jax.lax.scan(step, (first, vars_["cache"]),
                                jnp.arange(1, max_new_tokens))
    generated = jnp.concatenate([first[:, None], rest.T], axis=1)
    return jnp.concatenate([prompt_ids, generated], axis=1)


def greedy_generate(cfg: GPTConfig, params, prompt_ids, max_new_tokens: int):
    """Greedy decode (argmax each step); see :func:`_generate`.
    Returns ``[B, prompt_len + max_new_tokens]`` token ids."""
    return _generate(cfg, params, prompt_ids, max_new_tokens,
                     lambda logits, i: jnp.argmax(logits, axis=-1))


def lookup_generate(cfg: GPTConfig, params, prompt_ids,
                    max_new_tokens: int, *, ngram: int = 3,
                    draft_len: int = 8, return_stats: bool = False):
    """Prompt-lookup speculative decoding — greedy-exact tokens in fewer
    sequential forwards.

    Single-chip decode is HBM-bound: every forward reads all the weights
    to emit ONE token.  Speculation drafts ``draft_len`` candidate tokens
    for free (the longest recent ``ngram`` context match inside the
    sequence so far — no draft model), then verifies them in one cached
    forward over the ``draft_len + 1`` block; the accepted prefix commits
    several tokens per weight read.  Greedy verification accepts exactly
    the tokens greedy decode would emit, so the output is **identical to**
    :func:`greedy_generate` — only the forward count changes (it falls
    toward ``max_new / (draft_len+1)`` on repetitive continuations —
    extraction, code, summaries quoting the prompt — and degrades to one
    token per forward on novel text).

    Mechanics: the verify block is written into the static KV cache at
    positions ``p..p+draft_len``, then the per-layer cache ``index`` is
    REWOUND to the committed length; by-position causal masking plus the
    next block's overlapping write keep rejected tail entries invisible.
    With batches, the committed length is shared (one cache index), so
    each step advances by the batch-minimum acceptance.

    Prompts shorter than ``ngram`` work (output is still greedy-exact) but
    draft quality is degraded for the first blocks: until ``ngram`` tokens
    are committed the match window is clamped to start at position 0.

    Returns ``[B, T0 + max_new_tokens]`` ids (+ a ``{"forwards": n}``
    dict with ``return_stats=True``; ``forwards`` counts verify steps
    after the prefill).
    """
    B, T0 = prompt_ids.shape
    if cfg.has_state:
        raise ValueError(
            "lookup_generate rewinds the cache after every verify block, "
            f"and this configuration keeps {cfg.cache_kinds}: a recurrent "
            "state cannot be rewound")
    if max_new_tokens <= 0:
        return (prompt_ids, {"forwards": jnp.zeros((), jnp.int32)}) \
            if return_stats else prompt_ids
    if ngram < 1 or draft_len < 1:
        raise ValueError(f"ngram ({ngram}) and draft_len ({draft_len}) "
                         "must be >= 1")
    if cfg.rolling_kv_cache:
        raise ValueError("lookup_generate does not support "
                         "rolling_kv_cache (the rewind protocol assumes "
                         "absolute cache slots)")
    total = T0 + max_new_tokens
    k = draft_len
    if total + k > cfg.max_position_embeddings:
        raise ValueError(
            f"prompt + max_new_tokens + draft_len = {total + k} exceeds "
            f"max_position_embeddings ({cfg.max_position_embeddings}); "
            "the verify block needs draft_len slack past the sequence")
    model = GPT(cfg, decode=True)
    Lbuf = total + k  # committed tokens + scratch for one verify block
    g = ngram

    def draft(toks, p):
        """Longest-match prompt lookup: most recent window of the last
        ``g`` tokens inside ``toks[:, :p+1]``; its continuation is the
        draft, repeating the final token past the known prefix."""
        starts = jnp.arange(Lbuf - g)
        win = toks[:, starts[:, None] + jnp.arange(g)[None, :]]  # [B,S,g]
        # short prompts: p+1-g goes negative until g tokens are committed;
        # clamp explicitly (dynamic_slice would clamp silently) — the
        # suffix window then starts at 0 and can include not-yet-committed
        # buffer positions, degrading draft quality for those first blocks
        # while the output stays greedy-exact (every draft is verified)
        last = jax.lax.dynamic_slice(
            toks, (0, jnp.maximum(p + 1 - g, 0)), (B, g))        # [B, g]
        hit = jnp.all(win == last[:, None, :], axis=-1)
        # window fully inside committed tokens with its continuation at
        # <= p — this also excludes the current suffix itself
        hit &= (starts + g <= p)[None, :]
        best = jnp.argmax(hit * (starts + 1)[None, :], axis=-1)  # [B]
        has = jnp.any(hit, axis=-1)
        src = best[:, None] + g + jnp.arange(k)[None, :]         # [B, k]
        src = jnp.where(has[:, None], jnp.minimum(src, p), p)
        return jnp.take_along_axis(toks, src, axis=1)            # [B, k]

    def cond(carry):
        _, p, _, _, _ = carry
        return p < total

    def body(carry):
        toks, p, pending, cache, n_fwd = carry
        toks = jax.lax.dynamic_update_slice(toks, pending[:, None], (0, p))
        drafts = draft(toks, p)
        x = jnp.concatenate([pending[:, None], drafts], axis=1)
        logits, vars_ = model.apply({"params": params, "cache": cache},
                                    x, mutable=["cache"])
        preds = jnp.argmax(logits, axis=-1)                      # [B, k+1]
        agree = jnp.cumprod(
            (preds[:, :-1] == drafts).astype(jnp.int32), axis=1)
        a = jnp.min(jnp.sum(agree, axis=1))  # batch-min acceptance
        toks = jax.lax.dynamic_update_slice(toks, drafts, (0, p + 1))
        pending = preds[:, a].astype(toks.dtype)
        p = p + 1 + a
        return toks, p, pending, rewind_cache(vars_["cache"], p), n_fwd + 1

    cache = init_cache(cfg, params, B)
    logits, vars_ = model.apply({"params": params, "cache": cache},
                                prompt_ids, mutable=["cache"])
    toks = jnp.zeros((B, Lbuf), prompt_ids.dtype)
    toks = jax.lax.dynamic_update_slice(toks, prompt_ids, (0, 0))
    carry = (toks, jnp.asarray(T0, jnp.int32),
             jnp.argmax(logits[:, -1], axis=-1).astype(prompt_ids.dtype),
             vars_["cache"], jnp.zeros((), jnp.int32))
    toks, p, _, _, n_fwd = jax.lax.while_loop(cond, body, carry)
    out = toks[:, :total]
    return (out, {"forwards": n_fwd}) if return_stats else out


def _select_beam(scores, lengths, length_penalty: float):
    """argmax over beams of ``score / generated_len**length_penalty`` —
    modern HF's ``BeamHypotheses`` normalization (transformers >= 4.38
    passes ``generated_len = cur_len - decoder_prompt_len``: prompt
    excluded, EOS included); raw-score argmax when the penalty is 0."""
    sel = scores if length_penalty == 0.0 else \
        scores / lengths.astype(jnp.float32) ** length_penalty
    return jnp.argmax(sel, axis=-1)


def beam_generate(cfg: GPTConfig, params, prompt_ids, max_new_tokens: int,
                  *, num_beams: int = 4, eos_id: int | None = None,
                  length_penalty: float = 0.0, return_scores: bool = False):
    """Beam-search decode: ONE compiled program, like the other decoders.

    Beams ride the batch axis (``B·K`` rows) so every step is the same
    static-shape cached forward the greedy path uses; the per-step beam
    reorder is a gather over the cache's leading axis.  The prompt is
    prefilled ONCE at batch ``B`` and the cache tiled to ``B·K`` — no
    K-fold prefill cost.  With ``eos_id`` a finished beam is frozen (only
    its EOS continuation survives, score unchanged).  Returns the best
    beam ``[B, T0 + max_new_tokens]`` (and its raw log-prob sum ``[B]``
    when ``return_scores``).

    ``length_penalty`` selects the best beam by
    ``score / generated_len**length_penalty`` where ``generated_len``
    counts generated tokens up to and including EOS (prompt excluded) —
    modern HF's ``BeamHypotheses`` normalization (transformers >= 4.38;
    older releases divided by the full prompt-inclusive length).  The
    default 0.0 compares raw log-prob sums, which — with finished beams
    frozen at constant score — biases toward shorter sequences relative
    to HF's default of 1.0; pass 1.0 for HF-equivalent selection.
    """
    B, T0 = prompt_ids.shape
    K = int(num_beams)
    if K < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    if eos_id is not None and not 0 <= eos_id < cfg.vocab_size:
        raise ValueError(
            f"eos_id {eos_id} out of range for vocab_size {cfg.vocab_size}")
    if max_new_tokens <= 0:
        return (prompt_ids, jnp.zeros((B,))) if return_scores else prompt_ids
    total = T0 + max_new_tokens
    if total > cfg.max_position_embeddings:
        raise ValueError(
            f"prompt ({T0}) + max_new_tokens ({max_new_tokens}) = {total} "
            f"exceeds max_position_embeddings ({cfg.max_position_embeddings})")
    model = GPT(cfg, decode=True)
    V = cfg.vocab_size
    N = max_new_tokens
    NEG = jnp.float32(-1e30)

    def map_cache_batch(cache, batch, fn):
        """Apply ``fn(x, axis)`` to every batch-carrying cache leaf.  Under
        ``scan_layers`` the stacked per-layer leaves (under "layers") carry
        batch on axis 1 behind the layer axis; path-based detection, not
        shape-matching, so num_layers == batch coincidences can't misfire.
        Stacked scalars (per-layer ``index``, shape [layers]) fall through
        the ndim check."""
        def visit(path, x):
            top = getattr(path[0], "key", None) if path else None
            axis = 1 if (cfg.scan_layers and top == "layers") else 0
            if x.ndim > axis and x.shape[axis] == batch:
                return fn(x, axis)
            return x
        return jax.tree_util.tree_map_with_path(visit, cache)

    # prefill at batch B, then tile every batch axis of the cache to B*K
    cache = init_cache(cfg, params, B)
    logits, vars_ = model.apply({"params": params, "cache": cache},
                                prompt_ids, mutable=["cache"])
    logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))  # [B, V]
    cache = map_cache_batch(vars_["cache"], B,
                            lambda x, ax: jnp.repeat(x, K, axis=ax))
    frozen = jnp.full((V,), NEG).at[eos_id].set(0.0) \
        if eos_id is not None else None

    # beam 0 holds the top-1, beams 1.. the runners-up; all live
    scores, tok = jax.lax.top_k(logp0, K)                  # [B, K] each
    seqs = jnp.zeros((B, K, N), jnp.int32)
    seqs = seqs.at[:, :, 0].set(tok)
    finished = (tok == eos_id) if eos_id is not None \
        else jnp.zeros((B, K), bool)
    lengths = jnp.ones((B, K), jnp.int32)  # generated tokens incl. EOS

    def step(carry, i):
        seqs, scores, tok, finished, lengths, cache = carry
        logits, vars_ = model.apply(
            {"params": params, "cache": cache},
            tok.reshape(B * K)[:, None], mutable=["cache"])
        logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32)) \
            .reshape(B, K, V)
        if eos_id is not None:
            # frozen beams: only the EOS continuation survives, at cost 0
            logp = jnp.where(finished[:, :, None], frozen[None, None], logp)
        cand = scores[:, :, None] + logp                    # [B, K, V]
        scores, idx = jax.lax.top_k(cand.reshape(B, K * V), K)
        parent, tok = idx // V, idx % V                     # [B, K] each
        # reorder beam state (and the cache rows) by parent
        take = lambda a: jnp.take_along_axis(a, parent, axis=1)  # noqa: E731
        seqs = jnp.take_along_axis(
            seqs, parent[:, :, None], axis=1).at[:, :, i].set(tok)
        was_finished = take(finished)
        finished = was_finished | ((tok == eos_id) if eos_id is not None
                                   else False)
        # a beam not finished BEFORE this token grew to i+1 tokens
        lengths = jnp.where(was_finished, take(lengths), i + 1)
        flat_parent = (jnp.arange(B)[:, None] * K + parent).reshape(B * K)
        cache = map_cache_batch(
            vars_["cache"], B * K,
            lambda x, ax: jnp.take(x, flat_parent, axis=ax))
        return (seqs, scores, tok, finished, lengths, cache), None

    (seqs, scores, _, _, lengths, _), _ = jax.lax.scan(
        step, (seqs, scores, tok, finished, lengths, cache),
        jnp.arange(1, N))
    best = _select_beam(scores, lengths, length_penalty)    # [B]
    out = jnp.take_along_axis(seqs, best[:, None, None], axis=1)[:, 0]
    out = jnp.concatenate([prompt_ids, out], axis=1)
    if return_scores:
        return out, jnp.take_along_axis(scores, best[:, None], axis=1)[:, 0]
    return out


def nucleus_filter(logits, top_p):
    """Top-p (nucleus) truncation on (already temperature-scaled) logits:
    keep the smallest descending-sorted prefix whose mass reaches
    ``top_p`` (HF order; the top token always survives), masking the rest
    to ``-inf``.  Tokens TIED at the cutoff logit are all kept (threshold
    semantics).  Shared by :func:`sample_generate` and the serving
    batcher's per-row sampler (``models/serving.py``) so the two can
    never drift.  Works on ``[..., V]``."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = cum - probs < top_p  # mass BEFORE this token
    kept_min = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1,
        keepdims=True)
    return jnp.where(logits < kept_min, -jnp.inf, logits)


def sample_generate(cfg: GPTConfig, params, prompt_ids, max_new_tokens: int,
                    rng, *, temperature: float = 1.0,
                    top_k: int | None = None, top_p: float | None = None):
    """Stochastic decode: temperature-scaled categorical sampling with
    optional top-k and/or top-p (nucleus) truncation, one compiled program
    like :func:`greedy_generate`.  ``rng`` is a ``jax.random`` key; each
    step folds in its index so the whole rollout is reproducible.  With
    both filters set, top-k applies first (HF convention)."""
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")

    def next_token(logits, i):
        if top_k is not None:  # rank-invariant: pre- or post-temperature
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if temperature == 0.0:  # greedy limit
            return jnp.argmax(logits, axis=-1)
        logits = logits / temperature
        if top_p is not None and top_p < 1.0:
            logits = nucleus_filter(logits, top_p)
        return jax.random.categorical(jax.random.fold_in(rng, i),
                                      logits, axis=-1)

    return _generate(cfg, params, prompt_ids, max_new_tokens, next_token)
