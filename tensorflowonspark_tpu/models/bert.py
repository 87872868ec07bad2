"""BERT-style transformer encoder with mesh-aware sharding.

Reference workload: "BERT-base SQuAD fine-tune via Spark ML TFEstimator
pipeline" (``BASELINE.json`` configs[3]); the reference itself has no model
code — users bring Keras models — so this is the rebuild's flagship model,
designed TPU-first:

- kernels carry GSPMD partitioning annotations: QKV/up projections shard
  their output dim over ``tp``, output/down projections their input dim
  (the Megatron pattern — one all-reduce per block, emitted by XLA);
- embeddings shard over ``tp`` rows;
- attention is pluggable: dense softmax by default, ring attention
  (``parallel.ring_attention``) for sequence-parallel long-context runs;
- bf16 activations, fp32 layernorms/softmax/logits.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)


def _context_mesh():
    """The mesh from the enclosing ``jax.set_mesh`` / ``with mesh:`` scope,
    or None when tracing outside any mesh context (single-device use,
    ``eval_shape``) — where a bare-PartitionSpec sharding constraint would
    raise."""
    m = jax.sharding.get_abstract_mesh()
    if not m.empty:
        return m
    try:
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            from jax.interpreters import pxla

            m = pxla.thread_resources.env.physical_mesh
    # tfos: ignore[broad-except] — probing a deprecated jax internal for an
    # ambient mesh; any failure just means "no mesh", the supported default
    except Exception:
        return None
    return None if m.empty else m


@functools.cache
def _warn_no_attention_dropout() -> None:
    """Custom attention kernels (ring/flash) compute softmax online inside
    the loop and do not materialize attention probabilities, so the
    attention-probability dropout of the dense path cannot be applied there
    (post-attention and MLP dropout still are).  Warn once so the config
    divergence is explicit rather than silent."""
    logger.warning(
        "BertConfig.dropout_rate > 0 with a custom attention_fn: "
        "attention-probability dropout is not applied on this path "
        "(residual/MLP dropout still is)")


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16
    # Optional global-array attention override, e.g.
    # ``partial(ring_self_attention, mesh, causal=False)``; signature
    # ``(q, k, v, mask=None) -> out`` with [batch, seq, heads, head_dim]
    # arrays and an optional [batch, seq] key-padding mask.
    attention_fn: Callable | None = None
    # PartitionSpec entries for embedding tables (vocab, features).  Default
    # shards vocab rows over tp; pass (("ep", "tp"), None) to also spread
    # tables over the embedding-shard axis (the num_ps analogue).
    emb_spec: tuple = ("tp", None)
    # PartitionSpec entries for activations (batch, seq, feature).  When
    # set, the embedding-lookup outputs are pinned with
    # ``with_sharding_constraint`` so GSPMD partitions the gather
    # index-parallel (each device looks up its own batch rows) instead of
    # inheriting the table's sharding and paying an "involuntary full
    # rematerialization" reshard when a table dim is weight-sharded (e.g.
    # ZeRO-3/fsdp on the feature dim).  Requires tracing under a mesh
    # context (``with mesh:``); leave None for single-device use.
    act_spec: tuple | None = None
    # Stack encoder layers with nn.scan (+ nn.remat): one traced block,
    # O(1)-in-depth compile time, per-layer rematerialisation — the same
    # knobs as GPTConfig (params gain a leading ``layers`` axis).
    scan_layers: bool = False
    remat: bool = False
    # Numerics knobs for checkpoint interchange (models/convert.py): HF
    # BERT uses exact erf-gelu and LayerNorm eps 1e-12; the defaults keep
    # this module's original behavior (tanh gelu, flax eps 1e-6).
    norm_eps: float = 1e-6
    gelu_exact: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def _dense(features, spec, dtype, name=None, use_bias=True):
    return nn.Dense(
        features, use_bias=use_bias, dtype=dtype, name=name,
        kernel_init=nn.with_partitioning(
            nn.initializers.normal(stddev=0.02), spec))


class SelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask=None, *, train: bool = False):
        cfg = self.cfg
        B, T, _ = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        qkv_spec = (None, "tp")
        q = _dense(H * D, qkv_spec, cfg.dtype, "query")(x).reshape(B, T, H, D)
        k = _dense(H * D, qkv_spec, cfg.dtype, "key")(x).reshape(B, T, H, D)
        v = _dense(H * D, qkv_spec, cfg.dtype, "value")(x).reshape(B, T, H, D)

        if cfg.attention_fn is not None:
            if train and cfg.dropout_rate > 0:
                _warn_no_attention_dropout()
            ctx = cfg.attention_fn(q, k, v, mask=mask)
        else:
            scale = D ** -0.5
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                           k.astype(jnp.float32)) * scale
            if mask is not None:
                s = jnp.where(mask[:, None, None, :], s, -1e30)
            p = nn.softmax(s, axis=-1)
            p = nn.Dropout(cfg.dropout_rate, deterministic=not train)(p)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
        ctx = ctx.astype(cfg.dtype).reshape(B, T, H * D)
        return _dense(cfg.hidden_size, ("tp", None), cfg.dtype, "out")(ctx)


class EncoderLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask=None, train: bool = False):
        # ``train`` positional-or-keyword so the loop-branch remat can
        # mark it static (checkpoint kwargs are traced; see gpt.py)
        cfg = self.cfg
        y = SelfAttention(cfg, name="attn")(x, mask, train=train)
        y = nn.Dropout(cfg.dropout_rate, deterministic=not train)(y)
        x = nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.norm_eps,
                         name="ln_attn")(x + y).astype(cfg.dtype)
        y = _dense(cfg.intermediate_size, (None, "tp"), cfg.dtype, "mlp_up")(x)
        y = nn.gelu(y, approximate=not cfg.gelu_exact)
        y = _dense(cfg.hidden_size, ("tp", None), cfg.dtype, "mlp_down")(y)
        y = nn.Dropout(cfg.dropout_rate, deterministic=not train)(y)
        return nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.norm_eps,
                            name="ln_mlp")(x + y).astype(cfg.dtype)


class _ScanEncoderLayer(EncoderLayer):
    """Scan-body adapter: ``(carry, mask, train) -> (carry, None)``."""

    @nn.compact
    def __call__(self, x, mask, train):  # noqa: D102 (scan signature)
        return EncoderLayer.__call__(self, x, mask, train=train), None


class Bert(nn.Module):
    """Encoder trunk: ``(input_ids, attention_mask, token_type_ids) →
    sequence of hidden states``."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 *, train: bool = False):
        cfg = self.cfg
        T = input_ids.shape[1]
        emb_init = nn.with_partitioning(nn.initializers.normal(0.02), cfg.emb_spec)
        if cfg.act_spec is not None and _context_mesh() is not None:
            P = jax.sharding.PartitionSpec
            anchor = lambda v: jax.lax.with_sharding_constraint(
                v, P(*cfg.act_spec))
            # pos lookup has batch dim 1 — only its seq/feature dims can
            # carry the activation sharding
            anchor_pos = lambda v: jax.lax.with_sharding_constraint(
                v, P(None, *cfg.act_spec[1:]))
        else:
            anchor = anchor_pos = lambda v: v
        tok = anchor(nn.Embed(cfg.vocab_size, cfg.hidden_size,
                              embedding_init=emb_init, dtype=cfg.dtype,
                              name="tok_emb")(input_ids))
        pos = anchor_pos(nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                                  embedding_init=emb_init, dtype=cfg.dtype,
                                  name="pos_emb")(jnp.arange(T)[None, :]))
        x = tok + pos
        if token_type_ids is not None:
            x = x + anchor(nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                                    embedding_init=emb_init, dtype=cfg.dtype,
                                    name="type_emb")(token_type_ids))
        x = nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.norm_eps,
                         name="ln_emb")(x).astype(cfg.dtype)
        x = nn.Dropout(cfg.dropout_rate, deterministic=not train)(x)
        if cfg.scan_layers:
            block_cls = _ScanEncoderLayer
            if cfg.remat:
                block_cls = nn.remat(_ScanEncoderLayer, static_argnums=(3,),
                                     prevent_cse=False)
            blocks = nn.scan(
                block_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=nn.broadcast,  # mask/train are config, not scanned
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: None},
            )(cfg, name="layers")
            x, _ = blocks(x, attention_mask, train)
        else:
            # ``train`` static (argnum 3: module, x, mask, train) and
            # positional — a traced kwarg breaks ``not train`` dropout
            # toggles; default prevent_cse=True holds outside lax.scan
            block_cls = (nn.remat(EncoderLayer, static_argnums=(3,))
                         if cfg.remat else EncoderLayer)
            for i in range(cfg.num_layers):
                x = block_cls(cfg, name=f"layer_{i}")(x, attention_mask,
                                                      train)
        return x


class BertForQuestionAnswering(nn.Module):
    """SQuAD-style span head: start/end logits per position."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 *, train: bool = False):
        x = Bert(self.cfg, name="bert")(input_ids, attention_mask,
                                        token_type_ids, train=train)
        logits = nn.Dense(2, dtype=jnp.float32, name="qa_head")(x)
        start, end = logits[..., 0], logits[..., 1]
        return start, end


class BertForSequenceClassification(nn.Module):
    cfg: BertConfig
    num_classes: int = 2

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 *, train: bool = False):
        x = Bert(self.cfg, name="bert")(input_ids, attention_mask,
                                        token_type_ids, train=train)
        pooled = jnp.tanh(nn.Dense(self.cfg.hidden_size, dtype=jnp.float32,
                                   name="pooler")(x[:, 0].astype(jnp.float32)))
        pooled = nn.Dropout(self.cfg.dropout_rate, deterministic=not train)(pooled)
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="cls_head")(pooled)
