"""BERT family — bidirectional encoder matching the reference's BERT-base
FusedLAMB + FusedLayerNorm benchmark config (ref BASELINE; primitives from
apex/normalization/fused_layer_norm.py and apex.optimizers.FusedLAMB).

Functional conventions match :mod:`apex_tpu.models.llama`; attention is
bidirectional with an optional padding mask through
``scaled_masked_softmax`` (ref apex/transformer/functional/fused_softmax.py:94).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.models._common import (
    fan_in_normal,
    layer_norm,
    packed_mlp,
    packed_qkv_attention,
)

from apex_tpu.transformer.functional.fused_softmax import scaled_masked_softmax
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    vocab_parallel_embedding,
)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30528  # 30522 padded for tp/tile divisibility
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 512
    num_types: int = 2
    ln_eps: float = 1e-12
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def bert_base(**over) -> BertConfig:
    return BertConfig(**over)


def tiny(**over) -> BertConfig:
    kw = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
              max_seq_len=64, dtype=jnp.float32)
    kw.update(over)
    return BertConfig(**kw)


def init_params(key, cfg: BertConfig):
    h, L = cfg.hidden_size, cfg.num_layers
    dt = cfg.dtype
    ks = jax.random.split(key, 8)

    def norm(k, *shape, fan_in=None):
        return fan_in_normal(k, *shape, fan_in=fan_in, dtype=dt)

    return {
        "embed": norm(ks[0], cfg.vocab_size, h, fan_in=h),
        "pos_embed": norm(ks[1], cfg.max_seq_len, h, fan_in=h),
        "type_embed": norm(ks[2], cfg.num_types, h, fan_in=h),
        "emb_ln_w": jnp.ones((h,), dt), "emb_ln_b": jnp.zeros((h,), dt),
        "layers": {
            "wqkv": norm(ks[3], L, h, 3, h, fan_in=h),
            "bqkv": jnp.zeros((L, 3, h), dt),
            "wo": norm(ks[4], L, h, h), "bo": jnp.zeros((L, h), dt),
            "ln1_w": jnp.ones((L, h), dt), "ln1_b": jnp.zeros((L, h), dt),
            "wfc": norm(ks[5], L, h, 4 * h), "bfc": jnp.zeros((L, 4 * h), dt),
            "wproj": norm(ks[6], L, 4 * h, h), "bproj": jnp.zeros((L, h), dt),
            "ln2_w": jnp.ones((L, h), dt), "ln2_b": jnp.zeros((L, h), dt),
        },
        "mlm_dense": norm(ks[7], h, h),
        "mlm_bias": jnp.zeros((h,), dt),
        "mlm_ln_w": jnp.ones((h,), dt), "mlm_ln_b": jnp.zeros((h,), dt),
    }


def param_specs(cfg: BertConfig, tp_axis: str = "tp",
                with_decoder_bias: bool = False):
    """tp PartitionSpec pytree matching :func:`init_params`
    (``with_decoder_bias`` adds the HF-imported ``mlm_decoder_bias``
    entry, models/convert.py)."""
    from jax.sharding import PartitionSpec as P

    t = tp_axis
    # the decoder bias adds onto the vocab-LOCAL logits → vocab-sharded
    extra = {"mlm_decoder_bias": P(t)} if with_decoder_bias else {}
    return {**extra,
        "embed": P(t, None), "pos_embed": P(), "type_embed": P(),
        "emb_ln_w": P(), "emb_ln_b": P(),
        "layers": {
            "wqkv": P(None, None, None, t), "bqkv": P(None, None, t),
            "wo": P(None, t, None), "bo": P(),
            "ln1_w": P(), "ln1_b": P(),
            "wfc": P(None, None, t), "bfc": P(None, t),
            "wproj": P(None, t, None), "bproj": P(),
            "ln2_w": P(), "ln2_b": P(),
        },
        "mlm_dense": P(), "mlm_bias": P(),
        "mlm_ln_w": P(), "mlm_ln_b": P(),
    }


_ln = layer_norm


def _attention(x, lp, cfg: BertConfig, pad_mask, tp_axis):
    def attend(q, k, v):
        # mask: True = masked-out key (ref scaled_masked_softmax semantics)
        mask = None if pad_mask is None else pad_mask[:, None, None, :]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        probs = scaled_masked_softmax(scores, mask, q.shape[-1] ** -0.5)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)

    return packed_qkv_attention(x, lp, cfg.num_heads, cfg.head_dim, attend,
                                tp_axis)


def _mlp(x, lp, tp_axis):
    return packed_mlp(x, lp, lambda y: jax.nn.gelu(y, approximate=False),
                      tp_axis)


def encoder_layer(x, lp, cfg: BertConfig, pad_mask,
                  tp_axis: Optional[str] = "tp"):
    """Post-norm block (original BERT residual order)."""
    x = _ln(x + _attention(x, lp, cfg, pad_mask, tp_axis),
            lp["ln1_w"], lp["ln1_b"], cfg.ln_eps)
    x = _ln(x + _mlp(x, lp, tp_axis), lp["ln2_w"], lp["ln2_b"], cfg.ln_eps)
    return x


def forward(params, tokens, cfg: BertConfig, type_ids=None, pad_mask=None,
            tp_axis: Optional[str] = "tp", remat: bool = True):
    """tokens [b, s] → hidden states [b, s, h]."""
    b, s = tokens.shape
    x = vocab_parallel_embedding(tokens, params["embed"], axis_name=tp_axis)
    x = x + params["pos_embed"][None, :s]
    if type_ids is None:
        x = x + params["type_embed"][0]
    else:
        x = x + jnp.take(params["type_embed"], type_ids, axis=0)
    x = _ln(x.astype(cfg.dtype), params["emb_ln_w"], params["emb_ln_b"],
            cfg.ln_eps)

    def body(h, lp):
        return encoder_layer(h, lp, cfg, pad_mask, tp_axis), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return x


def mlm_transform(params, hidden, cfg: BertConfig):
    """The pre-decoder MLM head transform: dense + gelu + LN."""
    x = jnp.matmul(hidden, params["mlm_dense"].astype(hidden.dtype))
    x = jax.nn.gelu(x + params["mlm_bias"], approximate=False)
    return _ln(x, params["mlm_ln_w"], params["mlm_ln_b"], cfg.ln_eps)


def mlm_logits(params, hidden, cfg: BertConfig,
               tp_axis: Optional[str] = "tp"):
    """Masked-LM head: dense+gelu+LN, tied decoder → [b, s, v_local].
    An optional ``mlm_decoder_bias`` [vocab] (HF BERT's
    cls.predictions.bias) adds per-vocab offsets when present."""
    x = mlm_transform(params, hidden, cfg)
    logits = jnp.matmul(
        x, params["embed"].T.astype(x.dtype)).astype(jnp.float32)
    if "mlm_decoder_bias" in params:
        logits = logits + params["mlm_decoder_bias"].astype(jnp.float32)
    return logits


def loss_fn(params, batch, cfg: BertConfig, type_ids=None, pad_mask=None,
            tp_axis: Optional[str] = "tp", remat: bool = True,
            vocab_chunks: Optional[int] = None):
    """MLM loss; ``batch = (tokens, targets, loss_mask)`` — loss_mask selects
    the masked positions (targets elsewhere are ignored). ``pad_mask``
    (True = padding) masks attention; the loss_mask only masks the CE sum.
    ``vocab_chunks`` streams the tied decoder + CE without materializing
    the fp32 [b·s, vocab] logits (functional/chunked_ce.py)."""
    tokens, targets, loss_mask = batch
    hidden = forward(params, tokens, cfg, type_ids=type_ids,
                     pad_mask=pad_mask, tp_axis=tp_axis, remat=remat)
    if vocab_chunks:
        from apex_tpu.transformer.functional.chunked_ce import (
            chunked_lm_cross_entropy,
        )
        from apex_tpu.transformer.tensor_parallel.mappings import (
            _axis_bound,
        )

        x = mlm_transform(params, hidden, cfg)
        losses = chunked_lm_cross_entropy(
            x.reshape(-1, x.shape[-1]), params["embed"].T,
            targets.reshape(-1), vocab_chunks,
            tp_axis=tp_axis if _axis_bound(tp_axis) else None,
            bias=params.get("mlm_decoder_bias"))
        losses = losses.reshape(targets.shape)
    else:
        logits = mlm_logits(params, hidden, cfg, tp_axis)
        losses = vocab_parallel_cross_entropy(logits, targets,
                                              axis_name=tp_axis)
    denom = jnp.maximum(jnp.sum(loss_mask), 1.0)
    return jnp.sum(losses * loss_mask) / denom
