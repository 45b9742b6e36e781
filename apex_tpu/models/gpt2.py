"""GPT-2 family — Megatron-style TP transformer with learned positions.

Corresponds to the reference's GPT-2 345M benchmark config (Apex transformer
primitives assembled Megatron-LM-style: LayerNorm + TP linear layers — ref
apex/transformer/tensor_parallel/layers.py — with causal self-attention
through the flash kernels, ref apex/contrib/fmha, so no [s, s] score square
reaches HBM in the forward, its recomputation or the backward). Same
functional conventions as :mod:`apex_tpu.models.llama`: stacked [L, ...]
layer params under ``lax.scan``, collectives no-op when the tp axis is
unbound.
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

from apex_tpu.observability import scope
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.transformer.tensor_parallel.mappings import (
    _axis_bound,
)
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    vocab_parallel_embedding,
)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304  # 50257 padded to a tp/128-friendly multiple
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    max_seq_len: int = 1024
    ln_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def gpt2_345m(**over) -> GPT2Config:
    return GPT2Config(**over)


def tiny(**over) -> GPT2Config:
    kw = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
              max_seq_len=64, dtype=jnp.float32)
    kw.update(over)
    return GPT2Config(**kw)


def init_params(key, cfg: GPT2Config):
    h, L = cfg.hidden_size, cfg.num_layers
    dt = cfg.dtype
    ks = jax.random.split(key, 8)

    def norm(k, *shape, fan_in=None):
        return fan_in_normal(k, *shape, fan_in=fan_in, dtype=dt)

    return {
        "embed": norm(ks[0], cfg.vocab_size, h, fan_in=h),
        "pos_embed": norm(ks[1], cfg.max_seq_len, h, fan_in=h),
        "layers": {
            "ln1_w": jnp.ones((L, h), dt), "ln1_b": jnp.zeros((L, h), dt),
            # packed qkv, [L, h, 3, h] so P(..., 'tp') on the LAST dim
            # shards each of q/k/v by heads (Megatron packing, ref
            # tensor_parallel/layers.py ColumnParallelLinear qkv use)
            "wqkv": norm(ks[2], L, h, 3, h, fan_in=h),
            "bqkv": jnp.zeros((L, 3, h), dt),
            "wo": norm(ks[3], L, h, h), "bo": jnp.zeros((L, h), dt),
            "ln2_w": jnp.ones((L, h), dt), "ln2_b": jnp.zeros((L, h), dt),
            "wfc": norm(ks[4], L, h, 4 * h), "bfc": jnp.zeros((L, 4 * h), dt),
            "wproj": norm(ks[5], L, 4 * h, h), "bproj": jnp.zeros((L, h), dt),
        },
        "lnf_w": jnp.ones((h,), dt), "lnf_b": jnp.zeros((h,), dt),
    }


def param_specs(cfg: GPT2Config, tp_axis: str = "tp"):
    """tp PartitionSpec pytree matching :func:`init_params`."""
    from jax.sharding import PartitionSpec as P

    t = tp_axis
    return {
        "embed": P(t, None), "pos_embed": P(),
        "layers": {
            "ln1_w": P(), "ln1_b": P(),
            "wqkv": P(None, None, None, t), "bqkv": P(None, None, t),
            "wo": P(None, t, None), "bo": P(),
            "ln2_w": P(), "ln2_b": P(),
            "wfc": P(None, None, t), "bfc": P(None, t),
            "wproj": P(None, t, None), "bproj": P(),
        },
        "lnf_w": P(), "lnf_b": P(),
    }


_ln = layer_norm


def _causal_attend(q, k, v):
    return flash_attention(q, k, v, causal=True, scale=q.shape[-1] ** -0.5)


def _attention(x, lp, cfg: GPT2Config, tp_axis):
    return packed_qkv_attention(x, lp, cfg.num_heads, cfg.head_dim,
                                _causal_attend, tp_axis)


def _mlp(x, lp, tp_axis):
    return packed_mlp(x, lp, lambda y: jax.nn.gelu(y, approximate=True),
                      tp_axis)


def decoder_layer(x, lp, cfg: GPT2Config, tp_axis: Optional[str] = "tp"):
    # each scope holds its LayerNorm and its residual add: the device
    # trace's ops carry these names (forward, recompute and backward)
    with scope("gpt2/attn"):
        x = x + _attention(_ln(x, lp["ln1_w"], lp["ln1_b"], cfg.ln_eps), lp,
                           cfg, tp_axis)
    with scope("gpt2/mlp"):
        x = x + _mlp(_ln(x, lp["ln2_w"], lp["ln2_b"], cfg.ln_eps), lp,
                     tp_axis)
    return x


def hidden_states(params, tokens, cfg: GPT2Config,
                  tp_axis: Optional[str] = "tp", remat: bool = True):
    """Shared trunk: embeddings + layers + final LN (pre-head)."""
    b, s = tokens.shape
    with scope("gpt2/embed"):
        x = vocab_parallel_embedding(tokens, params["embed"],
                                     axis_name=tp_axis)
        x = (x + params["pos_embed"][None, :s]).astype(cfg.dtype)

    def body(h, lp):
        return decoder_layer(h, lp, cfg, tp_axis), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    with scope("gpt2/final_ln"):
        return _ln(x, params["lnf_w"], params["lnf_b"], cfg.ln_eps)


def forward(params, tokens, cfg: GPT2Config, tp_axis: Optional[str] = "tp",
            remat: bool = True):
    """tokens [b, s] → vocab-sharded logits [b, s, v_local] (tied head)."""
    x = hidden_states(params, tokens, cfg, tp_axis, remat)
    # tied embedding head → vocab-sharded logits (embed rows are the shard)
    with scope("gpt2/head_ce"):
        return jnp.matmul(
            x, params["embed"].T.astype(x.dtype)).astype(jnp.float32)


def loss_fn(params, batch, cfg: GPT2Config, tp_axis: Optional[str] = "tp",
            remat: bool = True, vocab_chunks: Optional[int] = None):
    """Next-token CE; ``vocab_chunks`` streams the tied head + CE so the
    fp32 [b·s, vocab] logits never materialize (functional/chunked_ce.py)."""
    tokens, targets = batch
    if vocab_chunks:
        from apex_tpu.transformer.functional.chunked_ce import (
            chunked_lm_cross_entropy,
        )

        x = hidden_states(params, tokens, cfg, tp_axis, remat)
        with scope("gpt2/head_ce"):
            losses = chunked_lm_cross_entropy(
                x.reshape(-1, x.shape[-1]), params["embed"].T,
                targets.reshape(-1), vocab_chunks,
                tp_axis=tp_axis if _axis_bound(tp_axis) else None)
            return jnp.mean(losses)
    logits = forward(params, tokens, cfg, tp_axis, remat)
    with scope("gpt2/head_ce"):
        return jnp.mean(
            vocab_parallel_cross_entropy(logits, targets, axis_name=tp_axis)
        )
