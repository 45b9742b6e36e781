"""Shared model-zoo scaffolding: init helpers and the BatchNorm switch."""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.parallel.sync_batchnorm import SyncBatchNorm


def fan_in_normal(key, *shape, fan_in=None, dtype=jnp.float32):
    """N(0, 1/fan_in) init (fan_in defaults to the second-to-last dim)."""
    scale = (fan_in if fan_in is not None else shape[-2]) ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class BatchNorm(nn.Module):
    """Plain flax BatchNorm or cross-replica :class:`SyncBatchNorm`.

    ``momentum`` uses the flax convention (fraction of the running stat
    KEPT each step); SyncBatchNorm follows the torch convention (fraction
    REPLACED, ref apex/parallel/sync_batchnorm.py), so it gets ``1 - m`` —
    the same inversion ``convert_syncbn_model`` applies.
    """

    sync: bool = False
    axis_name: Optional[str] = "data"
    momentum: float = 0.9
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, train: bool):
        if self.sync:
            return SyncBatchNorm(momentum=1.0 - self.momentum, eps=self.eps,
                                 axis_name=self.axis_name)(
                x, use_running_average=not train)
        return nn.BatchNorm(use_running_average=not train,
                            momentum=self.momentum, epsilon=self.eps,
                            dtype=x.dtype)(x)


# --------------------------------------------------- shared transformer bits

from apex_tpu.normalization.fused_layer_norm import fused_layer_norm_affine
from apex_tpu.transformer.tensor_parallel.layers import (
    column_parallel_linear,
    row_parallel_linear,
)
from apex_tpu.transformer.tensor_parallel.mappings import _axis_bound


def layer_norm(x, w, b, eps):
    return fused_layer_norm_affine(x, w, b, (x.shape[-1],), eps=eps)


def tp_size(tp_axis) -> int:
    import jax.lax

    return jax.lax.axis_size(tp_axis) if _axis_bound(tp_axis) else 1


def packed_qkv_attention(x, lp, num_heads, head_dim, attend, tp_axis):
    """Megatron packed-qkv attention shared by the gpt2/bert families.

    ``lp`` carries wqkv [h, 3, h] / bqkv [3, h] / wo / bo; sharding the LAST
    dim of wqkv with P(..., 'tp') gives each rank its heads of all of q, k
    and v, so the flattened local kernel is q|k|v blocks and a thirds-split
    of the local gemm output is exact. ``attend(q, k, v) -> o``, all
    ``[b, s, n, d]``, is the attention core (causal flash attention for
    gpt2, a padding-masked softmax for bert), as ``llama.block`` takes it.
    """
    b, s, h = x.shape
    n = num_heads // tp_size(tp_axis)
    d = head_dim

    w = lp["wqkv"].reshape(h, -1)   # local [h, 3·h/tp]: q|k|v blocks
    qkv = column_parallel_linear(x, w, lp["bqkv"].reshape(-1),
                                 gather_output=False, axis_name=tp_axis)
    # heads-major [3, b, n, s, d] straight from the product, handed on as
    # [b, s, n, d] views: XLA then writes the product in the layout a
    # heads-major core reads (the flash kernels' [b·n, s, d]); split as
    # [b, s, n, d] first, each of q, k, v and their gradients took a
    # transposing copy in every pass
    q, k, v = (t.swapaxes(1, 2) for t in
               qkv.reshape(b, s, 3, n, d).transpose(2, 0, 3, 1, 4))

    o = attend(q, k, v).reshape(b, s, n * d)
    return row_parallel_linear(o, lp["wo"], lp["bo"], input_is_parallel=True,
                               axis_name=tp_axis)


def packed_mlp(x, lp, act_fn, tp_axis):
    """fc -> act -> proj with column/row tensor parallelism."""
    y = column_parallel_linear(x, lp["wfc"], lp["bfc"], gather_output=False,
                               axis_name=tp_axis)
    return row_parallel_linear(act_fn(y), lp["wproj"], lp["bproj"],
                               input_is_parallel=True, axis_name=tp_axis)
