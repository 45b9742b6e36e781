"""Chunked fused lm-head + cross-entropy: the ``[N, vocab]`` logits are
never materialized.

No reference-file analog (the CUDA reference predates this pattern; its
closest relative is contrib/xentropy's fused CE over *existing* logits).
TPU-first rationale: for an LLM loss the fp32 logits are often the
single largest live buffer (B·S·V·4 bytes — 1 GiB at the bench.py Llama
shapes), bigger than any activation. Streaming the vocab dimension in
``num_chunks`` slices with an online logsumexp (the flash-attention
trick applied to the classifier) caps that at ``B·S·V/num_chunks`` and
lets a larger batch fit HBM — more MXU work per step, higher MFU. The
backward recomputes each chunk's logits from the saved row statistics
instead of saving them.

All math is fp32 regardless of input dtypes (CE is range-sensitive;
same policy as contrib.xentropy).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.ops.vma import cotangent_like, to_varying, vma

__all__ = ["chunked_lm_cross_entropy"]


def _chunk_weights(weight, bias, num_chunks):
    h, v = weight.shape
    if v % num_chunks:
        raise ValueError(
            f"vocab {v} must divide into num_chunks={num_chunks}")
    vc = v // num_chunks
    w = weight.reshape(h, num_chunks, vc).transpose(1, 0, 2)  # [C, h, Vc]
    b = bias.astype(jnp.float32).reshape(num_chunks, vc)      # [C, Vc]
    los = (jnp.arange(num_chunks) * vc).astype(jnp.int32)
    return w, b, los, vc


def _rank_offset(tp_axis, v_local):
    if tp_axis is None:
        return jnp.int32(0)
    return (jax.lax.axis_index(tp_axis) * v_local).astype(jnp.int32)


def _carry_axes(tp_axis, *operands):
    """Mesh axes the scan carries become varying over: every axis any
    operand already varies over (e.g. 'cp'-sharded hidden states), plus
    the explicit vocab-parallel axis."""
    axes = set(vma(*operands))
    if tp_axis is not None:
        axes.add(tp_axis)
    return sorted(axes)


def chunked_lm_cross_entropy(hidden, weight, labels, num_chunks=8,
                             tp_axis=None, bias=None):
    """Per-token CE of ``hidden @ weight (+ bias)`` vs ``labels`` without
    the ``[N, V]`` logits: ``hidden`` [N, h], ``weight`` [h, V] (the
    lm-head kernel; pass ``embed.T`` for tied embeddings), ``labels``
    [N] int, optional ``bias`` [V] (e.g. HF BERT's decoder bias — it
    streams in the same vocab chunks). Returns per-token losses [N]
    (fp32).

    ``tp_axis``: inside ``shard_map`` with a vocab-sharded weight
    ([h, V/tp] per rank, Megatron layout; bias shards the same way),
    composes the chunked pass with the vocab-parallel reduction — local
    online logsumexp per rank, then pmax/psum across ranks (the
    vocab_parallel_cross_entropy math, streamed). The backward psums the
    partial ``d_hidden`` the way the column-parallel matmul transpose
    would."""
    if bias is None:
        bias = jnp.zeros((weight.shape[1],), jnp.float32)
    return _ce(hidden, weight, bias, labels, num_chunks, tp_axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ce(hidden, weight, bias, labels, num_chunks, tp_axis):
    return _fwd(hidden, weight, bias, labels, num_chunks, tp_axis)[0]


def _fwd(hidden, weight, bias, labels, num_chunks, tp_axis):
    w, bch, los, vc = _chunk_weights(weight, bias, num_chunks)
    x32 = hidden.astype(jnp.float32)
    n = x32.shape[0]
    lo_rank = _rank_offset(tp_axis, weight.shape[1])
    axes = _carry_axes(tp_axis, hidden, weight, bias, labels)

    def body(carry, inp):
        m, s, tgt = carry
        w_c, b_c, lo = inp
        logits = x32 @ w_c.astype(jnp.float32) + b_c      # [N, Vc]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = (s * jnp.exp(m - m_new)
             + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=-1))
        idx = labels.astype(jnp.int32) - lo_rank - lo
        in_c = (idx >= 0) & (idx < vc)
        tl = jnp.take_along_axis(
            logits, jnp.clip(idx, 0, vc - 1)[:, None], axis=1)[:, 0]
        tgt = jnp.where(in_c, tl, tgt)
        return (m_new, s, tgt), None

    init = (to_varying(jnp.full((n,), -jnp.inf, jnp.float32), axes),
            to_varying(jnp.zeros((n,), jnp.float32), axes),
            to_varying(jnp.zeros((n,), jnp.float32), axes))
    (m, s, tgt), _ = jax.lax.scan(body, init, (w, bch, los))
    if tp_axis is not None:
        # vocab-parallel merge of the per-rank streams (the stable
        # cross-rank max/sum of tensor_parallel/cross_entropy.py)
        m_g = jax.lax.pmax(m, tp_axis)
        s = jax.lax.psum(s * jnp.exp(m - m_g), tp_axis)
        tgt = jax.lax.psum(tgt, tp_axis)  # exactly one rank contributed
        m = m_g
    lse = jnp.log(s) + m
    return lse - tgt, (hidden, weight, bias, labels, lse)


def _bwd(num_chunks, tp_axis, res, g):
    hidden, weight, bias, labels, lse = res
    w, bch, los, vc = _chunk_weights(weight, bias, num_chunks)
    x32 = hidden.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    lo_rank = _rank_offset(tp_axis, weight.shape[1])
    axes = _carry_axes(tp_axis, hidden, weight, bias, labels, g)

    def body(dx, inp):
        w_c, b_c, lo = inp
        w32 = w_c.astype(jnp.float32)
        logits = x32 @ w32 + b_c                          # recompute [N, Vc]
        p = jnp.exp(logits - lse[:, None])                # softmax slice
        idx = labels.astype(jnp.int32) - lo_rank - lo
        in_c = (idx >= 0) & (idx < vc)
        onehot = (jax.nn.one_hot(jnp.clip(idx, 0, vc - 1), vc,
                                 dtype=jnp.float32)
                  * in_c[:, None].astype(jnp.float32))
        d = (p - onehot) * g32[:, None]                   # [N, Vc]
        dx = dx + d @ w32.T
        dw_c = x32.T @ d                                  # [h, Vc]
        db_c = jnp.sum(d, axis=0)                         # [Vc]
        return dx, (dw_c, db_c)

    dx, (dws, dbs) = jax.lax.scan(
        body, to_varying(jnp.zeros_like(x32), axes), (w, bch, los))
    if tp_axis is not None:
        # each rank's dx covers only its vocab shard's columns — the
        # column-parallel transpose is an allreduce
        dx = jax.lax.psum(dx, tp_axis)
    dweight = dws.transpose(1, 0, 2).reshape(weight.shape)
    dbias = dbs.reshape(bias.shape).astype(bias.dtype)
    return (cotangent_like(dx.astype(hidden.dtype), hidden),
            cotangent_like(dweight.astype(weight.dtype), weight),
            cotangent_like(dbias, bias), None)


_ce.defvjp(_fwd, _bwd)
