"""Pallas TPU flash attention (the kernel behind ``apex_tpu.contrib.fmha``;
ref apex/contrib/fmha/fmha.py + csrc/fmha cutlass kernels).

Design (TPU-first, not a CUDA port):
- grid = (batch*heads, q_blocks, k_blocks), k innermost so the online
  softmax state (m, l, acc) lives in VMEM scratch across the k sweep.
- one q tile is [BLOCK_Q, d] in VMEM; each step streams one [BLOCK_K, d]
  k/v tile through the MXU (q @ k^T then p @ v). Every product takes its
  operands in the inputs' dtype and accumulates in fp32 (:func:`_mxu`):
  bf16 inputs feed the MXU bf16, as XLA's own bf16 dots do, with p and ds
  cast to the dtype of the operand they meet; the scale, the running max,
  the sum, lse, delta and the accumulators stay fp32.
- causal masking is positional (iota compare) — no mask tensor ever
  materializes in HBM (the reference's kernels read a cu_seqlens array;
  fixed-shape batched input is the TPU-friendly layout).
- a sliding ``window`` (forward only) is a second positional bound: query
  ``i`` reads keys ``i - window < j <= i``. Key blocks wholly below a query
  block's window are skipped as those above the diagonal are, and the edge
  block is masked. The backward kernels do not know a window and raise.

Backward (FlashAttention-2 style, TPU-blocked): the forward additionally
writes the per-row logsumexp; the backward recomputes p-blocks from (q, k,
lse) in VMEM — dq accumulates over a k sweep, dk/dv accumulate over a q
sweep (and, for GQA, over the query heads sharing each kv head) — so
training, like inference, never materializes an [sq, sk] matrix in HBM
(ref apex/contrib/fmha csrc dgrad kernels). Non-TPU backends fall back to
the jnp reference VJP.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import pallas_config

_NEG_INF = -1e30


def _keep_mask(seed, bh, q_pos, k_pos, p_drop):
    """Counter-based Bernoulli keep mask for attention dropout.

    Deterministic in the ABSOLUTE (head, query, key) coordinates — the
    forward and backward kernels run different block grids, so a stateful
    per-block PRNG could not reproduce the same mask; a murmur3-finalized
    hash of the position counter can, from any tiling (ref
    apex/contrib/fmha/fmha.py:35 threads p_dropout through the fused
    kernel; philox counters play this role in the CUDA kernels).
    Pure elementwise uint32 math: runs identically inside a Pallas kernel
    and in the jnp fallback path.
    """
    x = (k_pos.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         + q_pos.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
         + bh.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
         + seed.astype(jnp.uint32))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    # compare in the positive-int31 domain: a logical >>1 makes the value
    # fit signed int32, so the threshold test never depends on how the
    # backend treats unsigned comparisons (Mosaic-safe)
    x31 = (x >> jnp.uint32(1)).astype(jnp.int32)
    return x31 > jnp.int32(min(int(p_drop * 2147483648.0), 2147483647))


def _mxu(a, b, dims):
    """``dot_general(a, b, dims)`` with both operands in their common
    dtype and fp32 accumulation: bf16 operands go to the MXU as they are,
    fp32 ones stay fp32."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    return jax.lax.dot_general(a.astype(dt), b.astype(dt), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a @ b^T
_NN = ((1,), (0,))   # a @ b
_TN = ((0,), (0,))   # a^T @ b


def _fwd_kernel(causal, scale, block_q, block_k, sq, sk, varlen, p_drop,
                window, q_ref, k_ref, v_ref, *refs):
    refs = list(refs)
    kvlen_ref = refs.pop(0) if varlen else None
    seed_ref = refs.pop(0) if p_drop else None
    o_ref, lse_ref, m_sc, l_sc, acc_sc = refs
    bh_idx = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    run = True
    if causal:
        # whole block above the diagonal ⇒ nothing to do
        run = (ki * block_k) <= (qi * block_q + block_q - 1)
    if window is not None:
        # whole block below the first query's window ⇒ nothing to do
        run = run & ((ki * block_k + block_k - 1) > (qi * block_q - window))
    if varlen:
        # whole block past this sequence's keys ⇒ nothing to do
        run = run & ((ki * block_k) < kvlen_ref[0, 0, 0])

    @pl.when(run)
    def _step():
        s = scale * _mxu(q_ref[0], k_ref[0], _NT)         # [bq, bk]
        if causal:
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        if window is not None:
            s = jnp.where(k_pos > q_pos - window, s, _NEG_INF)
        # mask key padding (sk not multiple of block_k)
        if sk % block_k:
            s = jnp.where(k_pos < sk, s, _NEG_INF)
        if varlen:
            s = jnp.where(k_pos < kvlen_ref[0, 0, 0], s, _NEG_INF)

        m_prev = m_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        # rows with nothing allowed yet: keep p exact zero
        p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, 0] = l_sc[:, 0] * alpha + jnp.sum(p, axis=-1)
        # dropout applies to the NORMALIZED probs (torch semantics:
        # dropout(softmax) @ v), so the numerator is masked while the
        # normalizer l accumulates the raw probs; the keep rescale
        # 1/(1-p_drop) is a constant, applied in fp32 with l at the end
        # (folded into a bf16 operand it would round every kept prob)
        pv = p
        if p_drop:
            keep = _keep_mask(seed_ref[0, 0], bh_idx.astype(jnp.uint32),
                              q_pos, k_pos, p_drop)
            pv = jnp.where(keep, p, 0.0)
        acc_sc[:] = acc_sc[:] * alpha[:, None] + _mxu(
            pv.astype(v_ref.dtype), v_ref[0], _NN)
        m_sc[:, 0] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[:, 0], 1e-30)
        o_ref[0] = (acc_sc[:] / (l[:, None] * (1.0 - p_drop))
                    ).astype(o_ref.dtype)
        # exact per-row logsumexp — the backward's p-block recompute key.
        # lse rides as [bh, sq, 1]: a (1, bq) block over [bh, sq] violates
        # Mosaic's last-two-dims rule (second-to-last must divide 8 or
        # equal the array dim); the trailing singleton makes the block
        # (1, bq, 1) legal (bq % 8 == 0, 1 == full dim)
        lse_ref[0, :, 0] = (m_sc[:, 0] + jnp.log(l)).astype(jnp.float32)


def _tile(s, target):
    """``(block, padded_len)`` for a sequence of ``s`` rows and a wanted
    block of ``target``: halve the block while it does not divide ``s``,
    but never below ``pallas_config.FLASH_MIN_BLOCK`` rows — from there
    the SEQUENCE is padded up to a block multiple instead. (Halving all
    the way down gave a 520-token prefill 8-row blocks and a prime length
    1-row blocks: under the bf16 (16, 128) tile, and a grid step per
    row.) A target at or below the floor is taken as is, so a short
    sequence stays one full-extent block."""
    b = min(target, s)
    while b > pallas_config.FLASH_MIN_BLOCK and s % b:
        b //= 2
    return b, -(-s // b) * b


def _pad_rows(x, rows):
    """Zero-pad dim 1 of ``x`` up to ``rows``."""
    if x.shape[1] == rows:
        return x
    return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]))
                   + ((0, 0),) * (x.ndim - 2))


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "p_drop", "window"))
def _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                      interpret=False, kv_lens=None, p_drop=0.0, seed=None,
                      window=None):
    """q [bh, sq, d], k/v [bh_kv, sk, d] → o [bh, sq, d].

    GQA: when bh_kv < bh, ``rep = bh // bh_kv`` query heads read the SAME
    k/v block via the BlockSpec index map — no repeated copy in HBM.
    Layout requirement: q heads grouped kv-major (head g*rep+r shares kv
    head g), which :func:`flash_attention` arranges.

    ``kv_lens`` [bh] int32 (varlen): row b attends only to its first
    kv_lens[b] keys; blocks entirely past the bound are skipped. The
    length rides as a [bh, 1, 1] array with a (1, 1, 1) VMEM block per
    row (the last two block dims must equal the array dims or divide the
    (8, 128) tile — CI pins this via tests/run_pallas/test_tpu_lowering);
    scalar prefetch (SMEM via PrefetchScalarGridSpec) would let Mosaic
    skip the block FETCH too, but needs per-shape grid plumbing —
    revisit if varlen profiles hot.
    """
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    rep = bh // bh_kv
    bq, sq_p = _tile(sq, block_q)
    bk, sk_p = _tile(sk, block_k)
    # padded keys are masked in the kernel (k_pos < sk); padded query
    # rows compute a harmless uniform softmax and are sliced off below
    q = _pad_rows(q, sq_p)
    k, v = _pad_rows(k, sk_p), _pad_rows(v, sk_p)
    grid = (bh, sq_p // bq, sk_p // bk)
    varlen = kv_lens is not None

    kernel = functools.partial(_fwd_kernel, causal, scale, bq, bk, sq, sk,
                               varlen, p_drop, window)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b // rep, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b // rep, j, 0)),
    ]
    args = (q, k, v)
    if varlen:
        # [bh, 1, 1] with a (1, 1, 1) block: last two dims equal the
        # array's, which Mosaic accepts ((1, 1) over [bh, 1] does not)
        in_specs.append(
            pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0)))
        args = args + (kv_lens.astype(jnp.int32).reshape(bh, 1, 1),)
    if p_drop:
        in_specs.append(pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)))
        args = args + (seed.astype(jnp.uint32).reshape(1, 1),)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            pallas_config.out_struct((bh, sq_p, d), q.dtype, q, k, v),
            pallas_config.out_struct((bh, sq_p, 1), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        name="apex_flash_fwd",
        interpret=interpret,
    )(*args)
    # public lse stays [bh, sq]; the singleton is a kernel-layout detail
    return o[:, :sq], lse[:, :sq, 0]


def _reference_attention(q, k, v, causal, scale, kv_lens=None, p_drop=0.0,
                         seed=None, window=None):
    """jnp reference — also the VJP path (rematerialized). GQA-aware:
    q [bh, sq, d] with k/v [bh_kv, sk, d]; grouped einsum, no kv copy.
    ``kv_lens`` [bh]: varlen key bound per row (finite fill — empty
    sequences stay NaN-free through autodiff). Dropout uses the SAME
    counter-based mask as the Pallas kernels, so both backends produce
    bit-identical masks for a given seed. Both products round where the
    kernels' do (:func:`_mxu`): operands in the inputs' dtype, p cast to
    v's, fp32 accumulation; float32 inputs stay float32 throughout."""
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    rep = bh // bh_kv
    qg = q.reshape(bh_kv, rep, sq, d)
    s = jnp.einsum("grqd,gkd->grqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        s = jnp.where(kpos <= qpos, s, _NEG_INF)
    if window is not None:
        s = jnp.where(jnp.arange(sk)[None, :]
                      > jnp.arange(sq)[:, None] - window, s, _NEG_INF)
    if kv_lens is not None:
        ok = (jnp.arange(sk)[None, None, None, :]
              < kv_lens.reshape(bh_kv, rep)[:, :, None, None])  # [g,r,1,sk]
        s = jnp.where(ok, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if p_drop:
        bh_idx = (jnp.arange(bh_kv, dtype=jnp.uint32)[:, None]
                  * jnp.uint32(rep)
                  + jnp.arange(rep, dtype=jnp.uint32)[None, :])
        keep = _keep_mask(
            seed, bh_idx[:, :, None, None],
            jnp.arange(sq, dtype=jnp.uint32)[None, None, :, None],
            jnp.arange(sk, dtype=jnp.uint32)[None, None, None, :], p_drop)
        p = jnp.where(keep, p / (1.0 - p_drop), 0.0)
    o = jnp.einsum("grqk,gkd->grqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(bh, sq, d).astype(q.dtype)


# ------------------------------------------------------------ backward
# FlashAttention-2-style blocked backward: p-blocks are recomputed in VMEM
# from (q, k, lse); dq accumulates over the k sweep, dk/dv over the q sweep
# (innermost, so scratch accumulation per kv block is contiguous) and, for
# GQA, over the `rep` query heads sharing each kv head. No [sq, sk] array
# ever exists in HBM (ref csrc/fmha dgrad kernels).


def _bwd_dq_kernel(causal, scale, bq, bk, sk, varlen, p_drop,
                   q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                   *refs):
    refs = list(refs)
    kvlen_ref = refs.pop(0) if varlen else None
    seed_ref = refs.pop(0) if p_drop else None
    dq_ref, acc_sc = refs
    bh_idx = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    run = True
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1)
    if varlen:
        run = run & ((ki * bk) < kvlen_ref[0, 0, 0])

    @pl.when(run)
    def _step():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = scale * _mxu(q, k, _NT)                        # [bq, bk]
        p = jnp.exp(s - lse_ref[0])
        if causal or varlen or p_drop or sk % bk:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
        if causal:
            p = jnp.where(k_pos <= q_pos, p, 0.0)
        if sk % bk:  # key padding (see _tile)
            p = jnp.where(k_pos < sk, p, 0.0)
        if varlen:
            p = jnp.where(k_pos < kvlen_ref[0, 0, 0], p, 0.0)
        dp = _mxu(do, v, _NT)                              # [bq, bk]
        if p_drop:
            # o = (p∘m)@v with m = keep/(1-pd): dL/dp = m∘(do@vᵀ), and the
            # softmax-backward row term stays D = rowsum(do∘o) because
            # Σ_k p_k m_k (do·v_k) = do·o — only dp gets masked
            keep = _keep_mask(seed_ref[0, 0], bh_idx.astype(jnp.uint32),
                              q_pos, k_pos, p_drop)
            dp = jnp.where(keep, dp / (1.0 - p_drop), 0.0)
        ds = p * (dp - dl_ref[0]) * scale
        acc_sc[:] += _mxu(ds.astype(k.dtype), k, _NN)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(causal, scale, bq, bk, sk, rep, nq, varlen, p_drop,
                    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                    *refs):
    refs = list(refs)
    kvlen_ref = refs.pop(0) if varlen else None
    seed_ref = refs.pop(0) if p_drop else None
    dk_ref, dv_ref, dk_sc, dv_sc = refs
    g_idx = pl.program_id(0)
    ki = pl.program_id(1)
    r = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when((r == 0) & (qi == 0))
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    run = True
    if causal:
        run = (qi * bq + bq - 1) >= (ki * bk)
    if varlen:
        run = run & ((ki * bk) < kvlen_ref[0, 0, 0])

    @pl.when(run)
    def _step():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = scale * _mxu(q, k, _NT)                        # [bq, bk]
        p = jnp.exp(s - lse_ref[0])
        if causal or varlen or p_drop or sk % bk:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
        if causal:
            p = jnp.where(k_pos <= q_pos, p, 0.0)
        if sk % bk:  # key padding (see _tile)
            p = jnp.where(k_pos < sk, p, 0.0)
        if varlen:
            p = jnp.where(k_pos < kvlen_ref[0, 0, 0], p, 0.0)
        if p_drop:
            # same counter-based mask as the forward: bh = g*rep + r here
            bh_idx = (g_idx * rep + r).astype(jnp.uint32)
            keep = _keep_mask(seed_ref[0, 0], bh_idx, q_pos, k_pos, p_drop)
            pm = jnp.where(keep, p, 0.0)   # 1/(1-p_drop) at the end
        else:
            pm = p
        dv_sc[:] += _mxu(pm.astype(do.dtype), do, _TN)    # [bk, d]
        dp = _mxu(do, v, _NT)
        if p_drop:
            dp = jnp.where(keep, dp / (1.0 - p_drop), 0.0)
        ds = p * (dp - dl_ref[0]) * scale
        dk_sc[:] += _mxu(ds.astype(q.dtype), q, _TN)      # [bk, d]

    @pl.when((r == rep - 1) & (qi == nq - 1))
    def _finish():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = (dv_sc[:] / (1.0 - p_drop)).astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "p_drop"))
def _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                      interpret=False, kv_lens=None, p_drop=0.0, seed=None):
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    rep = bh // bh_kv
    bq, sq_p = _tile(sq, block_q)
    bk, sk_p = _tile(sk, block_k)
    nq, nk = sq_p // bq, sk_p // bk
    varlen = kv_lens is not None

    # D_i = rowsum(dO * O): elementwise, O(s·d) — fine as fused XLA.
    # lse/delta ride as [bh, sq, 1] (same Mosaic block-shape rule as the
    # forward's lse output)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None]
    lse3 = lse.reshape(bh, sq, 1)
    # padded query rows carry do = delta = 0, so they add nothing to
    # dk/dv; padded keys are masked in the kernels (k_pos < sk)
    q, do, lse3, delta = (_pad_rows(x, sq_p) for x in (q, do, lse3, delta))
    k, v = _pad_rows(k, sk_p), _pad_rows(v, sk_p)

    dq_in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b // rep, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b // rep, j, 0)),
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
    ]
    dq_args = (q, k, v, do, lse3, delta)
    dkv_in_specs = [
        pl.BlockSpec((1, bq, d), lambda g, j, r, i: (g * rep + r, i, 0)),
        pl.BlockSpec((1, bk, d), lambda g, j, r, i: (g, j, 0)),
        pl.BlockSpec((1, bk, d), lambda g, j, r, i: (g, j, 0)),
        pl.BlockSpec((1, bq, d), lambda g, j, r, i: (g * rep + r, i, 0)),
        pl.BlockSpec((1, bq, 1), lambda g, j, r, i: (g * rep + r, i, 0)),
        pl.BlockSpec((1, bq, 1), lambda g, j, r, i: (g * rep + r, i, 0)),
    ]
    dkv_args = (q, k, v, do, lse3, delta)
    if varlen:
        kvl = kv_lens.astype(jnp.int32).reshape(bh, 1, 1)
        dq_in_specs.append(
            pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0)))
        dq_args = dq_args + (kvl,)
        dkv_in_specs.append(
            pl.BlockSpec((1, 1, 1), lambda g, j, r, i: (g * rep + r, 0, 0)))
        dkv_args = dkv_args + (kvl,)
    if p_drop:
        sd = seed.astype(jnp.uint32).reshape(1, 1)
        dq_in_specs.append(pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)))
        dq_args = dq_args + (sd,)
        dkv_in_specs.append(
            pl.BlockSpec((1, 1), lambda g, j, r, i: (0, 0)))
        dkv_args = dkv_args + (sd,)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal, scale, bq, bk, sk, varlen,
                          p_drop),
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=pallas_config.out_struct((bh, sq_p, d), q.dtype, q, k, v,
                                           do),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="apex_flash_bwd_dq",
        interpret=interpret,
    )(*dq_args)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal, scale, bq, bk, sk, rep,
                          nq, varlen, p_drop),
        grid=(bh_kv, nk, rep, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda g, j, r, i: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, j, r, i: (g, j, 0)),
        ],
        out_shape=[
            pallas_config.out_struct((bh_kv, sk_p, d), k.dtype, q, k, v,
                                     do),
            pallas_config.out_struct((bh_kv, sk_p, d), v.dtype, q, k, v,
                                     do),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        name="apex_flash_bwd_dkv",
        interpret=interpret,
    )(*dkv_args)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


def _use_pallas() -> bool:
    return pallas_config.use_pallas("flash_attention")


def _blocks(kind, q, k):
    return pallas_config.flash_blocks(kind, q.shape[1], k.shape[1],
                                      q.shape[2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, window=None):
    if _use_pallas():
        bq, bk = _blocks("fwd", q, k)
        return _flash_fwd_pallas(q, k, v, causal, scale, bq, bk,
                                 pallas_config.interpret(), window=window)[0]
    return _reference_attention(q, k, v, causal, scale, window=window)


def _flash_fwd(q, k, v, causal, scale, window):
    if _use_pallas():
        bq, bk = _blocks("fwd", q, k)
        o, lse = _flash_fwd_pallas(q, k, v, causal, scale, bq, bk,
                                   pallas_config.interpret(), window=window)
        return o, (q, k, v, o, lse)
    return (_reference_attention(q, k, v, causal, scale, window=window),
            (q, k, v, None, None))


def _flash_bwd(causal, scale, window, res, g):
    q, k, v, o, lse = res
    if lse is not None:
        if window is not None:
            raise NotImplementedError(
                "the flash attention backward kernels do not know a "
                "sliding window: the windowed call is forward only")
        bq, bk = _blocks("bwd", q, k)
        return _flash_bwd_pallas(q, k, v, o, lse, g, causal, scale, bq, bk,
                                 pallas_config.interpret())
    _, vjp = jax.vjp(
        lambda q, k, v: _reference_attention(q, k, v, causal, scale,
                                             window=window), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


# dropout flavor (ref apex/contrib/fmha/fmha.py:35 p_dropout): the seed
# rides as a traced uint32 so changing it does NOT retrace; the mask is
# recomputed in the backward kernels from the same counter hash.


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_dropout(q, k, v, seed, causal, scale, p_drop):
    return _flash_dropout_fwd(q, k, v, seed, causal, scale, p_drop)[0]


def _flash_dropout_fwd(q, k, v, seed, causal, scale, p_drop):
    if _use_pallas():
        bq, bk = _blocks("fwd", q, k)
        o, lse = _flash_fwd_pallas(q, k, v, causal, scale, bq, bk,
                                   pallas_config.interpret(),
                                   p_drop=p_drop, seed=seed)
        return o, (q, k, v, seed, o, lse)
    o = _reference_attention(q, k, v, causal, scale, p_drop=p_drop,
                             seed=seed)
    return o, (q, k, v, seed, None, None)


def _flash_dropout_bwd(causal, scale, p_drop, res, g):
    import numpy as _np

    q, k, v, seed, o, lse = res
    if lse is not None:
        bq, bk = _blocks("bwd", q, k)
        dq, dk, dv = _flash_bwd_pallas(q, k, v, o, lse, g, causal, scale,
                                       bq, bk, pallas_config.interpret(),
                                       p_drop=p_drop, seed=seed)
    else:
        _, vjp = jax.vjp(
            lambda q, k, v: _reference_attention(
                q, k, v, causal, scale, p_drop=p_drop, seed=seed), q, k, v)
        dq, dk, dv = vjp(g)
    dseed = _np.zeros(seed.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dseed


_flash_dropout.defvjp(_flash_dropout_fwd, _flash_dropout_bwd)


# varlen (kv_lens-bounded) flavor: same kernels, masked to each row's key
# count — the reference's cu_seqlens semantics with flash memory behavior.


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _flash_varlen(causal, scale, p_drop, q, k, v, kv_lens, seed):
    return _flash_varlen_fwd(causal, scale, p_drop, q, k, v, kv_lens,
                             seed)[0]


def _flash_varlen_fwd(causal, scale, p_drop, q, k, v, kv_lens, seed):
    if _use_pallas():
        bq, bk = _blocks("fwd", q, k)
        o, lse = _flash_fwd_pallas(q, k, v, causal, scale, bq, bk,
                                   pallas_config.interpret(),
                                   kv_lens=kv_lens, p_drop=p_drop,
                                   seed=seed)
        return o, (q, k, v, kv_lens, seed, o, lse)
    o = _reference_attention(q, k, v, causal, scale, kv_lens=kv_lens,
                             p_drop=p_drop, seed=seed)
    return o, (q, k, v, kv_lens, seed, None, None)


def _flash_varlen_bwd(causal, scale, p_drop, res, g):
    import numpy as _np

    q, k, v, kv_lens, seed, o, lse = res
    if lse is not None:
        bq, bk = _blocks("bwd", q, k)
        dq, dk, dv = _flash_bwd_pallas(q, k, v, o, lse, g, causal, scale,
                                       bq, bk, pallas_config.interpret(),
                                       kv_lens=kv_lens, p_drop=p_drop,
                                       seed=seed)
    else:
        _, vjp = jax.vjp(
            lambda q, k, v: _reference_attention(q, k, v, causal, scale,
                                                 kv_lens=kv_lens,
                                                 p_drop=p_drop, seed=seed),
            q, k, v)
        dq, dk, dv = vjp(g)
    dlens = _np.zeros(kv_lens.shape, dtype=jax.dtypes.float0)
    dseed = _np.zeros(seed.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dlens, dseed


_flash_varlen.defvjp(_flash_varlen_fwd, _flash_varlen_bwd)


def _dropout_seed(dropout_key):
    """uint32 kernel seed from a jax PRNG key (traced, so a fresh key per
    step does not retrace)."""
    try:
        return jax.random.bits(dropout_key, (), jnp.uint32)
    except (AttributeError, TypeError):  # older jax without random.bits
        return jax.random.randint(
            dropout_key, (), 0, jnp.iinfo(jnp.int32).max).astype(jnp.uint32)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, kv_lens=None,
                    dropout_p: float = 0.0, dropout_key=None,
                    deterministic: bool = False,
                    window: Optional[int] = None):
    """Fused attention on [b, s, h, d] (heads may differ for k/v — GQA).

    Returns [b, sq, h, d]; fp32 softmax internally, output in q's dtype.
    ``kv_lens`` [b] int32 bounds each sequence's keys (varlen batching —
    ref fmha cu_seqlens); padded QUERY rows of the output are zeroed.
    The varlen path is SELF-attention only (one shared length per row
    bounds both queries and keys, so it requires sq == sk); cross-attention
    with separate q/kv lengths is not expressible with a single kv_lens.

    ``dropout_p`` drops SOFTMAX PROBABILITIES inside the kernel (inverted
    dropout, ref apex/contrib/fmha/fmha.py:35 p_dropout) — requires
    ``dropout_key`` (jax PRNG key) unless ``deterministic`` is set, in
    which case dropout is a no-op (eval mode).

    ``window`` (causal self-attention only, no ``kv_lens``, no dropout):
    query ``i`` reads keys ``i - window < j <= i``, itself among them.
    Forward only under the Pallas kernels.
    """
    b, sq, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    sk = k.shape[1]
    if kv_lens is not None and sq != sk:
        raise ValueError(
            f"kv_lens implies self-attention (shared per-row length) but "
            f"sq={sq} != sk={sk}; cross-attention varlen needs separate "
            f"q_lens/kv_lens, which this kernel does not support")
    scale = scale if scale is not None else 1.0 / d ** 0.5
    p_drop = 0.0 if deterministic else float(dropout_p)
    if window is not None:
        if not causal or kv_lens is not None or p_drop or sq != sk:
            raise ValueError("a sliding window goes with causal "
                             "self-attention alone: no kv_lens, no dropout")
        if window < 1:
            raise ValueError(f"window must be at least 1, got {window}")
        window = int(window)
    if p_drop and dropout_key is None:
        raise ValueError(
            "dropout_p > 0 in training needs dropout_key (jax PRNG key); "
            "pass deterministic=True for eval")

    # heads-major flatten; q head g*rep+r shares kv head g (standard GQA
    # head order), matching the kernel's b//rep kv indexing
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h_kv, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h_kv, sk, d)
    if kv_lens is None:
        if p_drop:
            o = _flash_dropout(qt, kt, vt, _dropout_seed(dropout_key),
                               causal, float(scale), p_drop)
        else:
            o = _flash(qt, kt, vt, causal, float(scale), window)
        return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    seed = (_dropout_seed(dropout_key) if p_drop
            else jnp.zeros((), jnp.uint32))
    o = _flash_varlen(causal, float(scale), p_drop, qt, kt, vt,
                      jnp.repeat(kv_lens, h), seed)
    o = o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    # zero meaningless padded-query rows (and their gradients)
    q_ok = jnp.arange(sq)[None, :] < kv_lens[:, None]
    return jnp.where(q_ok[:, :, None, None], o, 0.0)
