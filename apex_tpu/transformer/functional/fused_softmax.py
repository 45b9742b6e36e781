"""Fused scale+mask+softmax (ref apex/transformer/functional/fused_softmax.py
+ csrc/megatron/scaled_{masked,upper_triang_masked}_softmax*.cu).

The CUDA kernels fuse scale→mask→softmax to avoid three HBM round-trips. On
TPU, XLA already fuses the elementwise chain into the surrounding ops, so the
pure-jnp path is close to optimal; the Pallas kernels here add the two wins
XLA can't express:

- the **causal** variant never materializes the [sq, sk] mask in HBM — it is
  generated from ``iota`` inside the kernel (the reference's
  upper-triang kernel hardcodes the triangle the same way);
- softmax statistics are computed in fp32 in VMEM regardless of the bf16
  storage dtype (same accumulator policy as the CUDA kernels).

The backward has a kernel of its own, ``apex_softmax_bwd``, behind both
``custom_vjp`` rules: dx = scale * y * (g - sum(g * y)) over blocks of whole
rows, reading the saved probabilities and the cotangent in the dtype autodiff
hands over (bf16 under amp O2) and writing dx once, float32 arithmetic in
VMEM. It needs no mask (y is 0 wherever the forward masked). Left to XLA, as
it was until PR 27, the row sum was lowered on the v5e to a ``reduce-window``
2,047 wide behind a layout copy of a float32 cotangent: ``fusion.323`` 0.867 s
and ``copy.170`` 0.550 s of the 5.44 s busy in GPT-2 345M's traced steps, 26%
of the step (PERF_LEDGER.jsonl, PR 26 ``breakdown``; PERF.md section 6). Rows
longer than ``_WHOLE_ROW_MAX_SK`` keys (no benchmark cell runs them) and
non-TPU backends (the CPU test mesh) keep the identical-math jnp backward, as
non-TPU backends keep the jnp forward.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import pallas_config
from apex_tpu.transformer.enums import AttnMaskType

_MASK_FILL = -10000.0


def _use_pallas() -> bool:
    return pallas_config.use_pallas("fused_softmax")


# ------------------------------------------------------------- jnp reference


def _softmax_fp32(x, dtype):
    return jax.nn.softmax(x.astype(jnp.float32), axis=-1).astype(dtype)


def _causal_mask(sq: int, sk: int, dtype):
    # True above the diagonal = masked (matches the reference's triangle).
    q = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    k = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return k > q + (sk - sq)


# ---------------------------------------------------------------- Pallas fwd

# Keep one fp32 row-block comfortably inside VMEM (~16 MiB/core): budget
# ~2 MiB for x plus the same for y.
_VMEM_ROW_BUDGET = 2 * 1024 * 1024
# Rows up to this many keys use the single-pass whole-row kernel; longer
# rows switch to the two-pass k-blocked kernels (no upper limit).
_WHOLE_ROW_MAX_SK = 16384
# Test/debug override for the blocked kernels' k-block; None defers to
# the tuner (apex_tpu.tuning.softmax_block_k: tuned cache entry for the
# device, else the search-space default — the 2048 that used to live
# here as a hardcoded tile).
_BLOCKED_BK = None


def _blocked_bk(sk: int) -> int:
    if _BLOCKED_BK is not None:
        return _BLOCKED_BK
    from apex_tpu.tuning import softmax_block_k

    return softmax_block_k(sk)


def _largest_divisor(s: int, target: int) -> int:
    b = min(s, target)
    while s % b:
        b -= 1
    return b


def _pick_block_rows(sq: int, sk: int, budget: Optional[int] = None) -> int:
    # largest divisor of sq whose fp32 row block fits the VMEM budget
    # (the forward's unless the caller has one of its own)
    budget = _VMEM_ROW_BUDGET if budget is None else budget
    return _largest_divisor(sq, max(8, budget // (4 * sk)))


def _pallas_ok(sq: int, sk: int) -> bool:
    del sq  # k-blocking removed the sk cap (VERDICT weak #9)
    if sk > _WHOLE_ROW_MAX_SK:
        # only long rows consult the tuner for their k-block: the
        # whole-row path never uses it, and must not pay a cache lookup
        # (or inherit a cache error) per dispatch
        bk = _blocked_bk(sk)
        if _largest_divisor(sk, bk) < min(128, bk):
            # awkward sk (e.g. prime): the blocked kernel would
            # degenerate to lane-dim blocks far below a TPU tile —
            # jnp/XLA is faster there (min() keeps tests that shrink
            # _BLOCKED_BK on the blocked path)
            return False
    return _use_pallas()


def _causal_kernel(scale, block_rows, sq, sk, x_ref, y_ref):
    j = pl.program_id(1)
    x = x_ref[:].astype(jnp.float32) * scale  # [1, block_rows, sk]
    row = (
        jax.lax.broadcasted_iota(jnp.int32, (block_rows, sk), 0)
        + j * block_rows
    )
    col = jax.lax.broadcasted_iota(jnp.int32, (block_rows, sk), 1)
    masked = jnp.where((col > row + (sk - sq))[None], _MASK_FILL, x)
    m = jnp.max(masked, axis=-1, keepdims=True)
    e = jnp.exp(masked - m)
    y = e / jnp.sum(e, axis=-1, keepdims=True)
    y_ref[:] = y.astype(y_ref.dtype)


def _masked_kernel(scale, x_ref, mask_ref, y_ref):
    x = x_ref[:].astype(jnp.float32) * scale
    masked = jnp.where(mask_ref[:], _MASK_FILL, x)
    m = jnp.max(masked, axis=-1, keepdims=True)
    e = jnp.exp(masked - m)
    y = e / jnp.sum(e, axis=-1, keepdims=True)
    y_ref[:] = y.astype(y_ref.dtype)


def _pallas_causal(x, scale):
    b, sq, sk = x.shape
    if sk > _WHOLE_ROW_MAX_SK:
        return _pallas_causal_blocked(x, scale)
    rows = _pick_block_rows(sq, sk)
    blk = (1, rows, sk)
    idx = lambda i, j: (i, j, 0)
    return pl.pallas_call(
        functools.partial(_causal_kernel, scale, rows, sq, sk),
        out_shape=pallas_config.out_struct(x.shape, x.dtype, x),
        grid=(b, sq // rows),
        in_specs=[pl.BlockSpec(blk, idx)],
        out_specs=pl.BlockSpec(blk, idx),
        name="apex_causal_softmax",
        interpret=pallas_config.interpret(),
    )(x)


# --------------------------------------------- k-blocked two-pass kernels
# Long-context rows (sk > _WHOLE_ROW_MAX_SK) never fit a whole fp32 row in
# VMEM, which is where fusion matters most (ref csrc/megatron/
# scaled_masked_softmax.h caps at 16k the same way and falls back to
# unfused torch). Two blocked passes: (1) online (max, sumexp) row stats
# over the k sweep, (2) normalize blockwise. x streams through VMEM twice;
# nothing of size [sq, sk] is ever resident.


def _causal_pos(bq, bk, qi, ki, off):
    row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return col > row + off


def _stats_kernel(scale, bq, bk, off, causal, x_ref, mask_ref, m_ref, l_ref,
                  m_sc, l_sc):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        # -inf, not _MASK_FILL: a row whose true max is below the fill
        # value must still normalize (exp(-inf - m_new) == 0 is fine;
        # seeding with the fill value would zero the sum and divide by 0).
        m_sc[:] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[:] = jnp.zeros_like(l_sc)

    xb = x_ref[0].astype(jnp.float32) * scale
    if causal:
        xb = jnp.where(_causal_pos(bq, bk, qi, ki, off), _MASK_FILL, xb)
    if mask_ref is not None:
        xb = jnp.where(mask_ref[0], _MASK_FILL, xb)
    m_prev = m_sc[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(xb, axis=-1))
    # m_new can be -inf while every element seen so far is -inf (additive
    # -inf masks reach this kernel); exp(-inf - -inf) = NaN, so shift by a
    # finite stand-in — all exps are exactly 0 then and l stays 0.
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    l_sc[:, 0] = (l_sc[:, 0] * jnp.exp(m_prev - m_safe)
                  + jnp.sum(jnp.exp(xb - m_safe[:, None]), axis=-1))
    m_sc[:, 0] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        m_ref[0] = m_sc[:, 0]
        l_ref[0] = l_sc[:, 0]


def _apply_kernel(scale, bq, bk, off, causal, x_ref, mask_ref, m_ref, l_ref,
                  y_ref):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    xb = x_ref[0].astype(jnp.float32) * scale
    if causal:
        xb = jnp.where(_causal_pos(bq, bk, qi, ki, off), _MASK_FILL, xb)
    if mask_ref is not None:
        xb = jnp.where(mask_ref[0], _MASK_FILL, xb)
    y = jnp.exp(xb - m_ref[0][:, None]) / l_ref[0][:, None]
    y_ref[0] = y.astype(y_ref.dtype)


def _pallas_blocked(x, mask, scale, causal):
    """Shared two-pass driver; ``mask`` broadcast to x's shape or None."""
    b, sq, sk = x.shape
    bk_target = _blocked_bk(sk)
    bq = _largest_divisor(sq, max(8, _VMEM_ROW_BUDGET // (4 * bk_target)))
    bk = _largest_divisor(sk, bk_target)
    off = sk - sq
    grid = (b, sq // bq, sk // bk)
    xspec = pl.BlockSpec((1, bq, bk), lambda i, j, k: (i, j, k))
    rowspec = pl.BlockSpec((1, bq), lambda i, j, k: (i, j))
    in_specs = [xspec]
    args = (x,)
    if mask is not None:
        in_specs.append(xspec)
        args = (x, mask)

    def with_mask(kernel):
        if mask is not None:
            return kernel
        return lambda x_ref, *rest: kernel(x_ref, None, *rest)

    m, l = pl.pallas_call(
        with_mask(functools.partial(_stats_kernel, scale, bq, bk, off,
                                    causal)),
        grid=grid,
        in_specs=in_specs,
        out_specs=[rowspec, rowspec],
        out_shape=[pallas_config.out_struct((b, sq), jnp.float32, *args)] * 2,
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32)] * 2,
        name="apex_softmax_stats",
        interpret=pallas_config.interpret(),
    )(*args)
    return pl.pallas_call(
        with_mask(functools.partial(_apply_kernel, scale, bq, bk, off,
                                    causal)),
        grid=grid,
        in_specs=in_specs + [rowspec, rowspec],
        out_specs=xspec,
        out_shape=pallas_config.out_struct(x.shape, x.dtype, *args, m, l),
        name="apex_softmax_apply",
        interpret=pallas_config.interpret(),
    )(*args, m, l)


def _pallas_causal_blocked(x, scale):
    return _pallas_blocked(x, None, scale, causal=True)


def _pallas_masked(x, mask, scale):
    mask = jnp.broadcast_to(mask, x.shape)
    lead = x.shape[:-2]
    sq, sk = x.shape[-2:]
    x3 = x.reshape((-1, sq, sk))
    mask3 = mask.reshape((-1, sq, sk))
    if sk > _WHOLE_ROW_MAX_SK:
        out = _pallas_blocked(x3, mask3, scale, causal=False)
        return out.reshape(lead + (sq, sk))
    rows = _pick_block_rows(sq, sk)
    blk = (1, rows, sk)
    idx = lambda i, j: (i, j, 0)
    out = pl.pallas_call(
        functools.partial(_masked_kernel, scale),
        out_shape=pallas_config.out_struct(x3.shape, x.dtype, x3, mask3),
        grid=(x3.shape[0], sq // rows),
        in_specs=[pl.BlockSpec(blk, idx), pl.BlockSpec(blk, idx)],
        out_specs=pl.BlockSpec(blk, idx),
        name="apex_masked_softmax",
        interpret=pallas_config.interpret(),
    )(x3, mask3)
    return out.reshape(lead + (sq, sk))


# ---------------------------------------------------------------- Pallas bwd
# One kernel for both custom_vjp rules. The backward needs no mask: a
# masked position has y == 0 exactly (causal) or y ~ exp(-10000) == 0, so
# dx = scale * y * (g - sum(g * y)) is 0 there by the formula. One grid
# step holds a block of whole rows of y, g and dx; the row sum is a lane
# reduction in VMEM, all arithmetic float32 as in the forward.

# fp32 row-block budget of the backward: y, g and dx blocks double-buffered
# in their storage dtype plus the float32 temporaries of one block must fit
# the ~16 MiB VMEM, so half the forward's.
_VMEM_BWD_ROW_BUDGET = _VMEM_ROW_BUDGET // 2


def _bwd_kernel(scale, y_ref, g_ref, dx_ref):
    y = y_ref[:].astype(jnp.float32)  # [1, block_rows, sk]
    g = g_ref[:].astype(jnp.float32)
    inner = jnp.sum(g * y, axis=-1, keepdims=True)
    dx_ref[:] = (scale * y * (g - inner)).astype(dx_ref.dtype)


def _pallas_softmax_bwd(scale, y, g):
    """``y``/``g`` of any rank >= 2 with rows last; ``g`` in the dtype
    autodiff hands over, ``dx`` in ``y.dtype``."""
    sq, sk = y.shape[-2:]
    y3 = y.reshape((-1, sq, sk))
    g3 = g.reshape((-1, sq, sk))
    rows = _pick_block_rows(sq, sk, _VMEM_BWD_ROW_BUDGET)
    blk = (1, rows, sk)
    idx = lambda i, j: (i, j, 0)
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, scale),
        out_shape=pallas_config.out_struct(y3.shape, y.dtype, y3, g3),
        grid=(y3.shape[0], sq // rows),
        in_specs=[pl.BlockSpec(blk, idx), pl.BlockSpec(blk, idx)],
        out_specs=pl.BlockSpec(blk, idx),
        name="apex_softmax_bwd",
        interpret=pallas_config.interpret(),
    )(y3, g3)
    return dx.reshape(y.shape)


# -------------------------------------------------------------- custom vjp
# The backward is the standard softmax vjp dx = scale * y * (g - sum(g * y)).
# Saving only ``y`` (not the masked pre-softmax logits) matches the CUDA
# kernels' backward (ref csrc/megatron/scaled_masked_softmax.h bwd reads
# softmax output). Both rules dispatch it by what they can observe, as the
# forward does: ``apex_softmax_bwd`` wherever the forward took its whole-row
# kernel, ``_softmax_bwd_math`` for rows longer than ``_WHOLE_ROW_MAX_SK``
# and off the TPU (why not XLA on the TPU: the module docstring).


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _causal_softmax(x, scale):
    if _pallas_ok(x.shape[-2], x.shape[-1]):
        return _pallas_causal(x, scale)
    xs = x.astype(jnp.float32) * scale
    mask = _causal_mask(xs.shape[-2], xs.shape[-1], xs.dtype)
    return _softmax_fp32(jnp.where(mask, _MASK_FILL, xs), x.dtype)


def _causal_softmax_fwd(x, scale):
    y = _causal_softmax(x, scale)
    return y, y


def _softmax_bwd_math(scale, y, g):
    y32 = y.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    inner = jnp.sum(g32 * y32, axis=-1, keepdims=True)
    return (scale * y32 * (g32 - inner)).astype(y.dtype)


def _softmax_bwd(scale, y, g):
    sq, sk = y.shape[-2:]
    if sk <= _WHOLE_ROW_MAX_SK and _pallas_ok(sq, sk):
        return _pallas_softmax_bwd(scale, y, g)
    return _softmax_bwd_math(scale, y, g)


def _causal_softmax_bwd(scale, y, g):
    return (_softmax_bwd(scale, y, g),)


_causal_softmax.defvjp(_causal_softmax_fwd, _causal_softmax_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _masked_softmax(x, mask, scale):
    if _pallas_ok(x.shape[-2], x.shape[-1]):
        return _pallas_masked(x, mask, scale)
    xs = x.astype(jnp.float32) * scale
    return _softmax_fp32(jnp.where(mask, _MASK_FILL, xs), x.dtype)


def _masked_softmax_fwd(x, mask, scale):
    y = _masked_softmax(x, mask, scale)
    return y, y


def _masked_softmax_bwd(scale, y, g):
    return (_softmax_bwd(scale, y, g), None)


_masked_softmax.defvjp(_masked_softmax_fwd, _masked_softmax_bwd)


# ------------------------------------------------------------------- public


def scaled_upper_triang_masked_softmax(inputs, _, scale: float = 1.0):
    """Causal scale+softmax on [attn_batches, sq, sk]
    (ref fused_softmax.py:53)."""
    return _causal_softmax(inputs, float(scale))


def scaled_masked_softmax(inputs, mask, scale: float = 1.0):
    """Mask-fill + scale + softmax on [b, np, sq, sk]; ``mask`` is boolean
    with True = masked (ref fused_softmax.py:94). ``mask=None`` is plain
    scaled softmax (ref ScaledSoftmax path)."""
    if mask is None:
        x = inputs.astype(jnp.float32) * scale
        return _softmax_fp32(x, inputs.dtype)
    return _masked_softmax(inputs, mask, float(scale))


class FusedScaleMaskSoftmax:
    """Dispatch wrapper (ref fused_softmax.py:101 FusedScaleMaskSoftmax).

    fusion flags are kept for parity; on TPU the fused path is always
    numerically identical to the unfused one, so the only dispatch that
    matters is causal (maskless kernel) vs padding (explicit mask).
    """

    def __init__(
        self,
        input_in_fp16: bool = False,
        input_in_bf16: bool = True,
        attn_mask_type: AttnMaskType = AttnMaskType.causal,
        scaled_masked_softmax_fusion: bool = True,
        mask_func: Optional[Callable] = None,
        softmax_in_fp32: bool = True,
        scale: Optional[float] = None,
    ):
        if input_in_fp16 and input_in_bf16:
            raise ValueError("both fp16 and bf16 flags are set")
        self.input_in_float16 = input_in_fp16 or input_in_bf16
        self.attn_mask_type = attn_mask_type
        self.scaled_masked_softmax_fusion = scaled_masked_softmax_fusion
        self.mask_func = mask_func
        self.softmax_in_fp32 = softmax_in_fp32
        self.scale = scale
        if self.scale is not None and not self.softmax_in_fp32:
            raise ValueError("softmax should be in fp32 when scaled")

    def __call__(self, input, mask=None):
        scale = self.scale if self.scale is not None else 1.0
        if self.attn_mask_type == AttnMaskType.causal:
            b, np_, sq, sk = input.shape
            if mask is None:
                out = scaled_upper_triang_masked_softmax(
                    input.reshape(b * np_, sq, sk), None, scale
                )
                return out.reshape(b, np_, sq, sk)
            # causal + padding: the triangle always applies (the reference's
            # causal kernel path never sees a mask; combining keeps both).
            mask = jnp.broadcast_to(mask, input.shape) | _causal_mask(
                sq, sk, input.dtype
            )
        if mask is not None and self.mask_func is not None:
            x = self.mask_func(input.astype(jnp.float32) * scale, mask)
            return _softmax_fp32(x, input.dtype)
        return scaled_masked_softmax(input, mask, scale)

    # parity helper (ref fused_softmax.py is_kernel_available)
    def is_kernel_available(self, mask, b, np_, sq, sk) -> bool:
        del mask, b, np_
        return _pallas_ok(sq, sk)

    @staticmethod
    def get_batch_per_block(sq, sk, b, np_):
        """ref fused_softmax.py get_batch_per_block — rows of the
        (b*np, sq, sk) batch one CUDA thread block handles. The Pallas
        analog is rows per kernel block: the grid tiles (rows, sq) and
        each program consumes a whole sk row, so the answer is the row
        tile — useful only for parity asserts, the TPU grid is chosen
        inside the kernels."""
        del sk, b, np_
        return max(1, min(128, sq))

    def forward_fused_softmax(self, input, mask=None):
        """ref fused_softmax.py:181 — force the fused (Pallas) path,
        like the reference forces its CUDA kernel; requires a TPU (or
        ``pallas_config.force('interpret')`` above this call in tests)."""
        from apex_tpu.ops import pallas_config

        mode = "interpret" if pallas_config.mode() == "interpret" else "on"
        with pallas_config.force(mode):
            return self(input, mask)

    def forward_torch_softmax(self, input, mask=None):
        """ref fused_softmax.py:186 — the unfused reference path (jnp
        fallback, named for parity with the torch implementation)."""
        from apex_tpu.ops import pallas_config

        with pallas_config.force("off"):
            return self(input, mask)
