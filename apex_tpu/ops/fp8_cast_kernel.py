"""Pallas TPU kernel for the fused fp8 cast-and-scale pass (O4 tier).

Delayed-scaling fp8 ("FP8 Formats for Deep Learning", Micikevicius et
al. 2022) quantizes every matmul operand as ``sat_cast(x * scale)`` and
wants the NEXT step's amax observation of the same tensor — two
elementwise passes XLA runs separately. This kernel fuses them: one
stream over the buffer emits the saturating-cast fp8 values AND the
pre-scale ``max(|x|)`` (accumulated across the sequential grid into a
(1, 1) output, the same pattern as the layer_norm backward's dw/db
accumulation), so the quantize pays one read instead of two.

Layout mirrors the flat-Adam slab: the buffer pads to a fp32-tileable
``(rows, cols)`` slab and the grid walks ``block_rows``-row blocks. The
geometry is TUNER-SUPPLIED (``apex_tpu.tuning.fp8_cast_geometry`` —
candidates declared VMEM-bounded in ``tuning/search_space.py``); the
jnp fallback (same math, fused by XLA) runs on non-TPU backends and is
the baseline the autotuner races the kernel against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops import pallas_config


def _cast_scale_kernel(fmax, x_ref, s_ref, y_ref, amax_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        amax_ref[...] = jnp.zeros_like(amax_ref)

    # pre-scale amax of the REAL values; padding rows are zeros and
    # amax is >= 0, so they never vote. Stored as a (1, 1) vector: Mosaic
    # refuses a scalar store to VMEM ("Cannot store scalars to VMEM")
    amax_ref[...] = jnp.maximum(
        amax_ref[...], jnp.max(jnp.abs(x), keepdims=True))
    y = jnp.clip(x * s_ref[0, 0], -fmax, fmax)  # saturate, never inf/nan
    y_ref[...] = y.astype(y_ref.dtype)


def _pad_to_slab(x, block_rows, cols):
    n = x.size
    rows = -(-n // cols)
    rows = -(-rows // block_rows) * block_rows
    pad = rows * cols - n
    flat = x.ravel()
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, cols), n


@functools.partial(jax.jit, static_argnames=(
    "dtype", "fmax", "block_rows", "cols", "interpret"))
def _cast_and_scale_pallas(x, scale, *, dtype, fmax, block_rows, cols,
                           interpret=False):
    x2, n = _pad_to_slab(x.astype(jnp.float32), block_rows, cols)
    rows = x2.shape[0]
    sc = jnp.reshape(jnp.asarray(scale, jnp.float32), (1, 1))
    row_spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    sc_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    y2, amax = pl.pallas_call(
        functools.partial(_cast_scale_kernel, fmax),
        grid=(rows // block_rows,),
        in_specs=[row_spec, sc_spec],
        out_specs=[row_spec, sc_spec],
        out_shape=[
            pallas_config.out_struct((rows, cols), dtype, x, scale),
            pallas_config.out_struct((1, 1), jnp.float32, x, scale),
        ],
        name="apex_fp8_cast",
        interpret=interpret,
    )(x2, sc)
    return y2.ravel()[:n].reshape(x.shape), amax[0, 0]


def _cast_and_scale_jnp(x, scale, dtype, fmax):
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32))
    scale = jnp.asarray(scale, jnp.float32)
    y = jnp.clip(x32 * scale, -fmax, fmax).astype(dtype)
    return y, amax


def cast_and_scale_stats(x, scale, dtype, fmax):
    """``(sat_cast(x * scale) -> dtype, max(|x|))`` in one fused pass —
    Pallas on TPU (``use_pallas('fp8_cast')``), jnp elsewhere. ``fmax``
    is the target format's largest magnitude (saturation bound: an fp8
    overflow must clamp to the edge, not round to inf/NaN — E4M3 has no
    inf encoding at all)."""
    if x.ndim == 0 or x.size == 0 or \
            not pallas_config.use_pallas("fp8_cast"):
        return _cast_and_scale_jnp(x, scale, dtype, fmax)
    from apex_tpu.tuning import fp8_cast_geometry

    block_rows, cols = fp8_cast_geometry(x.size)
    return _cast_and_scale_pallas(
        x, scale, dtype=jnp.dtype(dtype), fmax=float(fmax),
        block_rows=block_rows, cols=cols,
        interpret=pallas_config.interpret())
