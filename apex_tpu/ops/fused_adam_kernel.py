"""Pallas TPU kernel for the flat-buffer fused Adam update.

The reference's ``csrc/multi_tensor_adam.cu`` is ONE kernel over chunked
tensor lists; the TPU flat path packs the whole model into a 1-D buffer
per dtype, and this kernel is the single fused elementwise pass over it
(SURVEY §1 kernel layer: "fused adam/lamb on flat buffers"). XLA's own
fusion of the jnp chain is the fallback and the baseline ``bench.py``
races this kernel against — elementwise chains are XLA's home turf, so
the kernel must EARN its default (``use_kernel=None`` defers to the
pallas gate; the bench reports both).

Layout: the 1-D buffer pads to a fp32-tileable ``(rows, cols)`` slab and
the grid walks ``block_rows``-row blocks; traced scalars (lr_t and the
bias-correction denominators — step-dependent) ride a (1, 4) block,
static hyperparams close over the kernel. The slab geometry is
TUNER-SUPPLIED (apex_tpu.tuning): callers either pass ``(block_rows,
cols)`` explicitly (the sweep does) or leave them None and get the
tuned/default pick for the actual buffer size — the fixed (rows, 1024)
slab with a constant 512-row block was the prime suspect for the
3.2x inversion measured on a v5e on 2026-07-31, and the old
small-tensor path padded a scalar bias to 8x1024 fp32 x4 buffers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops import pallas_config


def _adam_kernel(b1, b2, eps, weight_decay, adam_w_mode, bias_correction,
                 sc_ref, g_ref, p_ref, m_ref, v_ref,
                 d_ref, mo_ref, vo_ref):
    lr_t = sc_ref[0, 0]
    c1 = sc_ref[0, 1]
    c2 = sc_ref[0, 2]
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    if not adam_w_mode and weight_decay:
        g = g + weight_decay * p
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    if bias_correction:
        m_hat = m / c1
        v_hat = v / c2
    else:
        m_hat, v_hat = m, v
    update = m_hat / (jnp.sqrt(v_hat) + eps)
    if adam_w_mode and weight_decay:
        update = update + weight_decay * p
    d_ref[...] = (-lr_t * update).astype(d_ref.dtype)
    mo_ref[...] = m
    vo_ref[...] = v


def _pad_to_slab(x, block_rows, cols):
    n = x.size
    rows = -(-n // cols)
    rows = -(-rows // block_rows) * block_rows
    pad = rows * cols - n
    if pad:
        x = jnp.pad(x.ravel(), (0, pad))
    return x.reshape(rows, cols), n


def slab_geometry(n: int, block_rows=None, cols=None) -> tuple:
    """Resolve the (block_rows, cols) slab for an ``n``-element buffer:
    explicit values win (the tuner's sweep passes candidates through
    here), otherwise the tuned/default pick from apex_tpu.tuning — which
    sizes the pad block from the ACTUAL buffer, so tiny leaves no longer
    over-pad."""
    if block_rows is not None and cols is not None:
        return int(block_rows), int(cols)
    from apex_tpu.tuning import flat_adam_geometry

    t_rows, t_cols = flat_adam_geometry(n)
    return (int(block_rows) if block_rows is not None else t_rows,
            int(cols) if cols is not None else t_cols)


def adam_flat_pallas(g, p, m, v, lr_t, step, *, b1, b2, eps, weight_decay,
                     adam_w_mode, bias_correction, block_rows=None,
                     cols=None, interpret=False):
    """One fused Adam pass over 1-D buffers.

    ``g``/``m``/``v`` fp32, ``p`` any float dtype; ``lr_t``/``step``
    traced scalars. Returns ``(delta, m', v')`` with delta in p's dtype.
    ``block_rows``/``cols`` pin the slab geometry; None defers to the
    tuning cache / per-size default. Resolution happens HERE, outside
    the jit, so the resolved geometry is part of the inner jit's static
    key — a fresh tune (or a sweep override) changes the key and forces
    a retrace instead of silently reusing the first-traced tile.
    """
    block_rows, cols = slab_geometry(g.size, block_rows, cols)
    return _adam_flat_pallas(g, p, m, v, lr_t, step, b1=b1, b2=b2,
                             eps=eps, weight_decay=weight_decay,
                             adam_w_mode=adam_w_mode,
                             bias_correction=bias_correction,
                             block_rows=block_rows, cols=cols,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "b1", "b2", "eps", "weight_decay", "adam_w_mode", "bias_correction",
    "block_rows", "cols", "interpret"))
def _adam_flat_pallas(g, p, m, v, lr_t, step, *, b1, b2, eps,
                      weight_decay, adam_w_mode, bias_correction,
                      block_rows, cols, interpret=False):
    g2, n = _pad_to_slab(g.astype(jnp.float32), block_rows, cols)
    p2, _ = _pad_to_slab(p, block_rows, cols)
    m2, _ = _pad_to_slab(m, block_rows, cols)
    v2, _ = _pad_to_slab(v, block_rows, cols)
    rows = g2.shape[0]
    step = step.astype(jnp.float32)
    scalars = jnp.stack([
        jnp.asarray(lr_t, jnp.float32),
        1.0 - b1 ** step if bias_correction else jnp.float32(1.0),
        1.0 - b2 ** step if bias_correction else jnp.float32(1.0),
        jnp.float32(0.0),
    ]).reshape(1, 4)

    row_spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    sc_spec = pl.BlockSpec((1, 4), lambda i: (0, 0))
    d2, mo2, vo2 = pl.pallas_call(
        functools.partial(_adam_kernel, b1, b2, eps, weight_decay,
                          adam_w_mode, bias_correction),
        grid=(rows // block_rows,),
        in_specs=[sc_spec, row_spec, row_spec, row_spec, row_spec],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[
            pallas_config.out_struct((rows, cols), p.dtype, g, p, m, v),
            pallas_config.out_struct((rows, cols), jnp.float32, g, p, m, v),
            pallas_config.out_struct((rows, cols), jnp.float32, g, p, m, v),
        ],
        name="apex_flat_adam",
        interpret=interpret,
    )(scalars, g2, p2, m2, v2)
    return (d2.ravel()[:n], mo2.ravel()[:n], vo2.ravel()[:n])
