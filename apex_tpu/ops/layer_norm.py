"""Pallas TPU kernels for fused LayerNorm / RMSNorm.

TPU re-design of the reference CUDA kernels
(ref csrc/layer_norm_cuda_kernel.cu via apex/normalization/fused_layer_norm.py).

Design: one single-pass kernel per row-block computes the statistics and the
normalized output in VMEM (fp32 math regardless of storage dtype — same
policy as the CUDA kernel's float accumulators). The backward is ALSO a
single-pass Pallas kernel (dx per row-block + dw/db accumulated across the
sequential grid into one (1, h) output — the TPU analog of the reference's
dedicated bwd kernels, csrc/layer_norm_cuda_kernel.cu cuComputeGradInput +
cuComputePartGradGammaBeta); saved activations are just (mu, rstd). A
closed-form jnp backward remains as the non-TPU fallback and as the
baseline bench.py races the kernel against.

On non-TPU backends (tests run on a CPU mesh) the forward falls back to an
equivalent jnp implementation — same math, same vjp.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import pallas_config
from apex_tpu.ops.vma import cotangent_like


def _use_pallas(kernel: str = "layer_norm") -> bool:
    return pallas_config.use_pallas(kernel)


# ---------------------------------------------------------------- kernels


def _ln_fwd_kernel(eps, affine, x_ref, w_ref, b_ref, y_ref, mu_ref, rstd_ref):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    if affine:
        y = xhat * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    else:
        y = xhat
    y_ref[:] = y.astype(y_ref.dtype)
    mu_ref[:] = mu
    rstd_ref[:] = rstd


def _rms_fwd_kernel(eps, affine, x_ref, w_ref, y_ref, rstd_ref):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    xhat = x * rstd
    if affine:
        y = xhat * w_ref[:].astype(jnp.float32)
    else:
        y = xhat
    y_ref[:] = y.astype(y_ref.dtype)
    rstd_ref[:] = rstd


# Row-block selection is TUNER-SUPPLIED (apex_tpu.tuning): a tuned cache
# entry for (device_kind, kernel, shape-bucket) wins, otherwise the
# search-space default ladder — the same VMEM-scoped heuristic that used
# to live here as module constants (Mosaic's stack limit is 16MB,
# validated on a v5e: the bwd kernel at block=256, h=4096 was rejected
# at 20.23M). `f32_temps` is the number of block×h fp32 intermediates
# the kernel holds live (measured ~5 for bwd, ~3 for fwd); the tuner
# clamps a tuned block back down when the bwd's temps would bust VMEM.


def _row_block(n_rows: int, h: int, f32_temps: int,
               kernel: str = "layer_norm") -> int:
    from apex_tpu.tuning import norm_row_block

    return norm_row_block(kernel, n_rows, h, f32_temps)


def _pad_rows(x2, block):
    n = x2.shape[0]
    pad = (-n) % block
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, n


def _ln_fwd_pallas(x2, w, b, eps):
    affine = w is not None
    block = _row_block(x2.shape[0], x2.shape[1], 3)
    if not block:
        return _ln_fwd_jnp(x2, w, b, eps)
    x2p, n = _pad_rows(x2, block)
    rows, h = x2p.shape
    grid = (rows // block,)
    row_spec = pl.BlockSpec((block, h), lambda i: (i, 0), memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((block, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)
    in_specs = [row_spec] + ([vec_spec, vec_spec] if affine else [])
    args = (x2p,) + ((w.reshape(1, h), b.reshape(1, h)) if affine else ())
    kernel = functools.partial(_ln_fwd_kernel, eps, affine)
    if not affine:
        kernel = functools.partial(
            lambda eps_, x_ref, y_ref, mu_ref, rstd_ref: _ln_fwd_kernel(
                eps_, False, x_ref, None, None, y_ref, mu_ref, rstd_ref), eps)
    y, mu, rstd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[
            pallas_config.out_struct((rows, h), x2.dtype, *args),
            pallas_config.out_struct((rows, 1), jnp.float32, *args),
            pallas_config.out_struct((rows, 1), jnp.float32, *args),
        ],
        name="apex_ln_fwd",
        interpret=pallas_config.interpret(),
    )(*args)
    return y[:n], mu[:n], rstd[:n]


def _rms_fwd_pallas(x2, w, eps):
    affine = w is not None
    block = _row_block(x2.shape[0], x2.shape[1], 3, kernel="rms_norm")
    if not block:
        return _rms_fwd_jnp(x2, w, eps)
    x2p, n = _pad_rows(x2, block)
    rows, h = x2p.shape
    grid = (rows // block,)
    row_spec = pl.BlockSpec((block, h), lambda i: (i, 0), memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((block, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)
    in_specs = [row_spec] + ([vec_spec] if affine else [])
    args = (x2p,) + ((w.reshape(1, h),) if affine else ())
    if affine:
        kernel = functools.partial(_rms_fwd_kernel, eps, True)
    else:
        kernel = functools.partial(
            lambda eps_, x_ref, y_ref, rstd_ref: _rms_fwd_kernel(
                eps_, False, x_ref, None, y_ref, rstd_ref), eps)
    y, rstd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[row_spec, stat_spec],
        out_shape=[
            pallas_config.out_struct((rows, h), x2.dtype, *args),
            pallas_config.out_struct((rows, 1), jnp.float32, *args),
        ],
        name="apex_rms_fwd",
        interpret=pallas_config.interpret(),
    )(*args)
    return y[:n], rstd[:n]


# ------------------------------------------------------- backward kernels


def _ln_bwd_kernel(affine, x_ref, dy_ref, mu_ref, rstd_ref, *refs):
    """dx for one row block; dw/db accumulate across the (sequential) grid
    into a shared (1, h) block — no [grid, h] partials in HBM."""
    i = pl.program_id(0)
    if affine:
        w_ref, dx_ref, dw_ref, db_ref = refs
    else:
        dx_ref, = refs
    x = x_ref[:].astype(jnp.float32)
    g = dy_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = (x - mu_ref[:]) * rstd
    gw = g * w_ref[:].astype(jnp.float32) if affine else g
    m1 = jnp.mean(gw, axis=-1, keepdims=True)
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (gw - m1 - xhat * m2)).astype(dx_ref.dtype)
    if affine:
        @pl.when(i == 0)
        def _init():
            dw_ref[:] = jnp.zeros_like(dw_ref)
            db_ref[:] = jnp.zeros_like(db_ref)

        dw_ref[:] += jnp.sum(g * xhat, axis=0, keepdims=True)
        db_ref[:] += jnp.sum(g, axis=0, keepdims=True)


def _rms_bwd_kernel(affine, x_ref, dy_ref, rstd_ref, *refs):
    i = pl.program_id(0)
    if affine:
        w_ref, dx_ref, dw_ref = refs
    else:
        dx_ref, = refs
    x = x_ref[:].astype(jnp.float32)
    g = dy_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x * rstd
    gw = g * w_ref[:].astype(jnp.float32) if affine else g
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (gw - xhat * m2)).astype(dx_ref.dtype)
    if affine:
        @pl.when(i == 0)
        def _init():
            dw_ref[:] = jnp.zeros_like(dw_ref)

        dw_ref[:] += jnp.sum(g * xhat, axis=0, keepdims=True)


def _ln_bwd_jnp(x2, w, mu, rstd, dy):
    """Closed-form jnp backward (fallback + non-TPU path)."""
    x = x2.astype(jnp.float32)
    g = dy.astype(jnp.float32)
    xhat = (x - mu) * rstd
    gw = g * w.astype(jnp.float32).reshape(1, -1) if w is not None else g
    m1 = jnp.mean(gw, axis=-1, keepdims=True)
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = (rstd * (gw - m1 - xhat * m2)).astype(x2.dtype)
    if w is None:
        return dx
    dw = jnp.sum(g * xhat, axis=0).astype(w.dtype)
    db = jnp.sum(g, axis=0).astype(w.dtype)
    return dx, dw, db


def _rms_bwd_jnp(x2, w, rstd, dy):
    x = x2.astype(jnp.float32)
    g = dy.astype(jnp.float32)
    xhat = x * rstd
    gw = g * w.astype(jnp.float32).reshape(1, -1) if w is not None else g
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = (rstd * (gw - xhat * m2)).astype(x2.dtype)
    if w is None:
        return dx
    dw = jnp.sum(g * xhat, axis=0).astype(w.dtype)
    return dx, dw


def _ln_bwd_pallas(x2, w, mu, rstd, dy):
    affine = w is not None
    block = _row_block(x2.shape[0], x2.shape[1], 5)
    if not block:
        return _ln_bwd_jnp(x2, w, mu, rstd, dy)
    x2p, n = _pad_rows(x2, block)
    dyp, _ = _pad_rows(dy, block)
    mup, _ = _pad_rows(mu, block)
    rstdp, _ = _pad_rows(rstd, block)
    rows, h = x2p.shape
    grid = (rows // block,)
    row_spec = pl.BlockSpec((block, h), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((block, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    in_specs = [row_spec, row_spec, stat_spec, stat_spec]
    args = (x2p, dyp, mup, rstdp)
    out_specs = [row_spec]
    out_shape = [pallas_config.out_struct((rows, h), x2.dtype, *args)]
    if affine:
        in_specs.append(vec_spec)
        args = args + (w.reshape(1, h),)
        out_specs += [vec_spec, vec_spec]
        out_shape += [
            pallas_config.out_struct((1, h), jnp.float32, *args),
            pallas_config.out_struct((1, h), jnp.float32, *args),
        ]
    outs = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, affine),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name="apex_ln_bwd",
        interpret=pallas_config.interpret(),
    )(*args)
    if affine:
        dx, dw, db = outs
        return dx[:n], dw[0].astype(w.dtype), db[0].astype(w.dtype)
    return outs[0][:n]


def _rms_bwd_pallas(x2, w, rstd, dy):
    affine = w is not None
    block = _row_block(x2.shape[0], x2.shape[1], 5, kernel="rms_norm")
    if not block:
        return _rms_bwd_jnp(x2, w, rstd, dy)
    x2p, n = _pad_rows(x2, block)
    dyp, _ = _pad_rows(dy, block)
    rstdp, _ = _pad_rows(rstd, block)
    rows, h = x2p.shape
    grid = (rows // block,)
    row_spec = pl.BlockSpec((block, h), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((block, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    in_specs = [row_spec, row_spec, stat_spec]
    args = (x2p, dyp, rstdp)
    out_specs = [row_spec]
    out_shape = [pallas_config.out_struct((rows, h), x2.dtype, *args)]
    if affine:
        in_specs.append(vec_spec)
        args = args + (w.reshape(1, h),)
        out_specs.append(vec_spec)
        out_shape.append(
            pallas_config.out_struct((1, h), jnp.float32, *args))
    outs = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, affine),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name="apex_rms_bwd",
        interpret=pallas_config.interpret(),
    )(*args)
    if affine:
        dx, dw = outs
        return dx[:n], dw[0].astype(w.dtype)
    return outs[0][:n]


# ------------------------------------------------------- fallbacks (jnp)


def _ln_fwd_jnp(x2, w, b, eps):
    x = x2.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd
    if w is not None:
        y = y * w.astype(jnp.float32).reshape(1, -1) + b.astype(jnp.float32).reshape(1, -1)
    return y.astype(x2.dtype), mu, rstd


def _rms_fwd_jnp(x2, w, eps):
    x = x2.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y = x * rstd
    if w is not None:
        y = y * w.astype(jnp.float32).reshape(1, -1)
    return y.astype(x2.dtype), rstd


# ------------------------------------------------ custom_vjp entry points


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm_affine(x2, w, b, eps):
    fwd = _ln_fwd_pallas if _use_pallas() else _ln_fwd_jnp
    return fwd(x2, w, b, eps)[0]


def _layer_norm_affine_fwd(x2, w, b, eps):
    fwd = _ln_fwd_pallas if _use_pallas() else _ln_fwd_jnp
    y, mu, rstd = fwd(x2, w, b, eps)
    return y, (x2, w, mu, rstd)


def _layer_norm_affine_bwd(eps, res, dy):
    x2, w, mu, rstd = res
    bwd = _ln_bwd_pallas if _use_pallas() else _ln_bwd_jnp
    dx, dw, db = bwd(x2, w, mu, rstd, dy)
    return dx, cotangent_like(dw, w), cotangent_like(db, w)


_layer_norm_affine.defvjp(_layer_norm_affine_fwd, _layer_norm_affine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _layer_norm_plain(x2, eps):
    fwd = _ln_fwd_pallas if _use_pallas() else _ln_fwd_jnp
    return fwd(x2, None, None, eps)[0]


def _layer_norm_plain_fwd(x2, eps):
    fwd = _ln_fwd_pallas if _use_pallas() else _ln_fwd_jnp
    y, mu, rstd = fwd(x2, None, None, eps)
    return y, (x2, mu, rstd)


def _layer_norm_plain_bwd(eps, res, dy):
    x2, mu, rstd = res
    if _use_pallas():
        return (_ln_bwd_pallas(x2, None, mu, rstd, dy),)
    return (_ln_bwd_jnp(x2, None, mu, rstd, dy),)


_layer_norm_plain.defvjp(_layer_norm_plain_fwd, _layer_norm_plain_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_affine(x2, w, eps):
    fwd = _rms_fwd_pallas if _use_pallas("rms_norm") else _rms_fwd_jnp
    return fwd(x2, w, eps)[0]


def _rms_norm_affine_fwd(x2, w, eps):
    fwd = _rms_fwd_pallas if _use_pallas("rms_norm") else _rms_fwd_jnp
    y, rstd = fwd(x2, w, eps)
    return y, (x2, w, rstd)


def _rms_norm_affine_bwd(eps, res, dy):
    x2, w, rstd = res
    bwd = _rms_bwd_pallas if _use_pallas("rms_norm") else _rms_bwd_jnp
    dx, dw = bwd(x2, w, rstd, dy)
    return dx, cotangent_like(dw, w)


_rms_norm_affine.defvjp(_rms_norm_affine_fwd, _rms_norm_affine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rms_norm_plain(x2, eps):
    fwd = _rms_fwd_pallas if _use_pallas("rms_norm") else _rms_fwd_jnp
    return fwd(x2, None, eps)[0]


def _rms_norm_plain_fwd(x2, eps):
    fwd = _rms_fwd_pallas if _use_pallas("rms_norm") else _rms_fwd_jnp
    y, rstd = fwd(x2, None, eps)
    return y, (x2, rstd)


def _rms_norm_plain_bwd(eps, res, dy):
    x2, rstd = res
    if _use_pallas("rms_norm"):
        return (_rms_bwd_pallas(x2, None, rstd, dy),)
    return (_rms_bwd_jnp(x2, None, rstd, dy),)


_rms_norm_plain.defvjp(_rms_norm_plain_fwd, _rms_norm_plain_bwd)


# ------------------------------------------------------------- public API


def _to_2d(x, normalized_shape):
    import numpy as np
    h = int(np.prod(normalized_shape))
    lead = x.shape[: x.ndim - len(normalized_shape)]
    if tuple(x.shape[x.ndim - len(normalized_shape):]) != tuple(normalized_shape):
        raise ValueError(
            f"input trailing dims {x.shape} do not match normalized_shape "
            f"{normalized_shape}")
    return x.reshape(-1, h), lead


def layer_norm(x, weight: Optional[jax.Array], bias: Optional[jax.Array],
               normalized_shape, eps: float = 1e-5):
    """Fused LayerNorm over trailing ``normalized_shape`` dims."""
    normalized_shape = (normalized_shape,) if isinstance(normalized_shape, int) else tuple(normalized_shape)
    x2, lead = _to_2d(x, normalized_shape)
    if weight is not None:
        y = _layer_norm_affine(x2, weight.reshape(-1), bias.reshape(-1), eps)
    else:
        y = _layer_norm_plain(x2, eps)
    return y.reshape(*lead, *normalized_shape)


def rms_norm(x, weight: Optional[jax.Array], normalized_shape, eps: float = 1e-5):
    """Fused RMSNorm over trailing ``normalized_shape`` dims."""
    normalized_shape = (normalized_shape,) if isinstance(normalized_shape, int) else tuple(normalized_shape)
    x2, lead = _to_2d(x, normalized_shape)
    if weight is not None:
        y = _rms_norm_affine(x2, weight.reshape(-1), eps)
    else:
        y = _rms_norm_plain(x2, eps)
    return y.reshape(*lead, *normalized_shape)
