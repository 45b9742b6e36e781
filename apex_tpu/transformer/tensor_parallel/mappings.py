"""Tensor-parallel collective regions (ref apex/transformer/tensor_parallel/mappings.py).

The reference wraps four NCCL patterns in autograd Functions:

    copy    — identity fwd,  allreduce bwd   (entering a column-parallel gemm)
    reduce  — allreduce fwd, identity bwd    (leaving a row-parallel gemm)
    scatter — split fwd,     all-gather bwd
    gather  — all-gather fwd, split bwd

On TPU none of these need a hand-written backward: JAX's collective
primitives already transpose to the right duals under ``shard_map``
(``pcast``-to-varying ⇄ ``psum``; tiled ``all_gather`` ⇄ ``psum_scatter``),
so each region is just the forward collective and autodiff produces the
reference's backward — with ``gather``'s transpose being the *more* correct
``psum_scatter`` (the reference's plain split silently assumes replicated
cotangents, ref mappings.py:127-145).

All functions must run inside ``shard_map`` with the tensor-parallel axis
bound; with tp=1 (axis absent) they are identity, so model code is
parallelism-agnostic.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.observability import scope
from apex_tpu.ops import vma as _vma
from apex_tpu.transformer import parallel_state


def _axis(axis_name: Optional[str]) -> str:
    """``None`` means the DEFAULT tp axis name, not "no parallelism" —
    like the reference's ``group=None`` → default NCCL group. To run
    tensor-parallel code unpartitioned on a mesh that has a bound 'tp'
    axis, use a different axis name for that mesh dimension; when 'tp' is
    simply unbound these regions are identity."""
    return (
        axis_name
        if axis_name is not None
        else parallel_state.TENSOR_AXIS
    )


def _axis_bound(axis: str) -> bool:
    """True when ``axis`` is a manual (shard_map) axis in the current trace."""
    try:
        jax.lax.axis_size(axis)
        return True
    except (NameError, ValueError, KeyError, TypeError):
        return False


def make_varying(x, axis: str):
    """Mark a replicated value as device-varying over a shard_map axis
    (transpose: psum). Idempotent: values already varying over ``axis``
    pass through. Public — model code, examples, and other subsystems
    need it whenever fresh values must match the vma of computed ones."""
    return _vma.to_varying(x, (axis,))


def tree_vma(*trees) -> set:
    """Union of the mesh axes any leaf of the given pytrees varies over.

    The standard companion to :func:`make_varying`: fresh zeros for scan
    carries / cond branches must be marked varying over exactly these
    axes to type-match values computed from the real inputs."""
    return set(_vma.vma(*trees))


_to_varying = make_varying


def _to_invariant(x, axis: str):
    """Make a numerically-replicated value vma-invariant over ``axis``
    (e.g. an all_gather output, identical on every rank). jax has no claim
    primitive, so this divides by the axis size and psums — psum is the
    variant→invariant collective. XLA folds the scale into the reduce."""
    if axis not in _vma.vma(x):
        return x
    n = jax.lax.axis_size(axis)
    return jax.lax.psum(x / n, axis)


def copy_to_tensor_model_parallel_region(x, axis_name: Optional[str] = None):
    """Identity forward; gradients allreduce over tp (ref mappings.py:148)."""
    axis = _axis(axis_name)
    if not _axis_bound(axis):
        return x
    with scope("tp/copy"):
        return _to_varying(x, axis)


def reduce_from_tensor_model_parallel_region(x, axis_name: Optional[str] = None):
    """Allreduce forward; identity gradient (ref mappings.py:152)."""
    axis = _axis(axis_name)
    if not _axis_bound(axis):
        return x
    with scope("tp/allreduce"):
        return jax.lax.psum(x, axis)


def scatter_to_tensor_model_parallel_region(x, axis_name: Optional[str] = None):
    """Keep this rank's last-dim chunk (ref mappings.py:156)."""
    axis = _axis(axis_name)
    if not _axis_bound(axis):
        return x
    n = jax.lax.axis_size(axis)
    rank = jax.lax.axis_index(axis)
    chunk = x.shape[-1] // n
    with scope("tp/scatter"):
        x = _to_varying(x, axis)
        return jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk,
                                            axis=x.ndim - 1)


def gather_from_tensor_model_parallel_region(x, axis_name: Optional[str] = None):
    """All-gather last-dim chunks into the full tensor (ref mappings.py:160)."""
    axis = _axis(axis_name)
    if not _axis_bound(axis):
        return x
    with scope("tp/all_gather"):
        return jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)


def reduce_scatter_to_tensor_model_parallel_region(x, axis_name: Optional[str] = None):
    """psum_scatter over the LAST dim: the fused form of ``reduce_from``
    followed by ``scatter_to``. A full allreduce whose result is then
    sliced back to this rank's chunk moves ~2x the bytes and throws
    (n-1)/n of them away — the pattern the ``psum-scatter`` analysis
    check flags; this is the one-call fix it points at."""
    axis = _axis(axis_name)
    if not _axis_bound(axis):
        return x
    with scope("tp/reduce_scatter"):
        return jax.lax.psum_scatter(x, axis,
                                    scatter_dimension=x.ndim - 1,
                                    tiled=True)


# --------------------------------------------------- sequence-parallel duals
# (ref: Megatron-LM sequence parallelism; the apex snapshot gates these behind
# sequence_parallel_enabled on the layers.)


def scatter_to_sequence_parallel_region(x, axis_name: Optional[str] = None,
                                        seq_dim: int = 0):
    """Split the *sequence* dim across tp ranks (Megatron layout puts it
    leading; our [b, s, h] model families pass ``seq_dim=1``)."""
    axis = _axis(axis_name)
    if not _axis_bound(axis):
        return x
    n = jax.lax.axis_size(axis)
    rank = jax.lax.axis_index(axis)
    chunk = x.shape[seq_dim] // n
    with scope("sp/scatter"):
        x = _to_varying(x, axis)
        return jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk,
                                            axis=seq_dim)


def gather_from_sequence_parallel_region(x, axis_name: Optional[str] = None,
                                         seq_dim: int = 0):
    axis = _axis(axis_name)
    if not _axis_bound(axis):
        return x
    with scope("sp/all_gather"):
        return jax.lax.all_gather(x, axis, axis=seq_dim, tiled=True)


def reduce_scatter_to_sequence_parallel_region(x, axis_name: Optional[str] = None,
                                               seq_dim: int = 0):
    """psum_scatter over the sequence dim (row-parallel output in SP mode)."""
    axis = _axis(axis_name)
    if not _axis_bound(axis):
        return x
    with scope("sp/reduce_scatter"):
        return jax.lax.psum_scatter(x, axis, scatter_dimension=seq_dim,
                                    tiled=True)
