"""Varying-manual-axes (vma) types inside ``jax.shard_map``.

Under ``check_vma`` every value carries the set of mesh axes it differs
over. Autodiff keeps the types honest for jnp code (an invariant weight
meeting a varying activation is implicitly ``pcast`` to varying, whose
transpose is ``psum``); a ``jax.custom_vjp`` hides that broadcast, so its
backward rule has to return cotangents of its primals' types itself.
:func:`cotangent_like` is the one rule every fused op here applies.

Outside ``shard_map`` (and with ``check_vma=False``, an untyped body
whose author reduces every gradient by hand) every set is empty and
everything in this module is an identity.
"""

from __future__ import annotations

import jax

__all__ = ["vma", "to_varying", "cotangent_like"]


def vma(*trees) -> frozenset:
    """Union of the mesh axes any leaf of the given pytrees varies over."""
    axes: frozenset = frozenset()
    for leaf in jax.tree_util.tree_leaves(trees):
        axes |= jax.typeof(leaf).vma
    return axes


def _typed(axis) -> bool:
    """Does the enclosing ``shard_map`` body track varying axes
    (``check_vma``)? ``axis_index`` varies over its own axis exactly when
    it does; no other public API says so."""
    return axis in jax.typeof(jax.lax.axis_index(axis)).vma


def to_varying(x, axes):
    """Mark ``x`` varying over every axis in ``axes`` it is not already
    varying over (transpose: ``psum``). In an untyped body this is the
    identity, forward and backward: there ``pcast`` would still transpose
    to a type-checked psum and fail on its untyped cotangent."""
    missing = tuple(sorted(set(axes) - jax.typeof(x).vma))
    if not missing or not _typed(missing[0]):
        return x
    return jax.lax.pcast(x, missing, to="varying")


def cotangent_like(ct, primal):
    """Type a custom-VJP cotangent like its primal.

    A weight replicated over ``dp`` whose activations are sharded over
    ``dp`` gets a per-shard partial gradient from the kernel; the
    gradient of the replicated weight is their sum, exactly what
    autodiff of the jnp path produces. So: ``psum`` over the axes the
    cotangent varies on and the primal does not, and mark varying the
    axes the primal varies on and the cotangent does not. Data-parallel
    code downstream therefore sees ONE kind of gradient for replicated
    parameters — already summed, invariant — whether or not the op was
    fused (see ``parallel.distributed.sync_autodiff_gradients``).
    """
    have, want = jax.typeof(ct).vma, jax.typeof(primal).vma
    extra = tuple(sorted(have - want))
    if extra:
        ct = jax.lax.psum(ct, extra)
    return to_varying(ct, want)
