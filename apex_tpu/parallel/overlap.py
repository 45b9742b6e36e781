"""Overlapped DDP comms engine — backward-interleaved bucket allreduce.

Ref: apex/parallel/distributed.py (the grad-ready bucketing + overlapped
NCCL allreduces PyTorch DDP performs with .grad hooks) and
csrc/host_runtime.cpp ``apex_plan_buckets`` (the reverse-order greedy
bucket planner — grad-ready order ≈ reverse parameter order).

:func:`sync_gradients_bucketed` reduces after the whole backward has
produced every gradient *in program order*; nothing in the emitted HLO
tells XLA which collective should go first, so a late bucket can be
scheduled ahead of the first-ready one and the comms tail lands after
the backward instead of under it. This module makes the overlap schedule
explicit:

- :func:`plan_overlap` — a static host-side :class:`OverlapPlan` from
  ``runtime.plan_buckets`` (the C++ reverse-order greedy when the .so is
  present): per-dtype flat buckets capped at ``bucket_cap_mb``, emitted
  in grad-ready order (bucket 0 holds the LAST parameters — the first
  gradients backprop completes).
- :func:`sync_gradients_overlapped` — per-bucket flat psums where each
  bucket's packed buffer is tied to the *previous* bucket's reduced
  result with ``lax.optimization_barrier``. The chain pins the issue
  order (first-ready first, the single-NCCL-stream semantic) while each
  psum's data deps stay just its member leaves, so XLA overlaps every
  collective with the backward compute still in flight.
- :func:`overlapped_value_and_grad` — the layer-wise ``custom_vjp``-hook
  variant: each bucket's reduction is emitted INTO the backward jaxpr as
  the transpose of a per-bucket identity hook on the parameters, i.e.
  the collective appears exactly where the bucket's cotangent completes.
  Returns grads already reduced.

Both paths are bit-identical to the single-psum :func:`sync_gradients`
(same predivide -> psum -> ``* predivide/axis_size`` arithmetic; packing
is elementwise-neutral), asserted on the 8-device simulated mesh in
``tests/run_parallel/test_overlap.py``.

:func:`grad_sync_comms_bytes` is the shared comms price for the
schedule (allreduce ``2(n-1)/n`` vs ZeRO-1 reduce-scatter + all-gather
``1.5(n-1)/n`` when params are stored in half precision) — the analysis
planner and the ``ddp/comms_bytes`` gauge both read it, so the static
estimate and the runtime metric can never disagree on the model.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from apex_tpu.observability import scope
from apex_tpu.observability.fleet import probe as fleet_probe


@dataclasses.dataclass(frozen=True)
class OverlapBucket:
    """One flat bucket: contiguous run of same-dtype leaves."""

    dtype: str        # dtype name of the packed buffer
    indices: tuple    # leaf indices (tree_flatten order), ascending
    shapes: tuple     # per-leaf shapes
    sizes: tuple      # per-leaf element counts
    total: int        # sum(sizes)
    padded: int       # total rounded up to a multiple of num_shards

    @property
    def offsets(self):
        off, out = 0, []
        for s in self.sizes:
            out.append(off)
            off += s
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class OverlapPlan:
    """Static bucket schedule for one gradient pytree. ``buckets`` are
    in grad-ready (issue) order; ``num_shards`` is the ZeRO padding
    quantum (1 for plain allreduce plans)."""

    buckets: tuple
    n_leaves: int
    bucket_cap_mb: float
    num_shards: int = 1

    def total_bytes(self) -> int:
        return sum(b.total * jnp.dtype(b.dtype).itemsize
                   for b in self.buckets)


def _pad_up(total: int, k: int) -> int:
    return total + ((-total) % max(1, k))


def plan_overlap(tree, bucket_cap_mb: float = 10.0,
                 num_shards: int = 1) -> OverlapPlan:
    """Plan grad-ready-ordered flat buckets for ``tree``.

    Buckets come from :func:`apex_tpu.runtime.plan_buckets` — the
    reference's reverse-order greedy, so bucket 0 collects the LAST
    leaves (whose grads the backward finishes first) and the issue
    order follows gradient readiness. Leaves are grouped per dtype
    (flat buffers need a uniform dtype); within a dtype the bucket
    members are a contiguous ascending index run. ``num_shards`` > 1
    pads every bucket to a multiple of it (the ZeRO-1 scatter/gather
    quantum)."""
    from apex_tpu.runtime import plan_buckets

    leaves, _ = jax.tree_util.tree_flatten(tree)
    cap = int(bucket_cap_mb * 1024 * 1024)
    by_dtype: dict[str, list[int]] = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(leaf.dtype).name, []).append(i)
    buckets = []
    for dt in sorted(by_dtype):
        idxs = by_dtype[dt]
        sizes_b = [leaves[i].size * leaves[i].dtype.itemsize
                   for i in idxs]
        ids = plan_buckets(sizes_b, cap)
        n_buckets = max(ids) + 1 if ids else 0
        # bucket id 0 = the tail of the parameter list = first-ready
        for b in range(n_buckets):
            members = [i for i, bid in zip(idxs, ids) if bid == b]
            sizes = tuple(leaves[i].size for i in members)
            total = int(sum(sizes))
            buckets.append(OverlapBucket(
                dtype=dt, indices=tuple(members),
                shapes=tuple(tuple(leaves[i].shape) for i in members),
                sizes=sizes, total=total,
                padded=_pad_up(total, num_shards)))
    return OverlapPlan(buckets=tuple(buckets), n_leaves=len(leaves),
                       bucket_cap_mb=bucket_cap_mb,
                       num_shards=max(1, int(num_shards)))


def _check_plan(plan: OverlapPlan, leaves) -> None:
    if plan.n_leaves != len(leaves):
        raise ValueError(
            f"OverlapPlan was built for {plan.n_leaves} leaves, tree "
            f"has {len(leaves)} — plan and gradient tree diverged")
    for b in plan.buckets:
        for i, shape in zip(b.indices, b.shapes):
            if tuple(leaves[i].shape) != shape:
                raise ValueError(
                    f"OverlapPlan leaf {i} expects shape {shape}, got "
                    f"{tuple(leaves[i].shape)} — plan and tree diverged")


def _chain(flat, token):
    """Tie this bucket's packed buffer to the previous bucket's reduced
    result: the barrier makes XLA issue the collectives in grad-ready
    order (the reference's single comm stream) without adding any real
    compute or comms."""
    if token is None:
        return flat, None
    flat, token = jax.lax.optimization_barrier((flat, token))
    return flat, token


def _token_of(red):
    # a 1-element static slice: enough of a data dep to order the next
    # barrier, too small to keep the full buffer alive
    return jax.lax.slice_in_dim(red, 0, 1)


def _pack(leaves, bucket: OverlapBucket, cast=None):
    parts = [leaves[i].ravel() for i in bucket.indices]
    if cast is not None:
        parts = [p.astype(cast) for p in parts]
    flat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if bucket.padded != bucket.total:
        flat = jnp.pad(flat, (0, bucket.padded - bucket.total))
    return flat


def _unpack_into(out, red, bucket: OverlapBucket):
    for i, off, sz, shape in zip(bucket.indices, bucket.offsets,
                                 bucket.sizes, bucket.shapes):
        out[i] = red[off:off + sz].reshape(shape)


def sync_gradients_overlapped(grads, axis_name: str = "data",
                              gradient_average: bool = True,
                              gradient_predivide_factor: float = 1.0,
                              bucket_cap_mb: float = 10.0,
                              plan: Optional[OverlapPlan] = None):
    """Grad-ready-ordered, barrier-chained bucket allreduce.

    Bit-identical to :func:`~apex_tpu.parallel.sync_gradients` (same
    predivide -> psum -> ``* predivide/n`` chain; flat packing is
    elementwise-neutral), but each bucket's psum depends only on its
    member leaves plus the previous bucket's token, so issued inside a
    jitted step the collectives run under the remaining backward
    compute in bucket-plan order."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    if plan is None:
        plan = plan_overlap(grads, bucket_cap_mb)
    _check_plan(plan, leaves)
    pre = gradient_predivide_factor
    n = jax.lax.axis_size(axis_name)
    out = [None] * len(leaves)
    token = None
    for k, bucket in enumerate(plan.buckets):
        site = f"ddp/overlap/bucket{k}/{bucket.dtype}"
        with scope(site):
            flat = _pack(leaves, bucket)
            if pre != 1.0:
                flat = flat / pre
            flat, token = _chain(flat, token)
            # fleet barrier-wait probe (ISSUE 12): identity when off;
            # armed, it stamps per-rank enter/exit around the psum so
            # the straggler detector sees each rank's wait
            flat = fleet_probe.collective_enter(flat, site, axis_name)
            red = jax.lax.psum(flat, axis_name)
            red = fleet_probe.collective_exit(red, site, axis_name)
            if gradient_average:
                # static axis size (never psum(ones) — dead-collective)
                red = red * jnp.asarray(pre / n, red.dtype)
        token = _token_of(red)
        _unpack_into(out, red, bucket)
    return jax.tree_util.tree_unflatten(treedef, out)


def overlapped_value_and_grad(
        loss_fn: Callable, axis_name: str = "data",
        gradient_average: bool = True,
        gradient_predivide_factor: float = 1.0,
        bucket_cap_mb: float = 10.0,
        plan: Optional[OverlapPlan] = None,
        has_aux: bool = False) -> Callable:
    """``value_and_grad`` whose backward carries the bucket schedule.

    Each bucket's parameters pass through a ``custom_vjp`` identity
    hook whose transpose packs the bucket's cotangents and reduces them
    over ``axis_name`` — the collective is emitted into the backward at
    the point the bucket's grads complete (the reference's .grad-hook
    placement), instead of as a separate sync pass after it. Grads come
    back already reduced; bit-identical to ``jax.grad`` +
    :func:`~apex_tpu.parallel.sync_gradients`.

    ``loss_fn``'s first argument must be the parameter pytree."""
    pre = gradient_predivide_factor

    def _make_hook(bucket: OverlapBucket, tag: int):
        @jax.custom_vjp
        def hook(*leaves):
            return leaves

        def fwd(*leaves):
            return leaves, None

        def bwd(_, cts):
            with scope(f"ddp/overlap/bwd_bucket{tag}/{bucket.dtype}"):
                # pack the accumulated bucket cotangents and reduce them
                # right here in the backward
                local = _pack(list(cts), _rebase(bucket))
                if pre != 1.0:
                    local = local / pre
                red = jax.lax.psum(local, axis_name)
                if gradient_average:
                    n = jax.lax.axis_size(axis_name)
                    red = red * jnp.asarray(pre / n, red.dtype)
            outs: list = [None] * len(bucket.indices)
            _unpack_into(outs, red, _rebase(bucket))
            return tuple(outs)

        hook.defvjp(fwd, bwd)
        return hook

    def _rebase(bucket: OverlapBucket) -> OverlapBucket:
        # inside the hook the bucket's leaves are positions 0..k-1
        return dataclasses.replace(
            bucket, indices=tuple(range(len(bucket.indices))))

    def wrapped(params, *args, **kwargs):
        plan_ = plan if plan is not None else plan_overlap(
            params, bucket_cap_mb)
        _check_plan(plan_, jax.tree_util.tree_leaves(params))

        def hooked_loss(params, *a, **kw):
            # the hooks must sit INSIDE the differentiated function so
            # their transposes (the per-bucket reductions) are emitted
            # into the backward
            leaves, treedef = jax.tree_util.tree_flatten(params)
            hooked = list(leaves)
            for tag, bucket in enumerate(plan_.buckets):
                hook = _make_hook(bucket, tag)
                outs = hook(*[leaves[i] for i in bucket.indices])
                for i, o in zip(bucket.indices, outs):
                    hooked[i] = o
            return loss_fn(jax.tree_util.tree_unflatten(treedef, hooked),
                           *a, **kw)

        return jax.value_and_grad(hooked_loss, has_aux=has_aux)(
            params, *args, **kwargs)

    return wrapped


# --------------------------------------------------------- comms model

GRAD_SYNC_MODES = ("allreduce", "zero1")


def grad_sync_bytes_from_sizes(grad_bytes: int, param_bytes: int,
                               axis_size: int,
                               mode: str = "allreduce") -> int:
    """Size-based core of :func:`grad_sync_comms_bytes` — the form the
    auto-sharding planner prices candidates with (it has byte totals,
    not live trees)."""
    n = max(1, int(axis_size))
    if n <= 1:
        return 0
    if mode == "allreduce":
        return int(2 * grad_bytes * (n - 1) / n)
    if mode == "zero1":
        return int((grad_bytes + param_bytes) * (n - 1) / n)
    raise ValueError(
        f"unknown grad-sync mode {mode!r}; valid: "
        f"{', '.join(GRAD_SYNC_MODES)}")


def grad_sync_comms_bytes(tree, axis_size: int, mode: str = "allreduce",
                          grad_dtype=jnp.float32) -> int:
    """Per-device bytes the data-parallel gradient sync moves for one
    step over ``tree`` (the parameter pytree), under the ring model the
    sharding-flow estimator uses (`collective_bytes`):

    - ``allreduce``: psum of every gradient — ``2(n-1)/n`` of the grad
      bytes (grads travel in ``grad_dtype``, fp32 by default);
    - ``zero1``: reduce-scatter of the grads (``(n-1)/n`` of the grad
      bytes) + all-gather of the updated params in their own storage
      dtype (``(n-1)/n`` of the PARAM bytes) — 0.75x the allreduce when
      params are stored at half the gradient width (bf16 params, fp32
      grads), the ZeRO-1 pitch.

    Shared between the planner's comms model, the analysis targets and
    the ``ddp/comms_bytes`` gauge so they cannot drift apart."""
    leaves = jax.tree_util.tree_leaves(tree)
    gsize = jnp.dtype(grad_dtype).itemsize
    grad_bytes = sum(leaf.size * gsize for leaf in leaves)
    param_bytes = sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
                      for leaf in leaves)
    return grad_sync_bytes_from_sizes(grad_bytes, param_bytes,
                                      axis_size, mode)
