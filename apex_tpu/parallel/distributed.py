"""DistributedDataParallel — TPU re-design of ``apex.parallel.distributed``.

Ref: apex/parallel/distributed.py (+ csrc/flatten_unflatten.cpp).

The reference intercepts ``.grad`` hooks, fills flat buckets, and overlaps
NCCL allreduces with the backward pass. Under XLA the same overlap falls out
of compilation: gradient psums issued inside the jitted step are scheduled
by XLA concurrently with independent backward compute, riding the ICI mesh.
What remains of DDP is therefore:

- :func:`sync_gradients` — per-leaf ``lax.pmean``/``psum`` over the data
  axis (the default; preserves shardings, XLA fuses/overlaps);
- :func:`sync_gradients_flat` — explicit flat-bucket variant mirroring the
  reference's ``message_size`` bucketing: leaves are packed into per-dtype
  buffers (optionally planned by the C++ bucketizer in csrc/) and reduced
  with a handful of large collectives;
- :class:`DistributedDataParallel` — an apex-shaped wrapper over a flax
  module / apply_fn carrying the options (``gradient_average``,
  ``gradient_predivide_factor``, ``delay_allreduce``, ``message_size``).

Use inside ``shard_map``/``pmap`` with the mesh axis named ``data`` (or pass
``axis_name``).

IMPORTANT (shard_map semantics under ``check_vma``): inside ``shard_map``,
``jax.grad`` w.r.t. *replicated* (unvaried, ``P()``) params already inserts
the cross-replica ``psum`` — the transpose of the implicit broadcast. In
that pattern grads arrive globally **summed**; use :func:`average_reduced`
(divide by world size), NOT :func:`sync_gradients`, or you double-reduce.
Explicit :func:`sync_gradients` is for genuinely per-replica grads:
pmap-style per-device param copies, params made varying with
``jax.lax.pcast(..., to="varying")``, or any body run with
``check_vma=False`` (no types, so nothing is summed for you).

The fused kernels follow the same rule: every ``jax.custom_vjp`` in the
tree returns cotangents typed like its primals
(:func:`apex_tpu.ops.vma.cotangent_like`), so a replicated norm weight's
gradient leaves the kernel summed and invariant like every other
replicated parameter's. :func:`sync_autodiff_gradients` reads each leaf's
type rather than assuming it, so it is the safe default for
replicated-param DDP: an invariant leaf is divided, a leaf the caller
made varying gets a real ``pmean``.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from apex_tpu.observability import scope
from apex_tpu.observability.fleet import probe as fleet_probe
from apex_tpu.ops.flat import flatten_tree, unflatten_tree


def sync_gradients(grads, axis_name: str = "data", gradient_average: bool = True,
                   gradient_predivide_factor: float = 1.0):
    """Allreduce a gradient pytree across the data-parallel axis.

    Ref apex/parallel/distributed.py:allreduce_params / allreduce hooks.
    ``gradient_predivide_factor`` splits the division between before and
    after the reduction to avoid overflow in fp16 sums (ref distributed.py
    predivide logic).
    """
    # fleet barrier-wait probe sites (ISSUE 12): one per leaf — the
    # per-leaf psums are independent and can overlap, so a shared site
    # key would clobber its own enter/exit timestamps. tree_map visits
    # leaves in deterministic flatten order, so the numbering is
    # stable across traces.
    leaf_counter = itertools.count()

    def reduce_leaf(g):
        if gradient_predivide_factor != 1.0:
            g = g / gradient_predivide_factor
        site = f"ddp/allreduce/leaf{next(leaf_counter)}"
        g = fleet_probe.collective_enter(g, site, axis_name)
        g = jax.lax.psum(g, axis_name)
        g = fleet_probe.collective_exit(g, site, axis_name)
        if gradient_average:
            # axis_size is a compile-time constant; psum(ones) here
            # would emit a real collective for it (apex_tpu.analysis
            # dead-collective)
            n = jax.lax.axis_size(axis_name)
            g = g * jnp.asarray(gradient_predivide_factor / n, g.dtype)
        return g

    with scope("ddp/allreduce"):
        return jax.tree_util.tree_map(reduce_leaf, grads)


def sync_gradients_flat(grads, axis_name: str = "data", gradient_average: bool = True,
                        gradient_predivide_factor: float = 1.0):
    """Flat-bucket allreduce: pack per-dtype, reduce once per dtype, unpack.

    The explicit analog of the reference's flat NCCL buckets
    (ref apex/parallel/distributed.py:flat_dist_call).
    ``gradient_predivide_factor`` splits the averaging around the
    reduction exactly as :func:`sync_gradients` does (pre-divide before
    the psum, multiply by ``factor/n`` after), so the flat path keeps
    the same fp16-overflow headroom.
    """
    pre = gradient_predivide_factor
    with scope("ddp/allreduce_flat"):
        bufs, meta = flatten_tree(grads)
        reduced = {}
        for k, buf in bufs.items():
            with scope(f"ddp/bucket/{k}"):
                if pre != 1.0:
                    buf = buf / pre
                buf = fleet_probe.collective_enter(
                    buf, f"ddp/bucket/{k}", axis_name)
                r = jax.lax.psum(buf, axis_name)
                r = fleet_probe.collective_exit(
                    r, f"ddp/bucket/{k}", axis_name)
                if gradient_average:
                    # static axis size, not psum(ones): the probe would
                    # be a dead collective riding every bucket
                    n = jax.lax.axis_size(axis_name)
                    r = r * jnp.asarray(pre / n, r.dtype)
            reduced[k] = r
        return unflatten_tree(reduced, meta)


def sync_gradients_bucketed(grads, axis_name: str = "data",
                            gradient_average: bool = True,
                            bucket_cap_mb: float = 10.0,
                            gradient_predivide_factor: float = 1.0):
    """Size-capped flat-bucket allreduce (ref apex DDP ``message_size``
    bucketing, apex/parallel/distributed.py).

    The bucket plan comes from the C++ host runtime
    (csrc/host_runtime.cpp apex_plan_buckets — reverse-order greedy, the
    grad-ready order of backprop); packing and the psum per bucket run
    inside the jitted step. Multiple buckets give XLA independent
    collectives to overlap with compute, mirroring the reference's
    overlapped NCCL buckets.
    """
    from apex_tpu.runtime import plan_buckets

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    # plan on host (static under trace): group same-dtype leaves by cap
    order = sorted(range(len(leaves)),
                   key=lambda i: jnp.dtype(leaves[i].dtype).name)
    cap = int(bucket_cap_mb * 1024 * 1024)
    plans = {}  # dtype -> (leaf indices, bucket ids)
    for dt in sorted({jnp.dtype(l.dtype).name for l in leaves}):
        idxs = [i for i in order if jnp.dtype(leaves[i].dtype).name == dt]
        sizes = [leaves[i].size * leaves[i].dtype.itemsize for i in idxs]
        plans[dt] = (idxs, plan_buckets(sizes, cap))

    out = [None] * len(leaves)
    n = jax.lax.axis_size(axis_name)
    pre = gradient_predivide_factor
    for dt, (idxs, bucket_ids) in plans.items():
        n_buckets = max(bucket_ids) + 1 if bucket_ids else 0
        for b in range(n_buckets):
            members = [i for i, bid in zip(idxs, bucket_ids) if bid == b]
            with scope(f"ddp/bucket{b}/{dt}"):
                flat = jnp.concatenate([leaves[i].ravel() for i in members])
                if pre != 1.0:
                    flat = flat / pre
                red = jax.lax.psum(flat, axis_name)
                if gradient_average:
                    red = red * jnp.asarray(pre / n, red.dtype)
            off = 0
            for i in members:
                sz = leaves[i].size
                out[i] = red[off:off + sz].reshape(leaves[i].shape)
                off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


def average_reduced(grads, axis_name: str = "data"):
    """Turn auto-psummed grads (replicated-params pattern, see module note)
    into data-parallel *averaged* grads: divide by the axis size."""
    def avg(g):
        n = jax.lax.axis_size(axis_name)
        return (g / jnp.asarray(n, g.dtype)).astype(g.dtype)
    return jax.tree_util.tree_map(avg, grads)


def sync_autodiff_gradients(grads, axis_name: str = "data"):
    """Per-leaf vma-aware gradient averaging for the replicated-params
    pattern (see the module note). Under ``check_vma`` autodiff — and
    every fused kernel's VJP rule — hands back the grads of replicated
    params already psummed (invariant over ``axis_name``); those are
    divided by the axis size. A leaf still varying over ``axis_name``
    (params the caller made varying) gets an explicit ``pmean``. Either
    way the result is the invariant global-batch-mean gradient, safe for
    ``lax.cond``-based overflow skips, and nothing is reduced twice."""
    def one(g):
        if axis_name in jax.typeof(g).vma:
            return jax.lax.pmean(g, axis_name)
        n = jax.lax.axis_size(axis_name)
        return (g / jnp.asarray(n, g.dtype)).astype(g.dtype)
    with scope("ddp/sync"):
        return jax.tree_util.tree_map(one, grads)


class Reducer:
    """Manually-triggered parameter allreducer (ref apex/parallel/__init__.py
    Reducer: "allreduce_params() averages parameters across processes")."""

    def __init__(self, params_or_module=None, axis_name: str = "data"):
        self.axis_name = axis_name
        self.params = params_or_module

    def reduce(self, tree=None):
        tree = tree if tree is not None else self.params
        n_fn = lambda x: jnp.asarray(
            jax.lax.axis_size(self.axis_name), x.dtype)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, self.axis_name) / n_fn(x), tree)


class DistributedDataParallel:
    """apex-shaped DDP wrapper for flax modules / apply functions.

    Ref apex/parallel/distributed.py:DistributedDataParallel.__init__
    (message_size, delay_allreduce, gradient_average,
    gradient_predivide_factor...).

    Functional usage (inside the jitted, shard_mapped train step)::

        ddp = DistributedDataParallel(model.apply, axis_name="data")
        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = ddp.sync(grads)           # bucketed allreduce over 'data'

    or wrap the grad fn once: ``grad_fn = ddp.wrap_grad_fn(jax.grad(loss_fn))``.
    With ``delay_allreduce=True`` :meth:`sync` is a no-op until
    :meth:`allreduce` is called explicitly (gradient accumulation).
    """

    def __init__(self, module_or_apply: Any = None, message_size: int = 10000000,
                 delay_allreduce: bool = False, shared_param: Optional[bool] = None,
                 allreduce_trigger_params=None, retain_allreduce_buffers: bool = False,
                 allreduce_always_fp32: bool = False, num_allreduce_streams: int = 1,
                 allreduce_communicators=None, gradient_average: bool = True,
                 gradient_predivide_factor: float = 1.0, gradient_average_split_factor=None,
                 prof: bool = False, axis_name: str = "data", flat_buckets: bool = True,
                 overlap_buckets: bool = False, bucket_cap_mb: float = 10.0):
        if shared_param is not None:
            raise ValueError(
                "shared_param is deprecated (matches the reference's error; "
                "ref distributed.py:__init__)")
        del allreduce_trigger_params, retain_allreduce_buffers  # GPU stream details
        del num_allreduce_streams, allreduce_communicators, prof
        del gradient_average_split_factor, message_size  # XLA schedules collectives
        self.module = module_or_apply
        self.axis_name = axis_name
        self.delay_allreduce = delay_allreduce
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.flat_buckets = flat_buckets
        self.overlap_buckets = overlap_buckets
        self.bucket_cap_mb = bucket_cap_mb

    def __call__(self, *args, **kwargs):
        if self.module is None:
            raise ValueError("DistributedDataParallel was built without a module")
        fn = getattr(self.module, "apply", self.module)
        return fn(*args, **kwargs)

    def _sync_fn(self, grads):
        if self.overlap_buckets:
            from apex_tpu.parallel.overlap import sync_gradients_overlapped

            return sync_gradients_overlapped(
                grads, self.axis_name, self.gradient_average,
                self.gradient_predivide_factor,
                bucket_cap_mb=self.bucket_cap_mb)
        if self.flat_buckets:
            return sync_gradients_flat(
                grads, self.axis_name, self.gradient_average,
                self.gradient_predivide_factor)
        return sync_gradients(grads, self.axis_name, self.gradient_average,
                              self.gradient_predivide_factor)

    def _reduce(self, grads):
        if self.allreduce_always_fp32:
            orig = grads
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
            return jax.tree_util.tree_map(
                lambda r, g: r.astype(g.dtype), self._sync_fn(grads), orig)
        return self._sync_fn(grads)

    def sync(self, grads):
        """Reduce grads across the data axis (no-op when delay_allreduce)."""
        if self.delay_allreduce:
            return grads
        return self._reduce(grads)

    def allreduce(self, grads):
        """Explicit reduction for the delay_allreduce accumulation pattern."""
        return self._reduce(grads)

    def average_reduced(self, grads):
        """Average grads that were already psummed by autodiff (the
        replicated-params pattern — see module docstring). vma-aware:
        leaves the caller made varying get a real pmean."""
        if not self.gradient_average:
            return grads
        return sync_autodiff_gradients(grads, self.axis_name)

    def wrap_grad_fn(self, grad_fn: Callable) -> Callable:
        """Return a grad fn whose outputs are already synced (per-replica
        grads pattern)."""
        def wrapped(*args, **kwargs):
            out = grad_fn(*args, **kwargs)
            if isinstance(out, tuple):  # value_and_grad
                return (*out[:-1], self.sync(out[-1]))
            return self.sync(out)
        return wrapped
