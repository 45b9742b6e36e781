"""ZeRO-1 sharded optimizer tier for the overlapped DDP comms engine.

Ref: apex/contrib/optimizers/distributed_fused_adam.py (the reference's
ZeRO shard of optimizer state over the process group) and the ZeRO
paper's stage-1 partitioning; the contrib port
(:mod:`apex_tpu.contrib.optimizers.distributed_fused_adam`) keeps the
reference's master-weights shape. This module is the *engine* tier:

- gradients are packed into the :class:`~apex_tpu.parallel.overlap.
  OverlapPlan` buckets (reverse-order greedy, grad-ready order) and
  **reduce-scattered** per bucket (``lax.psum_scatter``) with the same
  ``lax.optimization_barrier`` issue-order chain as the overlapped
  allreduce — each rank receives only its ``1/n`` shard of the summed
  gradient, ``(n-1)/n`` of the bytes an allreduce moves;
- fused Adam updates only the local optimizer-state shard (``mu``/
  ``nu`` fp32 shards — per-device optimizer HBM shrinks by ``1/dp``;
  donate the state at the jit boundary and the update is in-place);
- the updated **parameter** shard is all-gathered in the parameter's
  own storage dtype, so with bf16 params + fp32 grads the whole sync
  costs ``1.5(n-1)/n`` of the fp32 bytes — 0.75x the allreduce path
  (:func:`~apex_tpu.parallel.overlap.grad_sync_comms_bytes` is the
  shared price).

Bit-parity contract (asserted in tests/run_parallel/test_zero1.py on
the 8-device simulated mesh): for fp32 gradients the ZeRO-1 step is
bit-identical to ``sync_gradients`` + replicated ``fused_adam(flat=
True)`` — params AND optimizer state (each rank's shard equals the
matching slice of the replicated flat buffers). For bf16 grads the
reduction runs in fp32 (the cast happens before the scatter), which is
*better* than the replicated path's bf16 psum — documented difference,
not parity.

State is checkpoint-friendly: outside ``shard_map`` the shard buffers
are ordinary global arrays sharded ``P(axis)`` along dim 0 (a tiled
``psum_scatter``/``all_gather`` keeps original element order), so they
ride :mod:`apex_tpu.checkpoint`'s atomic manifest unchanged and survive
preempt/crash-restart via the resilience runtime bit-identically.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from apex_tpu.observability import scope
from apex_tpu.observability.fleet import probe as fleet_probe
from apex_tpu.optimizers import _math
from apex_tpu.parallel.overlap import (
    OverlapPlan,
    _chain,
    _pack,
    _token_of,
    _unpack_into,
    plan_overlap,
)

ScalarOrSchedule = Union[float, Callable[[jax.Array], jax.Array]]


class Zero1AdamState(NamedTuple):
    """Sharded FusedAdam state: one fp32 ``mu``/``nu`` buffer per plan
    bucket. Inside ``shard_map`` each buffer is the local
    ``padded/n`` shard; outside it is the global ``(padded,)`` array
    (shard ``P(axis)``)."""

    count: jax.Array
    mu: tuple
    nu: tuple


def _lr_at(lr: ScalarOrSchedule, count):
    return lr(count) if callable(lr) else lr


class Zero1FusedAdam:
    """Bucketed ZeRO-1 FusedAdam over a data-parallel mesh axis.

    Functional usage (``step`` must run inside ``shard_map`` with
    ``axis_name`` bound; ``init`` runs outside and returns GLOBAL
    state arrays to be passed in with dim-0 sharded specs —
    :meth:`state_specs`)::

        opt = Zero1FusedAdam(lr=1e-3, axis_name="dp", num_shards=8)
        state = opt.init(params)                    # global buffers
        specs = opt.state_specs(params)             # P("dp") per shard
        # inside the shard_mapped train step:
        new_params, new_state = opt.step(grads, state, params)

    Arguments mirror :func:`apex_tpu.optimizers.fused_adam`;
    ``gradient_average``/``gradient_predivide_factor`` fold the DDP
    gradient averaging into the scatter (do NOT also call
    ``sync_gradients`` — that would double-reduce)."""

    def __init__(self, lr: ScalarOrSchedule = 1e-3,
                 bias_correction: bool = True, betas=(0.9, 0.999),
                 eps: float = 1e-8, adam_w_mode: bool = True,
                 weight_decay: float = 0.0, axis_name: str = "dp",
                 num_shards: Optional[int] = None,
                 bucket_cap_mb: float = 10.0,
                 gradient_average: bool = True,
                 gradient_predivide_factor: float = 1.0):
        if num_shards is None:
            num_shards = jax.device_count()
        self.lr = lr
        self.bias_correction = bias_correction
        self.b1, self.b2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.axis_name = axis_name
        self.num_shards = int(num_shards)
        self.bucket_cap_mb = bucket_cap_mb
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor

    # ------------------------------------------------------------ plan

    def plan_for(self, params) -> OverlapPlan:
        """The bucket schedule (padded to the shard quantum)."""
        return plan_overlap(params, self.bucket_cap_mb,
                            num_shards=self.num_shards)

    # ------------------------------------------------------------ init

    def init(self, params) -> Zero1AdamState:
        """Global zero state: one ``(bucket.padded,)`` fp32 buffer per
        bucket for each moment. Shard them ``P(axis)`` on dim 0 when
        entering ``shard_map`` (:meth:`state_specs`)."""
        plan = self.plan_for(params)
        mu = tuple(jnp.zeros((b.padded,), jnp.float32)
                   for b in plan.buckets)
        return Zero1AdamState(count=jnp.zeros([], jnp.int32), mu=mu,
                              nu=tuple(jnp.zeros_like(m) for m in mu))

    def state_specs(self, params) -> Zero1AdamState:
        """Per-leaf PartitionSpec pytree for :class:`Zero1AdamState`
        (pass as the state's ``in_specs``/``out_specs``): one
        ``P(axis)`` per bucket buffer — moment shards along the axis —
        and a replicated step counter."""
        from jax.sharding import PartitionSpec as P

        plan = self.plan_for(params)
        return Zero1AdamState(
            count=P(),
            mu=tuple(P(self.axis_name) for _ in plan.buckets),
            nu=tuple(P(self.axis_name) for _ in plan.buckets))

    # ------------------------------------------------------------ step

    def step(self, grads, state: Zero1AdamState, params):
        """One ZeRO-1 update; call INSIDE ``shard_map``. Returns
        ``(new_params, new_state)`` — params fully updated on every
        rank (all-gathered), state advanced only in the local shard."""
        n = jax.lax.axis_size(self.axis_name)
        if n != self.num_shards:
            raise ValueError(
                f"Zero1FusedAdam was built for num_shards="
                f"{self.num_shards} but axis {self.axis_name!r} has "
                f"size {n} — state shards would not line up")
        rank = jax.lax.axis_index(self.axis_name)
        plan = self.plan_for(params)
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = jax.tree_util.tree_leaves(grads)
        if len(g_leaves) != len(p_leaves):
            raise ValueError(
                f"grads have {len(g_leaves)} leaves, params "
                f"{len(p_leaves)} — trees diverged")

        count = state.count + 1
        step_f = count.astype(jnp.float32)
        lr_t = _lr_at(self.lr, state.count)  # optax convention
        kw = dict(lr=lr_t, b1=self.b1, b2=self.b2, eps=self.eps,
                  weight_decay=self.weight_decay,
                  adam_w_mode=self.adam_w_mode, step=step_f,
                  bias_correction=self.bias_correction)
        pre = self.gradient_predivide_factor

        out = [None] * len(p_leaves)
        mu_out, nu_out = [], []
        token = None
        for k, bucket in enumerate(plan.buckets):
            shard_len = bucket.padded // n
            site = f"ddp/zero1/bucket{k}/{bucket.dtype}"
            with scope(site):
                # grads travel fp32 (the fused_adam flat packing),
                # params in their own storage dtype
                gflat = _pack(g_leaves, bucket, cast=jnp.float32)
                if pre != 1.0:
                    gflat = gflat / pre
                gflat, token = _chain(gflat, token)
                # fleet barrier-wait probe (ISSUE 12): identity when
                # off; armed, per-rank enter/exit brackets the
                # scatter+gather pair (the ZeRO-1 sync region)
                gflat = fleet_probe.collective_enter(
                    gflat, site, self.axis_name)
                g_shard = jax.lax.psum_scatter(
                    gflat, self.axis_name, scatter_dimension=0,
                    tiled=True)
                if self.gradient_average:
                    g_shard = g_shard * jnp.asarray(pre / n,
                                                    g_shard.dtype)
                pflat = _pack(p_leaves, bucket)
                p_shard = jax.lax.dynamic_slice_in_dim(
                    pflat, rank * shard_len, shard_len)
                d, m, v = _math.adam_step(
                    g_shard, p_shard, state.mu[k], state.nu[k], **kw)
                new_p_shard = p_shard + d.astype(pflat.dtype)
                new_pflat = jax.lax.all_gather(
                    new_p_shard, self.axis_name, tiled=True)
                new_pflat = fleet_probe.collective_exit(
                    new_pflat, site, self.axis_name)
            token = _token_of(new_pflat)
            mu_out.append(m)
            nu_out.append(v)
            _unpack_into(out, new_pflat, bucket)
        new_params = jax.tree_util.tree_unflatten(treedef, out)
        return new_params, Zero1AdamState(
            count=count, mu=tuple(mu_out), nu=tuple(nu_out))

    # ------------------------------------------------------- utilities

    def state_layout(self, params) -> dict:
        """The shard layout the checkpoint actually persists — what the
        state engine's ``reshard-illegal`` check consumes: the dp axis,
        the shard count the buffers were padded for, and per bucket the
        ``{dtype, total, padded}`` triple that decides whether a new
        shard count is a pure reshard (``padded % n == 0`` AND
        re-planning at ``n`` reproduces the same padding)."""
        plan = self.plan_for(params)
        return {
            "axis": self.axis_name,
            "num_shards": self.num_shards,
            "buckets": [{"dtype": b.dtype, "total": int(b.total),
                         "padded": int(b.padded)}
                        for b in plan.buckets],
        }

    def elastic_candidates(self, params, max_shards: Optional[int] = None
                           ) -> tuple:
        """Shard counts a saved state can be re-laid-out onto without
        repacking: every ``n`` (1..max_shards, default 2x the current
        count) for which EVERY bucket keeps its flat layout —
        ``padded % n == 0`` and ``_pad_up(total, n) == padded``, i.e.
        re-planning at ``n`` pads each bucket to the same length the
        saved buffers already have. Always includes the current
        ``num_shards``. The claim is machine-checked: the state
        engine's ``reshard-illegal`` proof runs over exactly this set
        in the registered ZeRO-1 target."""
        from apex_tpu.parallel.overlap import _pad_up

        plan = self.plan_for(params)
        limit = max_shards if max_shards is not None \
            else 2 * self.num_shards
        out = []
        for n in range(1, max(limit, self.num_shards) + 1):
            ok = all(b.padded % n == 0
                     and _pad_up(b.total, n) == b.padded
                     for b in plan.buckets)
            if ok or n == self.num_shards:
                out.append(n)
        return tuple(out)

    def comms_bytes(self, params) -> int:
        """Per-device grad-sync bytes of one step (the shared price —
        see :func:`~apex_tpu.parallel.overlap.grad_sync_comms_bytes`)."""
        from apex_tpu.parallel.overlap import grad_sync_comms_bytes

        return grad_sync_comms_bytes(params, self.num_shards,
                                     mode="zero1")

    def unpack_state(self, params, state: Zero1AdamState):
        """GLOBAL state buffers -> ``(mu_tree, nu_tree)`` shaped like
        ``params`` (inspection / parity testing / migration off the
        sharded layout). A tiled scatter keeps element order, so the
        global buffer is just the padded flat packing."""
        plan = self.plan_for(params)
        _, treedef = jax.tree_util.tree_flatten(params)
        trees = []
        for bufs in (state.mu, state.nu):
            if len(bufs) != len(plan.buckets):
                raise ValueError(
                    f"state has {len(bufs)} bucket buffers, plan "
                    f"{len(plan.buckets)} — state/plan diverged")
            leaves: list = [None] * plan.n_leaves
            for buf, bucket in zip(bufs, plan.buckets):
                _unpack_into(leaves, buf, bucket)
            trees.append(jax.tree_util.tree_unflatten(treedef, leaves))
        return tuple(trees)


def zero1_fused_adam(**kwargs) -> Zero1FusedAdam:
    """Factory mirroring :func:`apex_tpu.optimizers.fused_adam`'s
    call shape."""
    return Zero1FusedAdam(**kwargs)
