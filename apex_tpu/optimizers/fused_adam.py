"""FusedAdam — TPU re-design of ``apex.optimizers.FusedAdam``.

Ref: apex/optimizers/fused_adam.py + csrc/multi_tensor_adam.cu.

The CUDA version fuses (a) the Adam elementwise chain and (b) the
per-parameter kernel launches via multi-tensor apply. On TPU both fusions
fall out of compilation: ``fused_adam`` returns an optax-compatible
transform whose whole update is one jitted executable; ``flat=True``
additionally packs every parameter into one buffer per dtype so the update
is a single fused elementwise kernel no matter how many parameters exist
(the exact end state multi-tensor apply approximates on GPU).

Drop-in replacement for ``optax.adamw`` / ``optax.adam`` (adam_w_mode=False).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Union

import jax
import jax.numpy as jnp
import optax

from apex_tpu.optimizers import _math
from apex_tpu.optimizers._base import FusedOptimizer
from apex_tpu.ops.flat import flatten_tree, unflatten_tree

ScalarOrSchedule = Union[float, Callable[[jax.Array], jax.Array]]


class FusedAdamState(NamedTuple):
    count: jax.Array  # int32 step counter (apex keeps this per group; ours is global)
    mu: Any
    nu: Any


def _lr_at(lr: ScalarOrSchedule, count):
    return lr(count) if callable(lr) else lr


def fused_adam(
    lr: ScalarOrSchedule = 1e-3,
    bias_correction: bool = True,
    betas=(0.9, 0.999),
    eps: float = 1e-8,
    adam_w_mode: bool = True,
    weight_decay: float = 0.0,
    flat: bool = False,
    use_kernel: Union[bool, None] = None,
) -> optax.GradientTransformation:
    """Functional FusedAdam. Arguments mirror apex/optimizers/fused_adam.py:64.

    ``use_kernel`` (flat mode only): run the flat update through the
    Pallas kernel (ops/fused_adam_kernel.py — the multi_tensor_adam.cu
    analog) instead of the XLA-fused jnp chain. ``None`` defers to the
    pallas gate (kernel on TPU); the bench races both paths.
    """
    b1, b2 = betas

    def init(params):
        if flat:
            bufs, meta = flatten_tree(params)
            zeros = {k: jnp.zeros((v.size,), jnp.float32) for k, v in bufs.items()}
            mu = dict(zeros)
            nu = {k: jnp.zeros_like(v) for k, v in zeros.items()}
        else:
            mu = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            nu = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return FusedAdamState(count=jnp.zeros([], jnp.int32), mu=mu, nu=nu)

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("fused_adam requires params (for weight decay / bias)")
        count = state.count + 1
        step = count.astype(jnp.float32)
        lr_t = _lr_at(lr, state.count)  # optax convention: schedule sees pre-increment count
        kw = dict(
            lr=lr_t, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
            adam_w_mode=adam_w_mode, step=step, bias_correction=bias_correction,
        )
        from apex_tpu.observability import get_registry, scope, span

        if flat:
            from apex_tpu.ops import pallas_config

            # default OFF even on TPU (unlike the other fused kernels):
            # the flat update is a pure bandwidth-bound elementwise chain
            # that XLA already fuses to minimal HBM traffic, so the
            # Pallas kernel can at best tie — and lost the r3 CPU race
            # (docs/kernel_cost_study.md). The verdict lives in
            # pallas_config._KERNEL_AUTO['flat_adam'];
            # force('on')/use_kernel=True opts in; bench_kernels races
            # both and flips the table if on-chip numbers ever disagree.
            kernel_on = (use_kernel if use_kernel is not None
                         else pallas_config.use_pallas("flat_adam"))
            # the _KERNEL_AUTO outcome, observable: the counter ticks
            # once per TRACE of this update (not per step — eval_shape
            # and cond-branch traces count too), and the scope names the
            # ops so an on-silicon trace attributes kernel time to
            # flat/pallas vs flat/xla — the per-kernel race table's
            # missing evidence
            path = "pallas" if kernel_on else "xla"
            get_registry().counter("optimizer/fused_adam/dispatch",
                                   path=f"flat_{path}").inc()
            with scope(f"fused_adam/flat/{path}"):
                # Group by *param* dtype; grads may arrive in a different
                # dtype (e.g. fp32 grads over bf16 params) and are packed
                # fp32 anyway.
                pbufs, meta = flatten_tree(params)
                _, _, specs = meta
                g_leaves = jax.tree_util.tree_leaves(grads)
                deltas, mu, nu = {}, {}, {}
                for k, (idxs, spec) in specs.items():
                    gbuf = jnp.concatenate(
                        [g_leaves[i].ravel().astype(jnp.float32)
                         for i in idxs])
                    if kernel_on:
                        from apex_tpu.ops.fused_adam_kernel import (
                            adam_flat_pallas,
                        )

                        # slab geometry is tuner-supplied: the wrapper
                        # resolves it outside its inner jit, so a fresh
                        # tune changes the static key and retraces
                        d, m, v = adam_flat_pallas(
                            gbuf, pbufs[k], state.mu[k], state.nu[k],
                            jnp.asarray(lr_t, jnp.float32), step,
                            b1=b1, b2=b2, eps=eps,
                            weight_decay=weight_decay,
                            adam_w_mode=adam_w_mode,
                            bias_correction=bias_correction,
                            interpret=pallas_config.interpret())
                    else:
                        d, m, v = _math.adam_step(
                            gbuf, pbufs[k], state.mu[k], state.nu[k], **kw)
                    deltas[k] = d.astype(spec.dtype)
                    mu[k], nu[k] = m, v
                updates = unflatten_tree(deltas, meta)
        else:
            get_registry().counter("optimizer/fused_adam/dispatch",
                                   path="tree").inc()
            # the one in-jit site still in the span ring (the others are
            # bare scopes: under jit the ring would time the trace): an
            # eager update is real host time, and
            # tests/run_observability::test_hot_paths_record_spans reads it
            with span("fused_adam/tree"):
                g_leaves, treedef = jax.tree_util.tree_flatten(grads)
                p_leaves = jax.tree_util.tree_leaves(params)
                m_leaves = jax.tree_util.tree_leaves(state.mu)
                v_leaves = jax.tree_util.tree_leaves(state.nu)
                results = [
                    _math.adam_step(g, p, m, v, **kw)
                    for g, p, m, v in zip(g_leaves, p_leaves, m_leaves,
                                          v_leaves)
                ]
                updates = treedef.unflatten(
                    [r[0].astype(p.dtype)
                     for r, p in zip(results, p_leaves)])
                mu = treedef.unflatten([r[1] for r in results])
                nu = treedef.unflatten([r[2] for r in results])
        return updates, FusedAdamState(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init, update)


class FusedAdam(FusedOptimizer):
    """Stateful apex-style API (ref apex/optimizers/fused_adam.py:64).

    ``opt = FusedAdam(params, lr=1e-3); new_params = opt.step(grads)``
    """

    def __init__(self, params, lr=1e-3, bias_correction=True, betas=(0.9, 0.999),
                 eps=1e-8, adam_w_mode=True, weight_decay=0.0, amsgrad=False,
                 set_grad_none=True, flat=False):
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        del set_grad_none  # grads are functional; retained for API parity
        kw = dict(lr=lr, bias_correction=bias_correction, betas=betas, eps=eps,
                  adam_w_mode=adam_w_mode, weight_decay=weight_decay, flat=flat)
        super().__init__(params, fused_adam(**kw), dict(
            lr=lr, bias_correction=bias_correction, betas=betas, eps=eps,
            weight_decay=weight_decay),
            tx_factory=lambda **ov: fused_adam(**{**kw, **ov}))
