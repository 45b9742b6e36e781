"""DistributedFusedLAMB (ZeRO LAMB) parity vs unsharded FusedLAMB on the
dp mesh (VERDICT next-round #7; ref apex/contrib/optimizers/
distributed_fused_lamb.py)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from apex_tpu.contrib.optimizers import distributed_fused_lamb
from apex_tpu.optimizers import fused_lamb


def mesh8():
    return Mesh(np.array(jax.devices()[:8]), ("dp",))


def _params():
    # deliberately awkward sizes: padding + tensors straddling shard
    # boundaries exercise the segment-sum norm path
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 3)
    return {
        "w": jax.random.normal(ks[0], (37, 5)),
        "b": jax.random.normal(ks[1], (11,)) * 0.1,
        "v": jax.random.normal(ks[2], (3,)),
    }


def _grads():
    k = jax.random.PRNGKey(1)
    ks = jax.random.split(k, 3)
    return {
        "w": jax.random.normal(ks[0], (37, 5)) * 0.3,
        "b": jax.random.normal(ks[1], (11,)),
        "v": jax.random.normal(ks[2], (3,)) * 2.0,
    }


def test_matches_unsharded_lamb_one_step():
    mesh = mesh8()
    params, grads = _params(), _grads()
    kw = dict(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0)
    tx = distributed_fused_lamb(axis_name="dp", **kw)

    def run(params, grads):
        state = tx.init(params)
        updates, _ = tx.update(grads, state, params)
        return updates

    got = shard_map(run, mesh=mesh, in_specs=(P(), P()),
                    out_specs=P())(params, grads)

    ref_tx = fused_lamb(**kw)
    st = ref_tx.init(params)
    want, _ = ref_tx.update(grads, st, params)
    for k in params:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_matches_unsharded_lamb_trajectory():
    """Three steps with different grads: moments and bias correction stay
    in sync with the unsharded optimizer."""
    mesh = mesh8()
    params = _params()
    kw = dict(lr=5e-3, weight_decay=0.1, max_grad_norm=0.5,
              use_nvlamb=True)
    tx = distributed_fused_lamb(axis_name="dp", **kw)
    ref_tx = fused_lamb(**kw)

    def run(params, g1, g2, g3):
        state = tx.init(params)
        p = params
        for g in (g1, g2, g3):
            updates, state = tx.update(g, state, p)
            p = jax.tree_util.tree_map(jnp.add, p, updates)
        return p

    gs = [jax.tree_util.tree_map(
        lambda a, i=i: a * (0.5 + i), _grads()) for i in range(3)]
    got = jax.jit(shard_map(run, mesh=mesh, in_specs=(P(),) * 4,
                            out_specs=P()))(params, *gs)

    st = ref_tx.init(params)
    p = params
    for g in gs:
        updates, st = ref_tx.update(g, st, p)
        p = jax.tree_util.tree_map(jnp.add, p, updates)
    for k in params:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(p[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)


def test_state_is_sharded():
    """ZeRO point: each rank's master/m/v shard is 1/8 of the padded flat
    size."""
    mesh = mesh8()
    params = _params()
    total = sum(p.size for p in jax.tree_util.tree_leaves(params))
    tx = distributed_fused_lamb(axis_name="dp")

    def run(params):
        state = tx.init(params)
        return state.master_shard["float32"].size

    out = shard_map(
        lambda p: jnp.asarray(run(p)), mesh=mesh, in_specs=(P(),),
        out_specs=P())(params)
    padded = total + (-total) % 8
    assert int(out) == padded // 8


def test_contrib_optimizer_imports():
    """Import-surface parity (ref apex/contrib/optimizers/*)."""
    from apex_tpu.contrib.optimizers import (  # noqa: F401
        FP16_Optimizer,
        DistributedFusedAdam,
        DistributedFusedLAMB,
    )
    from apex_tpu.contrib.optimizers.distributed_fused_adam_v2 import (  # noqa: F401
        DistributedFusedAdamV2,
    )
    from apex_tpu.contrib.optimizers.distributed_fused_adam_v3 import (  # noqa: F401
        DistributedFusedAdamV3,
    )
    from apex_tpu.contrib.optimizers.fused_adam import FusedAdam  # noqa: F401
    from apex_tpu.contrib.optimizers.fused_lamb import FusedLAMB  # noqa: F401
    from apex_tpu.contrib.optimizers.fused_sgd import FusedSGD  # noqa: F401


def test_dp4_parity_and_rank_consistency():
    """VERDICT r4 #8: dp=4 parity vs the unsharded optimizer, plus the
    all-gather invariant — every rank must hold BITWISE-identical updated
    params (the psum-placement gather makes them invariant by
    construction; this asserts it survives refactors)."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    params, grads = _params(), _grads()
    kw = dict(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0)
    tx = distributed_fused_lamb(axis_name="dp", **kw)

    def run(params, grads):
        state = tx.init(params)
        updates, _ = tx.update(grads, state, params)
        return updates

    # stack each rank's copy (mark varying + leading rank dim) so the
    # cross-rank comparison is a real bitwise check, not a vma property
    from apex_tpu.transformer.tensor_parallel.mappings import _to_varying

    per_rank = jax.jit(shard_map(
        lambda p, g: jax.tree_util.tree_map(
            lambda u: _to_varying(u, "dp")[None], run(p, g)),
        mesh=mesh, in_specs=(P(), P()), out_specs=P("dp")))(params, grads)

    ref_tx = fused_lamb(**kw)
    st = ref_tx.init(params)
    want, _ = ref_tx.update(grads, st, params)
    for k in params:
        ranks = np.asarray(per_rank[k])
        for r in range(1, 4):
            np.testing.assert_array_equal(
                ranks[0], ranks[r],
                err_msg=f"{k}: rank {r} diverged bitwise from rank 0")
        np.testing.assert_allclose(ranks[0], np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_master_dtype_bf16_halves_state_and_stays_close():
    """master_dtype=bf16: ZeRO state stored in bf16 (memory knob), step
    math still fp32 — one step lands within bf16 rounding of the fp32-
    master run."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    params, grads = _params(), _grads()
    kw = dict(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0)

    def run_with(master_dtype):
        tx = distributed_fused_lamb(axis_name="dp",
                                    master_dtype=master_dtype, **kw)

        def run(params, grads):
            state = tx.init(params)
            assert state.master_shard["float32"].dtype == master_dtype
            assert state.mu_shard["float32"].dtype == master_dtype
            updates, _ = tx.update(grads, state, params)
            return updates

        return jax.jit(shard_map(run, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P()))(params, grads)

    full = run_with(jnp.float32)
    half = run_with(jnp.bfloat16)
    for k in params:
        # the dominant term is the one-time bf16 rounding of the master
        # COPY of the params (~eps_bf16 * |p|), which lands in the first
        # update verbatim; subsequent drift is much smaller
        np.testing.assert_allclose(
            np.asarray(half[k]), np.asarray(full[k]), rtol=2e-2,
            atol=1e-2, err_msg=k)


def test_bf16_reduce_scatter_close_to_fp32():
    """fp32_reduce_scatter=False reduces grads on the wire in their own
    dtype; with bf16 grads the update stays within bf16 tolerance."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    params = _params()
    grads16 = jax.tree_util.tree_map(
        lambda g: g.astype(jnp.bfloat16), _grads())
    kw = dict(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0)

    def run_with(fp32_rs):
        tx = distributed_fused_lamb(axis_name="dp",
                                    fp32_reduce_scatter=fp32_rs, **kw)

        def run(params, grads):
            state = tx.init(params)
            updates, _ = tx.update(grads, state, params)
            return updates

        return jax.jit(shard_map(run, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P()))(params, grads16)

    a = run_with(True)
    b = run_with(False)
    for k in params:
        np.testing.assert_allclose(np.asarray(b[k]), np.asarray(a[k]),
                                   rtol=2e-2, atol=2e-3, err_msg=k)
