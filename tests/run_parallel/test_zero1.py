"""ZeRO-1 sharded optimizer tier (ISSUE 11): bit-parity with the
replicated flat fused-adam (params AND optimizer state), comms pricing
at 0.75x the allreduce, and — via the PR 5 chaos harness — sharded
optimizer state surviving preempt + crash-restart bit-identically
through the atomic checkpoint path."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.optimizers import fused_adam
from apex_tpu.parallel import Zero1FusedAdam, sync_gradients

pytestmark = pytest.mark.multidevice

_KEY = jax.random.PRNGKey(0)


def mesh8():
    return Mesh(np.array(jax.devices()[:8]), ("dp",))


def _params():
    return {"w": jax.random.normal(_KEY, (37, 11), jnp.float32),
            "b": jax.random.normal(jax.random.fold_in(_KEY, 1), (13,),
                                   jnp.float32)}


def _both_steps(opt, tx, mesh, params, zstate, rstate, gl):
    """(zero1 params, zero1 state, replicated params, replicated
    state) after one step on per-rank grads ``gl``."""
    def f(p, zs, rs, g):
        new_p, new_zs = opt.step(g, zs, p)
        gavg = sync_gradients(g, axis_name="dp")
        upd, new_rs = tx.update(gavg, rs, p)
        rp = jax.tree_util.tree_map(jnp.add, p, upd)
        return new_p, new_zs, rp, new_rs

    zspecs = opt.state_specs(params)
    rspecs = jax.tree_util.tree_map(lambda _: P(), rstate)
    fn = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(), zspecs, rspecs, P("dp")),
        out_specs=(P(), zspecs, P(), rspecs), check_vma=False))
    return fn(params, zstate, rstate, gl)


def _local_grads(key, n=8):
    return {"w": jax.random.normal(jax.random.fold_in(key, 10),
                                   (n, 37, 11)),
            "b": jax.random.normal(jax.random.fold_in(key, 11),
                                   (n, 13))}


def test_zero1_bit_identical_to_replicated_fused_adam():
    """THE acceptance criterion: one ZeRO-1 step == one replicated
    flat fused-adam step, bitwise, params and optimizer state."""
    mesh = mesh8()
    params = _params()
    opt = Zero1FusedAdam(lr=1e-2, weight_decay=0.01, axis_name="dp",
                         num_shards=8, bucket_cap_mb=0.0005)
    tx = fused_adam(lr=1e-2, weight_decay=0.01, flat=True)
    zstate, rstate = opt.init(params), tx.init(params)

    for round_ in range(3):  # multi-step: moments accumulate
        gl = _local_grads(jax.random.fold_in(_KEY, 100 + round_))
        zp, zstate, rp, rstate = _both_steps(
            opt, tx, mesh, params, zstate, rstate, gl)
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(zp[k]), np.asarray(rp[k]),
                err_msg=f"params[{k}] step {round_}")
        params = zp

    assert int(zstate.count) == 3 == int(rstate.count)
    mu_t, nu_t = opt.unpack_state(params, zstate)
    from apex_tpu.ops.flat import flatten_tree, unflatten_tree

    meta = flatten_tree(params)[1]
    rmu = unflatten_tree(rstate.mu, meta)
    rnu = unflatten_tree(rstate.nu, meta)
    for k in params:
        np.testing.assert_array_equal(np.asarray(mu_t[k]),
                                      np.asarray(rmu[k]),
                                      err_msg=f"mu[{k}]")
        np.testing.assert_array_equal(np.asarray(nu_t[k]),
                                      np.asarray(rnu[k]),
                                      err_msg=f"nu[{k}]")


def test_zero1_state_is_sharded_and_smaller():
    """The point of ZeRO-1: each rank's moment shard is 1/n of the
    replicated buffer (padded), and the global buffers reassemble in
    element order."""
    params = _params()
    opt = Zero1FusedAdam(axis_name="dp", num_shards=8)
    state = opt.init(params)
    n_el = sum(l.size for l in jax.tree_util.tree_leaves(params))
    total = sum(m.size for m in state.mu)
    assert total >= n_el and total % 8 == 0
    assert total - n_el < 8 * len(state.mu)  # padding bounded


def test_zero1_bf16_params_fp32_reduce():
    """bf16 storage + fp32 grads: params update and gather in bf16 (the
    0.75x layout), the moments stay fp32."""
    mesh = mesh8()
    params = {"w": jax.random.normal(_KEY, (24, 16)).astype(jnp.bfloat16)}
    opt = Zero1FusedAdam(lr=1e-2, axis_name="dp", num_shards=8)
    state = opt.init(params)
    gl = {"w": jax.random.normal(jax.random.fold_in(_KEY, 2),
                                 (8, 24, 16), jnp.float32)}
    zspecs = opt.state_specs(params)

    def f(p, zs, g):
        return opt.step(g, zs, p)

    fn = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(), zspecs, P("dp")),
        out_specs=(P(), zspecs), check_vma=False))
    new_p, new_state = fn(params, state, gl)
    assert new_p["w"].dtype == jnp.bfloat16
    assert all(m.dtype == jnp.float32 for m in new_state.mu)
    assert not np.array_equal(np.asarray(new_p["w"], np.float32),
                              np.asarray(params["w"], np.float32))
    # comms pricing of this layout: exactly 0.75x the allreduce
    from apex_tpu.parallel import grad_sync_comms_bytes

    assert opt.comms_bytes(params) * 4 == \
        grad_sync_comms_bytes(params, 8, "allreduce") * 3


def test_num_shards_mismatch_is_loud():
    mesh = mesh8()
    # 512-element tree so the wrong-quantum state still splits over the
    # 8-way mesh — the step's own num_shards check must fire, not the
    # shard_map divisibility error
    params = {"w": jnp.ones((32, 16), jnp.float32)}
    opt = Zero1FusedAdam(axis_name="dp", num_shards=4)  # wrong: axis is 8
    state = opt.init(params)
    gl = {"w": jnp.ones((8, 32, 16), jnp.float32)}
    with pytest.raises(ValueError, match="num_shards"):
        specs = opt.state_specs(params)
        jax.jit(shard_map(
            lambda p, zs, g: opt.step(g, zs, p), mesh=mesh,
            in_specs=(P(), specs, P("dp")),
            out_specs=(P(), specs),
            check_vma=False))(params, state, gl)


def test_unpack_state_rejects_diverged_plan():
    params = _params()
    opt = Zero1FusedAdam(axis_name="dp", num_shards=8)
    state = opt.init(params)
    bad = state._replace(mu=state.mu + (state.mu[0],))
    with pytest.raises(ValueError, match="diverged"):
        opt.unpack_state(params, bad)


# -------------------------------------- resilience: sharded state +
# atomic checkpoints (the PR 5 chaos harness)

_CHAOS_OPT = Zero1FusedAdam(lr=5e-2, weight_decay=0.01, axis_name="dp",
                            num_shards=8, bucket_cap_mb=0.0005)


@functools.lru_cache(maxsize=1)
def _chaos_step_fn():
    mesh = mesh8()
    zspecs = _CHAOS_OPT.state_specs(_params())

    def f(p, zs, g):
        return _CHAOS_OPT.step(g, zs, p)

    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(), zspecs, P("dp")),
        out_specs=(P(), zspecs), check_vma=False))


def _chaos_init():
    params = _params()
    return {"params": params, "opt": _CHAOS_OPT.init(params)}


def _chaos_step(state, step):
    gl = _local_grads(jax.random.fold_in(_KEY, 1000 + step))
    new_p, new_opt = _chaos_step_fn()(state["params"], state["opt"], gl)
    loss = sum(jnp.sum(p.astype(jnp.float32) ** 2)
               for p in jax.tree_util.tree_leaves(new_p))
    return {"params": new_p, "opt": new_opt}, {"loss": loss}


def _assert_bit_identical(a, b):
    la, lb = (jax.tree_util.tree_leaves(t) for t in (a, b))
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert jnp.asarray(x).dtype == jnp.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sharded_state_survives_preempt_crash_restart(tmp_path):
    """Sharded optimizer state rides the atomic checkpoint manifest:
    preempt mid-run, crash-restart with a fresh loop, and the resumed
    run must land bit-identical params AND moment shards vs an
    uninterrupted run."""
    from apex_tpu.resilience import (
        FaultPlan,
        Preempted,
        ResilientTrainLoop,
    )

    clean = ResilientTrainLoop(
        _chaos_step, directory=str(tmp_path / "clean"),
        save_every=3).run(_chaos_init(), 7)

    chaos_dir = str(tmp_path / "chaos")
    with pytest.raises(Preempted) as ei:
        ResilientTrainLoop(
            _chaos_step, directory=chaos_dir, save_every=3,
            fault_plan=FaultPlan.parse("preempt@4")).run(
            _chaos_init(), 7)
    assert ei.value.step == 4

    final = ResilientTrainLoop(
        _chaos_step, directory=chaos_dir, save_every=3,
        fault_plan=FaultPlan.parse("preempt@4")).run(_chaos_init(), 7)
    _assert_bit_identical(clean, final)
    assert int(final["opt"].count) == 7
    # the moments actually accumulated through the restart
    assert all(float(jnp.max(jnp.abs(m))) > 0 for m in final["opt"].mu)


def test_sharded_state_survives_torn_emergency_save(tmp_path):
    """The emergency save at the preemption step is itself torn: the
    restart must fall back to the previous VALID step, replay, and
    still reach bit-identical sharded state."""
    from apex_tpu.resilience import (
        FaultPlan,
        Preempted,
        ResilientTrainLoop,
    )

    clean = ResilientTrainLoop(
        _chaos_step, directory=str(tmp_path / "clean"),
        save_every=2).run(_chaos_init(), 7)

    chaos_dir = str(tmp_path / "chaos")
    with pytest.raises(Preempted) as ei:
        ResilientTrainLoop(
            _chaos_step, directory=chaos_dir, save_every=2,
            fault_plan=FaultPlan.parse("preempt@5,ckpt_torn@5")).run(
            _chaos_init(), 7)
    assert ei.value.checkpoint_path is None  # emergency save torn

    loop2 = ResilientTrainLoop(
        _chaos_step, directory=chaos_dir, save_every=2,
        fault_plan=FaultPlan.parse("ckpt_torn@5"))
    final = loop2.run(_chaos_init(), 7)
    assert loop2.resumed_from == 4
    _assert_bit_identical(clean, final)
