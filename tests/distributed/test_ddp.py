"""DDP grad-sync tests on the 8-device virtual mesh (mirrors ref
tests/distributed/DDP/ddp_race_condition_test.py intent: synced grads must
equal single-process grads over the full batch)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from apex_tpu.parallel import (
    DistributedDataParallel, Reducer, sync_gradients, sync_gradients_flat)


def mesh8():
    devs = np.array(jax.devices()[:8])
    return Mesh(devs, ("data",))


def test_eight_devices_available():
    assert len(jax.devices()) >= 8


def test_replicated_params_grads_autoreduced_then_averaged():
    """jax>=0.8 shard_map: grad w.r.t. replicated params arrives psummed;
    DDP.average_reduced turns it into the global-batch-mean gradient."""
    from apex_tpu.parallel import average_reduced
    mesh = mesh8()
    w = jnp.ones((4, 1))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jax.random.normal(jax.random.PRNGKey(1), (16, 1))

    def local_loss(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    @jax.jit
    def ddp_grads(w, x, y):
        def shard_fn(w, x, y):
            g = jax.grad(local_loss)(w, x, y)  # already psummed over 'data'
            return average_reduced({"w": g}, axis_name="data")["w"]
        return shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=P())(w, x, y)

    g_ddp = ddp_grads(w, x, y)
    g_ref = jax.grad(local_loss)(w, x, y)
    np.testing.assert_allclose(np.asarray(g_ddp), np.asarray(g_ref), rtol=1e-5)


@pytest.mark.parametrize("flat", [False, True])
def test_synced_local_grads_equal_global_batch_grads(flat):
    """Per-replica grads (params made varying via pvary) + explicit DDP sync."""
    mesh = mesh8()
    w = jnp.ones((4, 1))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jax.random.normal(jax.random.PRNGKey(1), (16, 1))

    def local_loss(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    sync = sync_gradients_flat if flat else sync_gradients

    @jax.jit
    def ddp_grads(w, x, y):
        def shard_fn(w, x, y):
            w_local = jax.lax.pcast(w, ("data",), to="varying")  # per-replica copy
            g = jax.grad(local_loss)(w_local, x, y)
            g = sync({"w": g}, axis_name="data")["w"]
            return jax.lax.psum(g, "data") / jax.lax.axis_size("data")  # unvary for P() out

        return shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=P())(w, x, y)

    g_ddp = ddp_grads(w, x, y)
    g_ref = jax.grad(local_loss)(w, x, y)
    np.testing.assert_allclose(np.asarray(g_ddp), np.asarray(g_ref), rtol=1e-5)


def test_psum_without_average():
    mesh = mesh8()

    @jax.jit
    def run(x):
        def f(x):
            return sync_gradients({"g": x}, axis_name="data",
                                  gradient_average=False)["g"]
        return shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)

    x = jnp.ones((8, 2))
    out = run(x)
    np.testing.assert_allclose(np.asarray(out), 8.0 * np.ones((8, 2)))


def test_predivide_factor_matches_plain_mean():
    mesh = mesh8()
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 3))

    def run(pre):
        @jax.jit
        def go(x):
            def f(x):
                return sync_gradients({"g": x}, axis_name="data",
                                      gradient_predivide_factor=pre)["g"]
            return shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)
        return go(x)

    np.testing.assert_allclose(np.asarray(run(1.0)), np.asarray(run(4.0)), rtol=1e-5)


def test_predivide_factor_parity_across_sync_paths():
    """ISSUE 11 satellite: the flat and bucketed paths must apply
    gradient_predivide_factor exactly like sync_gradients (pre-divide
    before the psum, * factor/n after) — bit-identical across all
    three, any factor."""
    from apex_tpu.parallel import sync_gradients_bucketed

    mesh = mesh8()
    g = {"w": jax.random.normal(jax.random.PRNGKey(5), (8, 33, 3)),
         "b": jax.random.normal(jax.random.PRNGKey(6), (8, 17))}

    def run(pre):
        @jax.jit
        def go(g):
            def f(g):
                plain = sync_gradients(g, axis_name="data",
                                       gradient_predivide_factor=pre)
                flat = sync_gradients_flat(
                    g, axis_name="data", gradient_predivide_factor=pre)
                bucketed = sync_gradients_bucketed(
                    g, axis_name="data", bucket_cap_mb=0.0002,
                    gradient_predivide_factor=pre)
                return plain, flat, bucketed
            return shard_map(f, mesh=mesh, in_specs=P("data"),
                             out_specs=(P("data"),) * 3)(g)
        return go(g)

    for pre in (1.0, 4.0, 0.5):
        plain, flat, bucketed = run(pre)
        for k in g:
            np.testing.assert_array_equal(
                np.asarray(plain[k]), np.asarray(flat[k]),
                err_msg=f"flat pre={pre} {k}")
            np.testing.assert_array_equal(
                np.asarray(plain[k]), np.asarray(bucketed[k]),
                err_msg=f"bucketed pre={pre} {k}")


def test_ddp_wrapper_sync_and_delay():
    mesh = mesh8()
    ddp = DistributedDataParallel(axis_name="data")
    delayed = DistributedDataParallel(axis_name="data", delay_allreduce=True)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 2))

    @jax.jit
    def run(x):
        def f(x):
            synced = ddp.sync({"g": x})["g"]
            kept = delayed.sync({"g": x})["g"]   # no-op
            forced = delayed.allreduce({"g": x})["g"]
            return synced, kept, forced
        return shard_map(f, mesh=mesh, in_specs=P("data"),
                         out_specs=(P("data"), P("data"), P("data")))(x)

    synced, kept, forced = run(x)
    np.testing.assert_allclose(np.asarray(kept), np.asarray(x))
    np.testing.assert_allclose(np.asarray(synced), np.asarray(forced), rtol=1e-6)
    expect = np.broadcast_to(np.asarray(x).reshape(8, 1, 2).mean(0), (8, 1, 2)).reshape(8, 2)
    np.testing.assert_allclose(np.asarray(synced), expect, rtol=1e-5)


def test_ddp_always_fp32_reduction_preserves_dtype():
    mesh = mesh8()
    ddp = DistributedDataParallel(axis_name="data", allreduce_always_fp32=True)

    @jax.jit
    def run(x):
        def f(x):
            return ddp.sync({"g": x})["g"]
        return shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)

    x = jnp.ones((8, 2), jnp.bfloat16)
    out = run(x)
    assert out.dtype == jnp.bfloat16


def test_reducer():
    mesh = mesh8()
    red = Reducer(axis_name="data")

    @jax.jit
    def run(x):
        def f(x):
            return red.reduce({"p": x})["p"]
        return shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)

    x = jnp.arange(8.0).reshape(8, 1)
    out = run(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.5))


def test_shared_param_rejected():
    with pytest.raises(ValueError):
        DistributedDataParallel(shared_param=True)


def test_sync_autodiff_gradients_custom_vjp_mixed_tree():
    """Under check_vma a custom_vjp must return cotangents typed like its
    primals, so a rule written with ``cotangent_like`` hands back the
    replicated param's grad summed and invariant — the same kind plain-op
    autodiff produces. A param the caller made varying arrives per-device
    LOCAL. The vma-aware sync must land the identical global-batch-mean
    gradient for all three kinds, reducing none of them twice."""
    from apex_tpu.ops.vma import cotangent_like, to_varying
    from apex_tpu.parallel import sync_autodiff_gradients

    @jax.custom_vjp
    def myscale(x, w):
        return x * w

    def fwd(x, w):
        return x * w, (x, w)

    def bwd(res, g):
        x, w = res
        return g * w, cotangent_like(jnp.sum(g * x, axis=0), w)

    myscale.defvjp(fwd, bwd)

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    params = {"plain": jnp.arange(4.0), "cvjp": jnp.arange(4.0) + 1,
              "local": jnp.arange(4.0) + 2}
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))

    def loss(p, x):
        return jnp.mean((x * p["plain"]) ** 2 + myscale(x, p["cvjp"]) ** 2
                        + (x * p["local"]) ** 2)

    def shard_fn(p, x):
        p = dict(p, local=to_varying(p["local"], ("data",)))
        g = jax.grad(loss)(p, x)
        # the precondition this helper exists for: mixed vma tree
        assert "data" in jax.typeof(g["local"]).vma
        assert "data" not in jax.typeof(g["cvjp"]).vma
        assert "data" not in jax.typeof(g["plain"]).vma
        return sync_autodiff_gradients(g, axis_name="data")

    g_ddp = jax.jit(shard_map(
        shard_fn, mesh=mesh, in_specs=(P(), P("data")),
        out_specs=P()))(params, x)
    g_ref = jax.grad(loss)(params, x)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_ddp[k]),
                                   np.asarray(g_ref[k]), rtol=1e-5,
                                   err_msg=k)
