"""Overlapped DDP comms engine (ISSUE 11 tentpole) on the 8-device
simulated mesh: the barrier-chained bucket allreduce and the
custom_vjp-hook backward-interleaved variant must both be BIT-identical
to the single-psum ``sync_gradients``, the plan must follow grad-ready
(reverse) order, and the shared multi-device subprocess harness must
run real collectives in a fresh interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel import (
    DistributedDataParallel,
    grad_sync_comms_bytes,
    overlapped_value_and_grad,
    plan_overlap,
    sync_gradients,
    sync_gradients_overlapped,
)

pytestmark = pytest.mark.multidevice


def mesh8():
    return Mesh(np.array(jax.devices()[:8]), ("dp",))


def _per_rank_grads(key):
    """A 3-leaf grad tree with a distinct value per rank (leading dim 8
    sharded over dp)."""
    mk = lambda k, shape: jax.random.normal(
        jax.random.fold_in(key, k), (8,) + shape)
    return {"a": mk(0, (33, 7)), "b": mk(1, (129,)), "c": mk(2, (5, 6))}


# ------------------------------------------------------------- planning

def test_plan_overlap_grad_ready_order():
    """Reverse-order greedy: bucket 0 holds the LAST leaves (first
    grads the backward completes), caps respected, indices contiguous
    ascending within a bucket."""
    tree = {f"p{i:02d}": jnp.zeros((256,), jnp.float32)
            for i in range(8)}  # 1 KiB leaves, tree order p00..p07
    plan = plan_overlap(tree, bucket_cap_mb=2 / 1024)  # 2 KiB cap
    assert len(plan.buckets) == 4
    # grad-ready order: first bucket covers the tail of the leaf list
    assert plan.buckets[0].indices == (6, 7)
    assert plan.buckets[-1].indices == (0, 1)
    covered = [i for b in plan.buckets for i in b.indices]
    assert sorted(covered) == list(range(8))


def test_plan_overlap_groups_per_dtype_and_pads():
    tree = {"w": jnp.zeros((100,), jnp.float32),
            "h": jnp.zeros((50,), jnp.bfloat16)}
    plan = plan_overlap(tree, bucket_cap_mb=10.0, num_shards=8)
    dtypes = {b.dtype for b in plan.buckets}
    assert dtypes == {"float32", "bfloat16"}
    for b in plan.buckets:
        assert b.padded % 8 == 0 and b.padded >= b.total


def test_plan_mismatch_is_loud():
    plan = plan_overlap({"a": jnp.zeros((4,))})
    with pytest.raises(ValueError, match="diverged"):
        sync_gradients_overlapped({"a": jnp.zeros((4,)),
                                   "b": jnp.zeros((2,))},
                                  axis_name="dp", plan=plan)


# ------------------------------------------------- bit-parity contracts

@pytest.mark.parametrize("pre,average", [(1.0, True), (4.0, True),
                                         (1.0, False)])
def test_overlapped_sync_bit_identical_to_single_psum(pre, average):
    mesh = mesh8()
    grads = _per_rank_grads(jax.random.PRNGKey(0))

    @jax.jit
    def run(g):
        def f(g):
            ref = sync_gradients(g, axis_name="dp",
                                 gradient_average=average,
                                 gradient_predivide_factor=pre)
            ov = sync_gradients_overlapped(
                g, axis_name="dp", gradient_average=average,
                gradient_predivide_factor=pre, bucket_cap_mb=0.0005)
            return ref, ov
        return shard_map(f, mesh=mesh, in_specs=P("dp"),
                         out_specs=(P("dp"), P("dp")))(g)

    ref, ov = run(grads)
    for k in grads:
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(ov[k]), err_msg=k)


def test_single_bucket_degenerates_to_flat_psum():
    """A cap larger than the tree = one bucket; still bit-identical."""
    mesh = mesh8()
    grads = _per_rank_grads(jax.random.PRNGKey(3))

    @jax.jit
    def run(g):
        def f(g):
            return (sync_gradients(g, axis_name="dp"),
                    sync_gradients_overlapped(g, axis_name="dp",
                                              bucket_cap_mb=100.0))
        return shard_map(f, mesh=mesh, in_specs=P("dp"),
                         out_specs=(P("dp"), P("dp")))(g)

    ref, ov = run(grads)
    for k in grads:
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(ov[k]), err_msg=k)


def test_overlapped_value_and_grad_backward_hooks():
    """The custom_vjp-hook variant: grads come back already reduced,
    bit-identical to jax.grad + sync_gradients."""
    mesh = mesh8()
    key = jax.random.PRNGKey(1)
    params = {"w1": jax.random.normal(key, (16, 16)),
              "w2": jax.random.normal(jax.random.fold_in(key, 1),
                                      (16, 4)),
              "b": jax.random.normal(jax.random.fold_in(key, 2), (4,))}
    x = jax.random.normal(jax.random.fold_in(key, 3), (32, 16))
    y = jax.random.normal(jax.random.fold_in(key, 4), (32, 4))

    def loss(p, x, y):
        h = jnp.tanh(x @ p["w1"])
        return jnp.mean((h @ p["w2"] + p["b"] - y) ** 2)

    @jax.jit
    def run(p, x, y):
        def f(p, x, y):
            loss_ov, g_ov = overlapped_value_and_grad(
                loss, axis_name="dp", bucket_cap_mb=0.0005)(p, x, y)
            loss_ref, g_ref = jax.value_and_grad(loss)(p, x, y)
            g_ref = sync_gradients(g_ref, axis_name="dp")
            return loss_ov, g_ov, g_ref
        return shard_map(f, mesh=mesh,
                         in_specs=(P(), P("dp"), P("dp")),
                         out_specs=(P(), P(), P()),
                         check_vma=False)(p, x, y)

    loss_ov, g_ov, g_ref = run(params, x, y)
    assert np.isfinite(float(loss_ov))
    for k in params:
        np.testing.assert_array_equal(np.asarray(g_ov[k]),
                                      np.asarray(g_ref[k]), err_msg=k)


def test_ddp_wrapper_overlap_mode():
    """DistributedDataParallel(overlap_buckets=True) routes sync
    through the overlapped engine — same result as the plain wrapper."""
    mesh = mesh8()
    plain = DistributedDataParallel(axis_name="dp", flat_buckets=False)
    over = DistributedDataParallel(axis_name="dp", overlap_buckets=True,
                                   bucket_cap_mb=0.0005)
    x = jax.random.normal(jax.random.PRNGKey(7), (8, 24))

    @jax.jit
    def run(x):
        def f(x):
            return plain.sync({"g": x})["g"], over.sync({"g": x})["g"]
        return shard_map(f, mesh=mesh, in_specs=P("dp"),
                         out_specs=(P("dp"), P("dp")))(x)

    a, b = run(x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------- comms pricing

def test_grad_sync_comms_bytes_zero1_ratio():
    """bf16 params + fp32 grads: the ZeRO-1 layout is exactly 0.75x
    the allreduce bytes (the ISSUE acceptance ratio)."""
    tree = {"w": jnp.zeros((512, 256), jnp.bfloat16),
            "b": jnp.zeros((256,), jnp.bfloat16)}
    ar = grad_sync_comms_bytes(tree, 8, "allreduce")
    z1 = grad_sync_comms_bytes(tree, 8, "zero1")
    assert ar > 0
    assert z1 * 4 == ar * 3  # exactly 0.75x
    # fp32 params: reduce-scatter+gather moves the same bytes
    tree32 = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.float32), tree)
    assert grad_sync_comms_bytes(tree32, 8, "zero1") == \
        grad_sync_comms_bytes(tree32, 8, "allreduce")
    # single device: no comms at all
    assert grad_sync_comms_bytes(tree, 1, "zero1") == 0
    with pytest.raises(ValueError, match="unknown grad-sync mode"):
        grad_sync_comms_bytes(tree, 8, "broadcast")


# ---------------------------------------------- the subprocess harness

def test_simulated_mesh_subprocess_runs_real_collectives(
        simulated_mesh_subprocess):
    """The shared fixture must hand a FRESH interpreter 8 simulated
    devices and the overlapped engine must reduce across all of them
    (the proving ground for environments where the in-process forcing
    never happened)."""
    code = """
import apex_tpu
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from apex_tpu.parallel import sync_gradients_overlapped
assert jax.device_count() == 8, jax.device_count()
mesh = Mesh(np.array(jax.devices()), ("dp",))
x = jnp.arange(8.0 * 3).reshape(8, 3)

def f(x):
    return sync_gradients_overlapped({"g": x}, axis_name="dp",
                                     gradient_average=False)["g"]

out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"),
                        out_specs=P("dp")))(x)
expect = np.broadcast_to(np.arange(24.0).reshape(8, 3).sum(0), (8, 3))
np.testing.assert_allclose(np.asarray(out), expect)
print("SIMULATED_MESH_OK", jax.device_count())
"""
    proc = simulated_mesh_subprocess(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SIMULATED_MESH_OK 8" in proc.stdout
