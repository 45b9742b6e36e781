"""torch.distributed-shaped backend over XLA collectives."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from apex_tpu import distributed as dist


def _run(fn, n=4, axis="dp", in_specs=None, out_specs=None):
    mesh = Mesh(np.array(jax.devices()[:n]), (axis,))
    return shard_map(fn, mesh=mesh,
                     in_specs=in_specs if in_specs is not None else P(axis),
                     out_specs=out_specs if out_specs is not None else P(axis))


def test_all_reduce_ops():
    x = jnp.arange(4.0).reshape(4, 1) + 1.0  # ranks hold 1, 2, 3, 4

    def sum_(v):
        return dist.all_reduce(v, dist.ReduceOp.SUM, "dp")[None]

    got = _run(lambda v: sum_(v[0]))(x)
    np.testing.assert_allclose(np.asarray(got), 10.0)

    got = _run(lambda v: dist.all_reduce(v[0], dist.ReduceOp.AVG, "dp")[None])(x)
    np.testing.assert_allclose(np.asarray(got), 2.5)
    got = _run(lambda v: dist.all_reduce(v[0], dist.ReduceOp.MAX, "dp")[None])(x)
    np.testing.assert_allclose(np.asarray(got), 4.0)
    got = _run(lambda v: dist.all_reduce(v[0], dist.ReduceOp.PRODUCT, "dp")[None])(x)
    np.testing.assert_allclose(np.asarray(got), 24.0, rtol=1e-5)


def test_gather_scatter_roundtrip():
    x = jnp.arange(8.0).reshape(4, 2)

    def f(v):
        full = dist.all_gather(v[0], "dp")          # [8]
        back = dist.reduce_scatter(full, "dp") / 4  # each rank its slice
        return back[None]

    got = _run(f)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x))


def test_broadcast():
    x = jnp.arange(4.0).reshape(4, 1) * 100

    def f(v):
        return dist.broadcast(v[0], src=2, group="dp")[None]

    got = _run(f)(x)
    np.testing.assert_allclose(np.asarray(got), 200.0)


def test_all_to_all():
    # each rank holds a row of 4 chunks; all_to_all transposes chunk owner
    x = jnp.arange(16.0).reshape(4, 4)

    def f(v):
        return dist.all_to_all(v, "dp", split_axis=1, concat_axis=0)

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    got = shard_map(f, mesh=mesh, in_specs=P("dp", None),
                    out_specs=P(None, "dp"))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x).T.reshape(4, 4).T
                               if False else np.asarray(x))


def test_host_init():
    dist.init_process_group()
    assert dist.is_initialized()
    assert dist.get_world_size() >= 1


def test_all_reduce_tuple_group():
    """Multi-axis groups must pvary over EVERY axis of the tuple (only
    varying the first tripped vma checking on psum over the pair)."""
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    x = jnp.arange(8.0).reshape(8, 1) + 1.0  # 1..8 over the 2x4 mesh

    def fn(v):
        return dist.all_reduce(v[0], dist.ReduceOp.SUM, ("dp", "tp"))[None]

    got = jax.jit(shard_map(fn, mesh=mesh, in_specs=P(("dp", "tp")),
                            out_specs=P(("dp", "tp"))))(x)
    np.testing.assert_allclose(np.asarray(got), 36.0)

    def avg(v):
        return dist.all_reduce(v[0], dist.ReduceOp.AVG, ("dp", "tp"))[None]

    got = jax.jit(shard_map(avg, mesh=mesh, in_specs=P(("dp", "tp")),
                            out_specs=P(("dp", "tp"))))(x)
    np.testing.assert_allclose(np.asarray(got), 4.5)


def test_broadcast_tuple_group():
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    x = jnp.arange(8.0).reshape(8, 1) + 1.0

    def fn(v):
        return dist.broadcast(v[0], src=5, group=("dp", "tp"))[None]

    got = jax.jit(shard_map(fn, mesh=mesh, in_specs=P(("dp", "tp")),
                            out_specs=P(("dp", "tp"))))(x)
    # composite rank 5 on the 2x4 mesh holds 6.0
    np.testing.assert_allclose(np.asarray(got), 6.0)
