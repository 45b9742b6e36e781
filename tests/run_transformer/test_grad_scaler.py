"""Model-parallel GradScaler (ref apex/transformer/amp/grad_scaler.py):
the overflow decision must agree across tp/pp ranks, and the dynamic
automaton honors asymmetric growth/backoff factors."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map

from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.transformer.amp import GradScaler


def test_asymmetric_backoff_factor():
    s = GradScaler(init_scale=2.0 ** 10, growth_factor=2.0,
                   backoff_factor=0.25, growth_interval=2000,
                   model_parallel_axes=())
    state = s.init()
    state = s.update(state, jnp.asarray(True))
    assert float(state.loss_scale) == 2.0 ** 10 * 0.25  # quarters, not halves
    state = s.update(state, jnp.asarray(False))
    assert float(state.loss_scale) == 2.0 ** 10 * 0.25  # window not reached


def test_default_backoff_is_inverse_growth():
    s = GradScaler(init_scale=2.0 ** 10, growth_factor=2.0,
                   model_parallel_axes=())
    state = s.update(s.init(), jnp.asarray(True))
    assert float(state.loss_scale) == 2.0 ** 9


def test_growth_after_interval():
    s = GradScaler(init_scale=2.0 ** 8, growth_factor=2.0,
                   growth_interval=3, model_parallel_axes=())
    state = s.init()
    for _ in range(3):
        state = s.update(state, jnp.asarray(False))
    assert float(state.loss_scale) == 2.0 ** 9


def test_overflow_synced_across_model_parallel_axes():
    """One tp rank overflowing must make every tp rank skip (ref
    grad_scaler.py MAX allreduce over get_model_parallel_group())."""
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "tp"))
    scaler = GradScaler(model_parallel_axes=("tp", "pp"))
    state = scaler.init()

    def shard_fn(grads):
        unscaled, overflow = scaler.unscale(grads, state)
        return overflow.astype(jnp.int32)[None]

    # only tp rank 1 has a non-finite grad
    grads = jnp.stack([jnp.ones((4,)),
                       jnp.full((4,), jnp.inf)]).reshape(2, 4)
    out = jax.jit(shard_map(
        lambda g: shard_fn({"w": g[0]}),
        mesh=mesh, in_specs=P("tp", None), out_specs=P("tp")))(grads)
    # both tp ranks report overflow after the pmax sync
    assert np.asarray(out).tolist() == [1, 1]


def test_unscale_divides_by_scale():
    s = GradScaler(init_scale=4.0, model_parallel_axes=())
    state = s.init()
    grads = {"w": jnp.full((3,), 8.0)}
    unscaled, overflow = s.unscale(grads, state)
    np.testing.assert_allclose(np.asarray(unscaled["w"]), 2.0)
    assert not bool(overflow)
