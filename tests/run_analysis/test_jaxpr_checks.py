"""Engine 1 (jaxpr) unit tests: each check gets a true-positive snippet
it MUST flag and an idiomatic clean snippet it must NOT flag."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from apex_tpu.analysis import analyze_fn


def _by_check(findings, check):
    return [f for f in findings if f.check == check]


# -------------------------------------------------------------- donation

def _alias_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2


def _aliased_call(x):
    return pl.pallas_call(
        _alias_kernel,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        input_output_aliases={0: 0})(x)


def test_donation_race_flagged():
    def step(x):
        y = _aliased_call(x)
        return y + x  # x read AFTER the kernel aliased it into y

    found = _by_check(
        analyze_fn(step, jnp.ones((8, 128)), donate_argnums=(0,)),
        "donation")
    assert len(found) == 1 and found[0].severity == "error"
    assert "aliased into an output" in found[0].message


def test_donation_race_flagged_when_returned_as_output():
    """Returning the pre-alias value to the caller is the same clobber
    as an in-graph read after the aliasing kernel."""
    def step(x):
        y = _aliased_call(x)
        return y, x

    found = _by_check(
        analyze_fn(step, jnp.ones((8, 128)), donate_argnums=(0,)),
        "donation")
    assert len(found) == 1 and found[0].severity == "error"
    assert "returned as an output" in found[0].message


def test_donation_race_clean_when_no_later_read():
    def step(x):
        return _aliased_call(x)

    assert not analyze_fn(step, jnp.ones((8, 128)), donate_argnums=(0,))


def test_donation_unused_flagged():
    def step(x, g):
        return (x[:4] + g[:4],)  # no output matches the donated aval

    found = _by_check(
        analyze_fn(step, jnp.ones((8,)), jnp.ones((8,)),
                   donate_argnums=(0,)),
        "donation")
    assert len(found) == 1 and "wasted" in found[0].message


def test_donation_clean_on_fused_adam_step():
    """Idiomatic apex_tpu: donated params/state threading through the
    flat FusedAdam update (the ISSUE's first customer)."""
    from apex_tpu.optimizers import fused_adam

    params = {"w": jnp.zeros((32, 128), jnp.float32)}
    tx = fused_adam(lr=1e-3, flat=True)
    state = tx.init(params)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)

    def train_step(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return (jax.tree_util.tree_map(jnp.add, params, updates),
                opt_state)

    found = analyze_fn(train_step, params, state, grads,
                       donate_argnums=(0, 1))
    assert not _by_check(found, "donation"), found


# ------------------------------------------------------------- recompile

def test_recompile_weak_scalar_flagged():
    def step(x, lr):
        return x * lr

    found = _by_check(analyze_fn(step, jnp.ones((4,)), 1e-3), "recompile")
    assert len(found) == 1 and "weak-typed Python scalar" in found[0].message


def test_recompile_const_capture_flagged():
    table = jnp.arange(4096, dtype=jnp.float32)

    def step(x):
        return x + table[:4]

    found = _by_check(analyze_fn(step, jnp.ones((4,))), "recompile")
    assert len(found) == 1 and "closes over" in found[0].message


def test_recompile_clean_on_typed_args():
    def step(x, lr):
        return x * lr

    found = analyze_fn(step, jnp.ones((4,)),
                       jnp.asarray(1e-3, jnp.float32))
    assert not _by_check(found, "recompile"), found


# -------------------------------------------------------- collective-axis

CANONICAL = ("pp", "dp", "cp", "tp")


def _mesh(n, axis):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), (axis,))


def test_collective_axis_mismatch_flagged():
    mesh = _mesh(2, "model")  # not a parallel_state axis name
    fn = shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                   in_specs=P("model"), out_specs=P())
    found = _by_check(
        analyze_fn(fn, jnp.ones((16,)), mesh_axes=CANONICAL),
        "collective-axis")
    assert len(found) == 1 and "'model'" in found[0].message
    assert found[0].severity == "error"


def test_collective_ppermute_out_of_range_flagged():
    mesh = _mesh(2, "tp")
    fn = shard_map(lambda x: jax.lax.ppermute(x, "tp", [(0, 1), (1, 2)]),
                   mesh=mesh, in_specs=P("tp"), out_specs=P("tp"))
    found = _by_check(analyze_fn(fn, jnp.ones((16,)), mesh_axes=mesh),
                      "collective-axis")
    assert len(found) == 1 and "out-of-range" in found[0].message


def test_collective_clean_against_parallel_state_mesh():
    """Idiomatic wiring: psum over get_tensor_model_parallel_group()
    checked against the live mesh."""
    from apex_tpu.transformer import parallel_state

    parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=2)
    try:
        mesh = parallel_state.get_mesh()
        axis = parallel_state.get_tensor_model_parallel_group()
        fn = shard_map(lambda x: jax.lax.psum(x, axis), mesh=mesh,
                       in_specs=P(axis), out_specs=P())
        found = analyze_fn(fn, jnp.ones((16,)))  # mesh from parallel_state
        assert not _by_check(found, "collective-axis"), found
    finally:
        parallel_state.destroy_model_parallel()


# ----------------------------------------------------------- pallas-block

def _identity_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _block_call(x, block, grid=(2,)):
    return pl.pallas_call(
        _identity_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda *i: (0, 0))],
        out_specs=pl.BlockSpec(block, lambda *i: (0, 0)))(x)


def test_pallas_block_misalignment_flagged():
    found = _by_check(
        analyze_fn(lambda x: _block_call(x, (7, 100)),
                   jnp.ones((64, 300), jnp.float32)),
        "pallas-block")
    # in + out mapping, lane + sublane each -> 4 findings
    assert len(found) == 4
    assert any("128-lane" in f.message for f in found)
    assert any("multiple of 8" in f.message for f in found)


def test_pallas_block_bf16_sublane_multiple():
    # 8 rows is fine for f32 but NOT for bf16 (needs 16)
    found = _by_check(
        analyze_fn(lambda x: _block_call(x, (8, 128)),
                   jnp.ones((64, 128), jnp.bfloat16)),
        "pallas-block")
    assert len(found) == 2
    assert all("multiple of 16" in f.message for f in found)


def test_pallas_vmem_budget_flagged():
    found = _by_check(
        analyze_fn(
            lambda x: _block_call(x, (2048, 2048), grid=()),
            jnp.ones((2048, 2048), jnp.float32)),
        "pallas-block")
    assert len(found) == 1 and found[0].severity == "error"
    assert "VMEM" in found[0].message


def test_pallas_block_clean_on_layer_norm():
    """Idiomatic apex_tpu kernel: the shipped layer_norm BlockSpecs."""
    from apex_tpu.ops import pallas_config
    from apex_tpu.ops.layer_norm import layer_norm

    x = jnp.zeros((256, 1024), jnp.bfloat16)
    w = jnp.ones((1024,), jnp.float32)
    b = jnp.zeros((1024,), jnp.float32)
    with pallas_config.force("on"):
        found = analyze_fn(
            lambda x, w, b: layer_norm(x, w, b, (1024,)), x, w, b)
    assert not _by_check(found, "pallas-block"), found


# ------------------------------------------------------------- plumbing

def test_unknown_check_id_raises():
    with pytest.raises(ValueError, match="unknown jaxpr check"):
        analyze_fn(lambda x: x, jnp.ones(()), checks=("no-such-check",))
