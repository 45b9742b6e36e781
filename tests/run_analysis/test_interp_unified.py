"""ISSUE 8 prerequisite regression: the unified multi-lattice walk
(analysis/interp.py) must produce IDENTICAL abstract values and visit
streams to the single-engine entry points, whether a lattice runs alone
or shares the traversal with the other engine — on programs covering
every structural primitive the walk special-cases (pjit, scan, while,
cond, shard_map, dot_general)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.analysis import interp
from apex_tpu.analysis.dataflow import (
    PRECISION_LATTICE,
    AbsVal,
    interpret,
)
from apex_tpu.analysis.sharding_flow import (
    SHARDING_LATTICE,
    ShardVal,
    estimate_hbm_and_comms,
    interpret_sharding,
    normalize_spec,
    shard_val_for_aval,
)

SIZES = {"dp": 2, "tp": 2}


def _mixed_fn():
    """scan + cond + pjit'd matmul + cast chains in one program."""
    w = jnp.zeros((8, 8), jnp.float32)
    x = jnp.zeros((4, 8), jnp.float32)

    @jax.jit
    def inner(x, w):
        return (x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)
                ).astype(jnp.float32)

    def fn(w, x):
        def body(carry, xi):
            carry = carry + jnp.sum(xi.astype(jnp.float32))
            return carry, xi * 2

        total, ys = jax.lax.scan(body, jnp.float32(0), x)
        y = inner(x, w)

        def while_body(c):
            i, v = c
            return i + 1, v * 0.5

        _, damped = jax.lax.while_loop(
            lambda c: c[0] < 3, while_body, (0, total))
        z = jax.lax.cond(damped > 0, lambda a: a + 1.0,
                         lambda a: a - 1.0, damped)
        return y, z + jnp.sum(ys)

    return jax.make_jaxpr(fn)(w, x), (w, x)


def _shard_map_fn():
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("dp", "tp"))
    x = jnp.zeros((8, 8), jnp.float32)

    def smfn(x):
        return jax.lax.psum(x * 2.0, "tp")

    f = jax.shard_map(smfn, mesh=mesh, in_specs=P("tp"), out_specs=P())
    return jax.make_jaxpr(f)(x), (x,)


def _events(stream):
    return [(prim, tuple(ins), tuple(outs)) for prim, ins, outs in
            stream]


def _run_both(closed, p_vals, s_vals):
    """(single-engine results, combined-walk results): per-engine
    outputs + visit streams."""
    p_stream, s_stream = [], []
    p_outs = interpret(
        closed, p_vals,
        visit=lambda eqn, ins, outs: p_stream.append(
            (eqn.primitive.name, ins, outs)))
    s_outs = interpret_sharding(
        closed, s_vals, axis_sizes=SIZES,
        visit=lambda eqn, ins, outs, ctx: s_stream.append(
            (eqn.primitive.name, ins, outs)))

    pc_stream, sc_stream = [], []
    pc_outs, sc_outs = interp.interpret_lattices(
        closed,
        [interp.LatticeRun(
            PRECISION_LATTICE, p_vals,
            lambda eqn, ins, outs, ctx: pc_stream.append(
                (eqn.primitive.name, ins, outs))),
         interp.LatticeRun(
             SHARDING_LATTICE, s_vals,
             lambda eqn, ins, outs, ctx: sc_stream.append(
                 (eqn.primitive.name, ins, outs)))],
        axis_sizes=SIZES)
    return (p_outs, p_stream, s_outs, s_stream,
            pc_outs, pc_stream, sc_outs, sc_stream)


def _assert_identical(closed, p_vals, s_vals):
    (p_outs, p_stream, s_outs, s_stream,
     pc_outs, pc_stream, sc_outs, sc_stream) = _run_both(
        closed, p_vals, s_vals)
    assert pc_outs == p_outs
    assert sc_outs == s_outs
    assert _events(pc_stream) == _events(p_stream)
    assert _events(sc_stream) == _events(s_stream)
    assert p_stream, "visit stream must not be empty"


def test_combined_walk_matches_single_engines_on_mixed_program():
    closed, args = _mixed_fn()
    p_vals = [AbsVal(dtype=str(a.dtype), origin=str(a.dtype),
                     taints=frozenset({"grad"}) if i == 0 else
                     frozenset())
              for i, a in enumerate(args)]
    s_vals = [shard_val_for_aval(jax.typeof(a),
                                 P("tp", None) if i == 0 else
                                 P("dp", None))
              for i, a in enumerate(args)]
    _assert_identical(closed, p_vals, s_vals)


def test_combined_walk_matches_single_engines_through_shard_map():
    closed, args = _shard_map_fn()
    p_vals = [None for _ in args]
    s_vals = [shard_val_for_aval(jax.typeof(a), P("tp", None))
              for a in args]
    _assert_identical(closed, p_vals, s_vals)


def test_precision_only_walk_skips_warm_pass_values():
    """A precision-only run must see the exact one-pass values the old
    engine produced (no carry join may leak in)."""
    closed, args = _mixed_fn()
    outs = interpret(closed, [None, None])
    assert all(isinstance(o, AbsVal) for o in outs)
    # bf16 matmul upcast back to f32: origin stays the input's f32
    assert outs[0].dtype == "float32"


def test_estimate_linearization_cache_is_pure():
    """estimate_hbm_and_comms memoizes the linearization per jaxpr; a
    second call (same or different in_vals) must not be perturbed by
    the first."""
    closed, args = _mixed_fn()
    aval = jax.typeof(args[0])
    sharded = [shard_val_for_aval(jax.typeof(a), P("tp", None))
               for a in args]
    replicated = [shard_val_for_aval(jax.typeof(a), P())
                  for a in args]
    first = estimate_hbm_and_comms(closed, sharded, axis_sizes=SIZES)
    again = estimate_hbm_and_comms(closed, sharded, axis_sizes=SIZES)
    assert first == again
    other = estimate_hbm_and_comms(closed, replicated, axis_sizes=SIZES)
    # replicated inputs cannot be cheaper than tp-sharded ones
    assert other["input_bytes"] >= first["input_bytes"]


def test_lattice_run_defaults_derive_from_avals():
    closed, _args = _mixed_fn()
    (outs,) = interp.interpret_lattices(
        closed, [interp.LatticeRun(SHARDING_LATTICE)])
    assert all(isinstance(o, ShardVal) for o in outs)
    ndim = len(closed.jaxpr.outvars[0].aval.shape)
    assert outs[0].spec == normalize_spec(None, ndim)
