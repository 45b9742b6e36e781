"""Guardrails for bench.py's timing methodology (timed regions that end
in a blocking sync, on-device scan loops). These run on the CPU mesh; the
magnitudes they assert are loose — the point is that the machinery
returns sane, positive, finite numbers and the scan really iterates."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench  # repo root is on sys.path via tests/conftest.py


def test_sync_blocks_on_every_leaf_and_returns_the_tree():
    """sync is block_until_ready on the whole pytree: every leaf is
    ready afterwards, the tree comes back for chaining, and an empty
    pytree is a no-op, not an IndexError."""
    from apex_tpu.runtime import timing

    out = (jnp.zeros((2, 2)) + 1, {"a": jnp.full((3,), 5.0) * 2})
    back = bench._sync(out)
    assert back is out
    assert all(leaf.is_ready() for leaf in jax.tree_util.tree_leaves(back))
    assert timing.sync(()) == ()
    assert timing.sync({}) == {}


def test_time_fn_measures_wall():
    def slow():
        time.sleep(0.02)
        return jnp.zeros(())

    t = bench.time_fn(slow, iters=3, warmup=1)
    assert 0.015 < t < 0.2


def test_time_fn_max_time_caps_iters():
    calls = []

    def slow():
        calls.append(1)
        time.sleep(0.03)
        return jnp.zeros(())

    bench.time_fn(slow, iters=50, warmup=1, max_time_s=0.1)
    # warmup (1) + timed iters capped to ~0.1/0.03 = 3
    assert len(calls) <= 6


def test_time_scanned_per_iteration_magnitude():
    """time_scanned's per-iteration figure must match a directly-timed
    single iteration of the same op — a regression in the scan length or
    the (reps-1)*k divisor shifts the result by a factor of k and fails
    this band."""
    k = 8
    x = jnp.ones((768, 768), jnp.float32)

    def make_step():
        return lambda c: (c @ c) * 1e-6  # heavy enough to time on CPU

    # direct single-iteration time (compile + settle first)
    f = jax.jit(make_step())
    y = f(x)
    bench._sync(y)
    t0 = time.perf_counter()
    for _ in range(4):
        y = f(y)
    bench._sync(y)
    t_direct = (time.perf_counter() - t0) / 4

    t_scan = bench.time_scanned(make_step, x, lambda c, s: s(c), k=k,
                                reps=3)
    assert np.isfinite(t_scan) and t_scan > 0
    assert 0.25 * t_direct < t_scan < 4.0 * t_direct, (t_scan, t_direct)


def test_time_scanned_tuple_carry():
    def make_step():
        return lambda a, b: a + b

    def chain(c, step):
        return step(*c), c[1]

    t = bench.time_scanned(make_step,
                           (jnp.zeros((4,)), jnp.ones((4,))),
                           chain, k=4, reps=2)
    assert t >= 0.0 and np.isfinite(t)


def test_peak_flops_table():
    assert bench._peak_flops("TPU v5 lite") == 197e12
    assert bench._peak_flops("TPU v4") == 275e12
    assert bench._peak_flops("unknown accelerator") is None
