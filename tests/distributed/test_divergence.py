"""Replica-divergence detection (the SPMD analog of race detection)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from apex_tpu.distributed import (
    DivergenceMonitor,
    assert_replicas_equal,
    replica_divergence,
)


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:8]), ("dp",))


def _tree(key):
    a = jax.random.normal(key, (8, 16))
    b = jax.random.normal(jax.random.fold_in(key, 1), (32,))
    return {"a": a, "b": b}


class TestReplicaDivergence:
    def test_identical_replicas_zero(self, mesh):
        tree = _tree(jax.random.PRNGKey(0))

        def fn(tree):
            return replica_divergence(tree, "dp")

        div = jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(jax.tree_util.tree_map(
                lambda _: P(), tree),), out_specs=P()))(tree)
        assert float(div) == 0.0

    def test_single_rank_drift_detected(self, mesh):
        tree = _tree(jax.random.PRNGKey(0))
        # per-rank input sharded over dp so we can poison one rank
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (8,) + a.shape).copy(), tree)
        # rank 3's copy drifts by 1 ulp-ish in one element
        stacked["a"] = stacked["a"].at[3, 0, 0].add(1e-3)

        def fn(stacked):
            local = jax.tree_util.tree_map(lambda a: a[0], stacked)
            ok, div = assert_replicas_equal(local, "dp")
            return ok, div

        ok, div = jax.jit(shard_map(
            fn, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(
                lambda _: P("dp"), stacked),),
            out_specs=(P(), P())))(stacked)
        assert not bool(ok)
        assert float(div) > 0.0

    def test_permutation_detected(self, mesh):
        """Same multiset of values, different order — a plain sum digest
        would miss it; the position-weighted fingerprint must not."""
        base = jnp.arange(32, dtype=jnp.float32)
        stacked = jnp.broadcast_to(base, (8, 32)).copy()
        stacked = stacked.at[5].set(base[::-1])

        def fn(stacked):
            ok, div = assert_replicas_equal({"x": stacked[0]}, "dp")
            return ok

        ok = jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(P("dp"),), out_specs=P()))(stacked)
        assert not bool(ok)


class TestDivergenceMonitor:
    def test_periodic_latching(self, mesh):
        mon = DivergenceMonitor(every=2)
        tree = _tree(jax.random.PRNGKey(0))
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (8,) + a.shape).copy(), tree)

        def step(state, stacked):
            local = jax.tree_util.tree_map(lambda a: a[0], stacked)
            return mon.update(state, local, "dp")

        sm = jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(), mon.init()),
                      jax.tree_util.tree_map(lambda _: P("dp"), stacked)),
            out_specs=jax.tree_util.tree_map(lambda _: P(), mon.init())))

        state = mon.init()
        for _ in range(4):  # steps 1..4 -> checks at 2 and 4
            state = sm(state, stacked)
        assert int(state.checks) == 2
        assert not bool(state.diverged)

        poisoned = dict(stacked)
        poisoned["a"] = stacked["a"].at[2, 0, 0].add(0.5)
        for _ in range(2):  # one more check window
            state = sm(state, poisoned)
        assert bool(state.diverged)
        assert float(state.max_divergence) > 0.0
        # latch persists even after the tree heals
        for _ in range(2):
            state = sm(state, stacked)
        assert bool(state.diverged)
