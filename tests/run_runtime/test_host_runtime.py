"""C++ host runtime tests: bucket planner, flat pack/unpack, prefetch ring,
the prefetch shutdown contract, and the bucketed DDP grad sync built on
the planner."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from apex_tpu.parallel.distributed import (
    sync_gradients,
    sync_gradients_bucketed,
)
from apex_tpu.runtime import (
    PrefetchLoader,
    bucket_offsets,
    flatten_into,
    plan_buckets,
    runtime_available,
    unflatten_from,
)


def test_native_library_loads():
    assert runtime_available(), "csrc/libapex_tpu_host.so missing — run make"


def test_plan_buckets_reverse_greedy():
    # reverse order fill: last tensors land in bucket 0
    sizes = [100, 200, 50, 400, 300]
    ids = plan_buckets(sizes, 500)
    assert ids[-1] == 0
    # caps respected
    offs, bsz = bucket_offsets(sizes, ids)
    for total in bsz:
        assert total <= 500
    # every tensor covered exactly once
    assert sorted(set(ids)) == list(range(max(ids) + 1))


def test_flatten_roundtrip_mixed_dtypes():
    rng = np.random.RandomState(0)
    arrs = [rng.randn(17).astype(np.float32),
            rng.randn(4, 5).astype(np.float64),
            rng.randint(0, 100, (7,)).astype(np.int32)]
    flat = np.zeros(sum(a.nbytes for a in arrs), np.uint8)
    flatten_into(arrs, flat)
    outs = [np.zeros_like(a) for a in arrs]
    unflatten_from(flat, outs)
    for a, b in zip(arrs, outs):
        np.testing.assert_array_equal(a, b)


def test_prefetch_loader_order_and_contents():
    seen = []

    def fill(i, out):
        out[:] = i * 10

    for batch in PrefetchLoader(fill, 12, (8,), np.float32, n_slots=3,
                                n_workers=3):
        seen.append(int(batch[0]))
    assert seen == [i * 10 for i in range(12)]


def test_prefetch_loader_error_propagates():
    def fill(i, out):
        if i == 3:
            raise ValueError("boom")
        out[:] = i

    with pytest.raises(RuntimeError):
        list(PrefetchLoader(fill, 6, (4,), np.float32, n_slots=2,
                            n_workers=2))


def test_bucketed_sync_matches_per_tensor():
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    grads = {
        "a": jax.random.normal(jax.random.PRNGKey(0), (33,)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (2, 17)),
        "c": jax.random.normal(jax.random.PRNGKey(2), (5, 5)).astype(
            jnp.bfloat16),
    }
    stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x] * 4), grads)

    def bucketed(g):
        return sync_gradients_bucketed(g, axis_name="data",
                                       bucket_cap_mb=0.0001)

    def plain(g):
        return sync_gradients(g, axis_name="data")

    got = shard_map(bucketed, mesh=mesh, in_specs=P("data"),
                    out_specs=P("data"))(stacked)
    want = shard_map(plain, mesh=mesh, in_specs=P("data"),
                     out_specs=P("data"))(stacked)
    for k in grads:
        np.testing.assert_allclose(
            np.asarray(got[k], np.float32), np.asarray(want[k], np.float32),
            rtol=1e-5, atol=1e-6)


# ----------------------------- shutdown/teardown chaos (ISSUE 16)

def _drain_generator(gen, n):
    out = []
    for _ in range(n):
        out.append(next(gen))
    return out


def test_native_abandoned_iterator_with_slow_fill_joins_workers():
    """Chaos: a slow fill callback is mid-flight when the consumer
    abandons the iterator. Closing the generator must stop + JOIN the
    C++ workers (deregistering the ring) before the callback object can
    die — without wedging on workers parked in the fill."""
    from apex_tpu.runtime import host

    def slow_fill(i, out):
        time.sleep(0.02)
        out[:] = i

    loader = PrefetchLoader(slow_fill, 64, (4,), np.float32,
                            n_slots=4, n_workers=3)
    assert loader._lib is not None  # native path under test
    gen = iter(loader)
    first = _drain_generator(gen, 1)[0]
    assert int(first[0]) == 0
    assert host._ACTIVE_RINGS  # ring live while iterating
    t0 = time.monotonic()
    gen.close()  # abandon: fills for batches 1..63 still queued
    assert time.monotonic() - t0 < 10.0
    assert not host._ACTIVE_RINGS  # stopped, joined, deregistered


def test_native_atexit_sweep_is_idempotent_and_unblocks_consumer():
    """The interpreter-exit sweep destroys abandoned rings; a consumer
    still iterating afterwards sees clean exhaustion (the C++ wait
    loop checks stop), and double-destroy is a no-op."""
    from apex_tpu.runtime import host

    def fill(i, out):
        out[:] = i

    loader = PrefetchLoader(fill, 32, (4,), np.float32,
                            n_slots=2, n_workers=2)
    gen = iter(loader)
    next(gen)
    assert len(host._ACTIVE_RINGS) == 1
    host._shutdown_rings()  # simulated interpreter-exit sweep
    host._shutdown_rings()  # idempotent
    assert not host._ACTIVE_RINGS
    # the consumer does not hang on a destroyed ring: the ring reports
    # exhaustion and the generator finishes (finally's destroy no-ops)
    assert list(gen) == []


def test_python_fallback_fill_exception_raises_instead_of_hanging(
        monkeypatch):
    """Regression: in the Python fallback a fill exception killed the
    worker silently and the consumer blocked on q.get() forever. The
    error sentinel must surface it as RuntimeError."""
    def fill(i, out):
        if i == 2:
            raise ValueError("boom")
        out[:] = i

    loader = PrefetchLoader(fill, 8, (4,), np.float32, n_slots=2,
                            n_workers=2)
    monkeypatch.setattr(loader, "_lib", None)  # force the fallback
    with pytest.raises(RuntimeError, match="prefetch fill"):
        list(loader)


def test_python_fallback_abandoned_iterator_joins_worker(monkeypatch):
    """Chaos: the fallback worker blocks on a full queue when the
    consumer walks away; the stop-aware put must let close() join it
    instead of leaking one fill thread per abandoned epoch."""
    def slow_fill(i, out):
        time.sleep(0.01)
        out[:] = i

    loader = PrefetchLoader(slow_fill, 128, (4,), np.float32,
                            n_slots=2, n_workers=1)
    monkeypatch.setattr(loader, "_lib", None)
    gen = iter(loader)
    next(gen)
    workers = [t for t in threading.enumerate()
               if t.name == "apex-prefetch-fill"]
    assert workers
    gen.close()
    for t in workers:
        t.join(timeout=10.0)
        assert not t.is_alive(), "fallback fill worker leaked"


def test_python_fallback_order_and_completion(monkeypatch):
    """The fallback path delivers every batch in order (the happy path
    the stop/drain machinery must not break)."""
    def fill(i, out):
        out[:] = i

    loader = PrefetchLoader(fill, 10, (4,), np.float32, n_slots=3,
                            n_workers=1)
    monkeypatch.setattr(loader, "_lib", None)
    got = [int(b[0]) for b in loader]
    assert got == list(range(10))


def test_load_is_race_free_on_concurrent_first_call():
    """Pinning test for the _load() double-checked lock (its
    blocking-call-under-lock suppression is justified BY this
    behavior): concurrent first-callers all get the same library
    object, without deadlock."""
    from apex_tpu.runtime import host

    results = []
    barrier = threading.Barrier(6)

    def race():
        barrier.wait(timeout=30)
        results.append(host._load())

    threads = [threading.Thread(target=race, daemon=True)
               for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    assert len(results) == 6
    assert len({id(r) for r in results}) == 1  # one shared lib (or None)
