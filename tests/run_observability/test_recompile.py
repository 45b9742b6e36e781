"""Recompile listener: a forced retrace under JAX_PLATFORMS=cpu is
counted per jitted function, flows into the registry, and trips the
budget guard (ISSUE 2 acceptance: "a test forces an extra retrace and
asserts the recompile counter catches it")."""

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.observability import (
    MetricRegistry,
    RetraceBudgetExceeded,
    install_recompile_listener,
    retrace_guard,
    uninstall_recompile_listener,
)
from apex_tpu.observability import recompile as recompile_mod


@pytest.fixture
def listener():
    reg = MetricRegistry()
    lst = install_recompile_listener(reg)
    yield lst
    uninstall_recompile_listener()


def test_forced_retrace_is_counted(listener):
    @jax.jit
    def obs_retrace_probe(x):
        return x * 2 + 1

    obs_retrace_probe(jnp.ones((4,)))
    base = listener.compiles("obs_retrace_probe")
    assert base >= 1  # first compile seen with its real name
    obs_retrace_probe(jnp.ones((5,)))  # new shape -> retrace
    obs_retrace_probe(jnp.ones((5,)))  # cache hit -> no compile
    assert listener.compiles("obs_retrace_probe") == base + 1
    assert listener.retraces("obs_retrace_probe") >= 1
    assert listener.total_retraces() >= 1


def test_counts_flow_into_registry(listener):
    @jax.jit
    def obs_registry_probe(x):
        return x + 1

    obs_registry_probe(jnp.ones((2,)))
    obs_registry_probe(jnp.ones((3,)))
    c = listener.registry.counter("jax/compiles", fn="obs_registry_probe")
    assert c.value == 2
    # monitoring totals feed the compile-seconds histogram
    h = listener.registry.histogram("jax/backend_compile_secs")
    assert h.count >= 2
    assert listener.backend_compiles() >= 2


def test_snapshot_shape(listener):
    @jax.jit
    def obs_snap_probe(x):
        return x - 1

    obs_snap_probe(jnp.ones((2,)))
    snap = listener.snapshot()
    assert snap["compiles_by_fn"].get("obs_snap_probe") == 1
    assert snap["backend_compiles"] >= 1
    assert snap["backend_compile_secs"] >= 0.0
    assert "retraces_by_fn" in snap


def test_retrace_guard_trips_over_budget(listener):
    @jax.jit
    def obs_guard_probe(x):
        return x * 3

    x4, x5, x6 = jnp.ones((4,)), jnp.ones((5,)), jnp.ones((6,))
    obs_guard_probe(x4)  # first compile, outside the guard
    with pytest.raises(RetraceBudgetExceeded) as ei:
        with retrace_guard(budget=0, fns=["obs_guard_probe"]):
            obs_guard_probe(x5)  # retrace inside -> over budget
    assert "obs_guard_probe" in str(ei.value)

    # budget=1 tolerates exactly one retrace
    with retrace_guard(budget=1, fns=["obs_guard_probe"]):
        obs_guard_probe(x6)

    # steady-state reuse does not spend budget
    with retrace_guard(budget=0, fns=["obs_guard_probe"]):
        obs_guard_probe(x6)
        obs_guard_probe(x6)


def test_guard_first_compile_is_free(listener):
    @jax.jit
    def obs_fresh_probe(x):
        return x / 2

    with retrace_guard(budget=0, fns=["obs_fresh_probe"]):
        obs_fresh_probe(jnp.ones((3,)))  # first-ever compile: free


def test_unnamed_compile_event_blinds_the_guard_loudly():
    """A jax that stops naming its compile events must not read as "zero
    retraces": every per-function read raises once one arrived unnamed."""
    lst = recompile_mod.RecompileListener(registry=MetricRegistry())
    lst._on_duration(recompile_mod._EV_COMPILE, 0.1, "jit(named)")
    assert lst.compiles("named") == 1
    lst._on_duration(recompile_mod._EV_COMPILE, 0.1, None)
    assert lst.backend_compiles() == 2
    for read in (lst.compiles, lst.retraces, lst.snapshot):
        with pytest.raises(RuntimeError, match="without a fun_name"):
            read()


def test_install_is_idempotent_and_uninstall_restores():
    reg = MetricRegistry()
    l1 = install_recompile_listener(reg)
    l2 = install_recompile_listener()
    assert l1 is l2
    assert recompile_mod.current() is l1
    uninstall_recompile_listener()
    assert recompile_mod.current() is None
    uninstall_recompile_listener()  # second uninstall is a no-op


def test_observer_error_counter_exact_under_contention():
    """Regression (unlocked-shared-mutation): ``observer_errors += 1``
    ran outside the listener's lock — concurrent compile notifications
    (jax's logging + monitoring hooks fire on whatever thread compiled)
    lost increments. The count must be exact."""
    import threading

    lst = recompile_mod.RecompileListener(registry=MetricRegistry())

    def bad_observer(kind, name):
        raise RuntimeError("observer blew up")

    lst.add_observer(bad_observer)
    n_threads, n_iters = 8, 200
    barrier = threading.Barrier(n_threads)

    def hammer():
        barrier.wait(timeout=30)
        for i in range(n_iters):
            lst._notify("compile", f"fn{i}")

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    assert lst.observer_errors == n_threads * n_iters


def test_observer_may_deregister_itself_during_notify():
    """The copy-then-invoke-outside-the-lock shape (the clean
    callback-reentry pattern): an observer re-entering
    remove_observer from inside the notification must not deadlock."""
    lst = recompile_mod.RecompileListener(registry=MetricRegistry())
    seen = []

    def once(kind, name):
        seen.append((kind, name))
        lst.remove_observer(once)

    lst.add_observer(once)
    lst._notify("compile", "fn_a")
    lst._notify("compile", "fn_b")  # already removed: no second fire
    assert seen == [("compile", "fn_a")]
    assert lst.observer_errors == 0
