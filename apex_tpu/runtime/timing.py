"""Device timing — the shared helpers every timed region goes through.

JAX dispatch is asynchronous: a call returns once the work is enqueued
(~0.2 ms for a program that then runs 6 ms on a v5e), so a wall-clock
interval measures device work only when it ends in a blocking call on the
outputs. :func:`sync` is that call — ``jax.block_until_ready`` on every
leaf. On the PJRT TPU client it waits for the device: measured on a
TPU v5 lite (jax 0.9.0, libtpu 0.0.34) one 8192³ bf16 matmul takes
6.63 ms timed with it against 6.96 ms timed with a one-element host
fetch, and ten chained calls 5.97 against 6.02 ms each.

This module is the one place timed regions are built. bench.py and
tools/ import from here, and the ``sync-timing`` check in
``apex_tpu.analysis`` flags code that pairs a clock with its own
``block_until_ready`` instead.

jax is imported lazily inside each function: bench.py's launcher half
must stay importable without touching the backend.
"""

from __future__ import annotations

import time

__all__ = [
    "sync", "time_fn", "time_train_step", "time_chained", "time_scanned",
]


def sync(out):
    """Wait until every array in the pytree ``out`` has been computed,
    and return ``out``. The end of every timed region."""
    import jax

    return jax.block_until_ready(out)


def time_fn(fn, *args, iters=20, warmup=3, max_time_s=None):
    """Warmup then time ``iters`` independent calls ending in one
    :func:`sync`. ``max_time_s`` caps the TIMED loop's wall clock: the
    last warmup call (synced) estimates the per-step cost and ``iters``
    shrinks to fit — one pass of a 2k-dispatch loop is a statistically
    fine sample."""
    for _ in range(max(warmup, 1) - 1):
        out = fn(*args)
    t0 = time.perf_counter()
    out = fn(*args)
    sync(out)
    per_step = time.perf_counter() - t0
    if max_time_s is not None:
        iters = max(1, min(iters, int(max_time_s / max(per_step, 1e-9))))
    # sync every ~2s of enqueued work: async dispatch with NO sync lets
    # the in-flight buffer queue grow until the device runs out of memory
    sync_every = max(1, int(2.0 / max(per_step, 1e-9)))
    t0 = time.perf_counter()
    for i in range(iters):
        out = fn(*args)
        if (i + 1) % sync_every == 0:
            sync(out)
    sync(out)
    return max(time.perf_counter() - t0, 1e-9) / iters


def time_train_step(step, state, batch, iters=10):
    """Warm up once, then time ``iters`` chained calls of a jitted train
    step whose outputs are ``(*new_state, loss)`` and whose inputs are
    ``(*state, *batch)`` — the shared methodology for every model-level
    bench (donated state threads through)."""
    out = step(*state, *batch)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(*out[:-1], *batch)
    sync(out)
    return max(time.perf_counter() - t0, 1e-9) / iters


def time_chained(step, grads, state, params, iters=100):
    """Output-feeds-input timing: true serial device time per step."""
    p, s = step(grads, state, params)
    sync((p, s))
    t0 = time.perf_counter()
    for _ in range(iters):
        p, s = step(grads, s, p)
    sync((p, s))
    return max(time.perf_counter() - t0, 1e-9) / iters


def time_scanned(make_step, carry, chain, k=32, reps=3):
    """Per-iteration device time of a kernel too short to resolve
    against the per-dispatch overhead of a host loop.

    Run ``k`` iterations ON DEVICE under one ``lax.scan`` dispatch
    (``chain(carry, step) -> carry`` threads the output back in so
    nothing is dead-code-eliminated), time 1 rep and ``reps`` chained
    reps of the SAME jitted scan, and take the slope — dispatch overhead
    cancels."""
    import jax

    step = make_step()

    @jax.jit
    def scan_k(c):
        return jax.lax.scan(lambda c, _: (chain(c, step), None), c, None,
                            length=k)[0]

    out = scan_k(carry)       # compile + settle
    sync(out)
    t0 = time.perf_counter()
    out = scan_k(out)
    sync(out)
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = scan_k(out)
    sync(out)
    t_many = time.perf_counter() - t0
    return max(t_many - t_one, 1e-9) / ((reps - 1) * k)
