"""Shared example plumbing: device check, compile cache + synthetic data.

Examples run on the backend JAX selects: the TPU where there is one, the
CPU under ``JAX_PLATFORMS=cpu`` (how the tests run them).
"""

from __future__ import annotations

import jax


def ensure_devices(n: int) -> None:
    """Check that the selected backend has ``n`` devices, and place the
    compile cache (``apex_tpu.runtime.compile_cache``). Call before the
    first JAX operation.

    The CPU backend is asked for ``n`` virtual devices (the option only
    governs the CPU client, and can only be set before the backend
    starts); an accelerator has the chips it has, and fewer than ``n``
    is an error — an example never moves itself to another backend."""
    from apex_tpu.runtime.compile_cache import enable_compile_cache

    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass  # backend already started: its device count stands
    devices = jax.devices()
    if len(devices) < n:
        raise SystemExit(
            f"need {n} devices, the {devices[0].platform} backend has "
            f"{len(devices)}")
    enable_compile_cache()


def synthetic_images(key, batch: int, size: int, classes: int):
    """One synthetic (images, labels) batch — stands in for the imagenet
    loader (ref examples/imagenet/main_amp.py uses real ImageFolder; the
    example trains on fixed random data so it runs anywhere)."""
    import jax.numpy as jnp

    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (batch, size, size, 3), jnp.float32)
    y = jax.random.randint(ky, (batch,), 0, classes)
    return x, y


def opt_partition_specs(tx, params, param_specs):
    """Re-export of :func:`apex_tpu.optimizers.opt_partition_specs` (the
    examples imported it from here before it was promoted to the package)."""
    from apex_tpu.optimizers import opt_partition_specs as f

    return f(tx, params, param_specs)


def resume_exhausted(start_it, total_steps) -> bool:
    """True (with a message) when a resumed step index is already past
    the requested step count — the train loop would run zero iterations."""
    if start_it is not None and start_it >= total_steps:
        print(f"nothing to do: resumed step + 1 ({start_it}) >= "
              f"--steps {total_steps}")
        return True
    return False
