"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; all sharding/collective tests run
against XLA's host-platform device partitioning (SURVEY.md §4).

The platform and the device count are set through the environment
(``JAX_PLATFORMS=cpu``, ``XLA_FLAGS``) before jax is imported, so every
subprocess a test starts inherits them; the ``jax.config`` update below
covers an invoker whose jax was imported first.
"""

import os
import sys

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Hermetic tuning cache: dispatch consults the persistent per-device
# tuning cache (apex_tpu.tuning), and a developer's real
# ~/.cache/apex_tpu/tuning_cache.json would change tile geometry and
# _KERNEL_AUTO verdicts under test (or, schema-drifted, error every
# dispatch). Point the whole suite at a fresh per-session path unless
# the invoker explicitly chose one; tests that need their own cache
# (tests/run_tuning) still monkeypatch over this.
if "APEX_TPU_TUNING_CACHE" not in os.environ:
    import tempfile

    os.environ["APEX_TPU_TUNING_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="apex_tpu_test_tuning_"),
        "tuning_cache.json")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end example tests")
    config.addinivalue_line(
        "markers",
        "multidevice(n=8): needs an n-way (simulated) device mesh; "
        "skipped when the backend came up with fewer devices")


def pytest_runtest_setup(item):
    marker = item.get_closest_marker("multidevice")
    if marker is None:
        return
    need = marker.kwargs.get("n", marker.args[0] if marker.args else 8)
    have = len(jax.devices())
    if have < need:
        pytest.skip(f"needs {need} devices, backend has {have} "
                    f"(the 8-way simulated mesh failed to force)")


@pytest.fixture
def simulated_mesh_subprocess():
    """Shared multi-device harness (ISSUE 11): run a python snippet in
    a FRESH subprocess against an 8-way simulated CPU mesh
    (``apex_tpu.parallel.multiproc.simulated_mesh_env`` sets
    ``--xla_force_host_platform_device_count`` before the interpreter
    starts, so every comms path runs real collectives even where this
    conftest's in-process forcing never ran). Returns a callable
    ``run(code, n=8, timeout=300)`` -> CompletedProcess."""
    def run(code: str, n: int = 8, timeout: float = 300.0):
        from apex_tpu.parallel import multiproc

        return multiproc.run_simulated(
            [sys.executable, "-c", code], n=n, timeout=timeout)

    return run
