"""Per-kernel dispatch table (pallas_config._KERNEL_AUTO).

The bench kernel race on real hardware pins per-kernel verdicts; 'auto'
mode must honor them, while force('on'/'off'/'interpret') must override
so tests and the race itself can still reach both paths.
"""

import jax
import pytest

from apex_tpu.ops import pallas_config


def test_device_tables_raise_on_unlisted_tpu_kind():
    """A planning figure is only returned for a kind the table lists
    (or off-TPU); a TPU nobody measured raises."""
    assert pallas_config.device_vmem_bytes("TPU v5 lite") == 16 << 20
    assert pallas_config.device_hbm_bytes("TPU v5 lite") == 16 << 30
    assert pallas_config.device_vmem_bytes("cpu") == 16 << 20
    assert pallas_config.device_hbm_bytes("cpu") == 16 << 30
    for fn in (pallas_config.device_vmem_bytes,
               pallas_config.device_hbm_bytes):
        with pytest.raises(ValueError, match="TPU v9"):
            fn("TPU v9")


def test_force_overrides_table():
    with pallas_config.force("on"):
        assert pallas_config.use_pallas("flat_adam")
    with pallas_config.force("interpret"):
        assert pallas_config.use_pallas("flat_adam")
    with pallas_config.force("off"):
        assert not pallas_config.use_pallas("layer_norm")


def test_auto_honors_verdict():
    on_tpu = jax.default_backend() == "tpu"
    with pallas_config.force("auto"):
        # flat_adam lost the race: off under auto everywhere
        assert pallas_config.use_pallas("flat_adam") is False
        # unlisted kernels keep the backend heuristic
        assert pallas_config.use_pallas("layer_norm") == on_tpu
        assert pallas_config.use_pallas() == on_tpu


def test_set_kernel_auto_roundtrip():
    on_tpu = jax.default_backend() == "tpu"
    # snapshot BOTH tables: restoring the verdicts through
    # set_kernel_auto(**prev) would re-tag every pin with
    # "runtime:set_kernel_auto" evidence, clobbering flat_adam's
    # shipped docs/kernel_cost_study.md (or tuning:) provenance — and
    # tests/run_analysis/test_provenance.py then fails whenever a
    # subset runs it after this file (any order must pass)
    prev = pallas_config.kernel_auto()
    prev_ev = pallas_config.kernel_auto_evidence()
    try:
        pallas_config.set_kernel_auto(layer_norm=False, rms_norm=True)
        with pallas_config.force("auto"):
            assert pallas_config.use_pallas("layer_norm") is False
            # True pins auto-on, but never off-backend: Pallas still
            # requires a TPU to compile
            assert pallas_config.use_pallas("rms_norm") == on_tpu
        pallas_config.set_kernel_auto(layer_norm=None, rms_norm=None)
        with pallas_config.force("auto"):
            assert pallas_config.use_pallas("layer_norm") == on_tpu
    finally:
        # exact-state restore (same pattern as tests/run_tuning's
        # tuning_env fixture): verdicts AND per-key evidence
        pallas_config._KERNEL_AUTO.clear()
        pallas_config._KERNEL_AUTO.update(prev)
        pallas_config._KERNEL_AUTO_EVIDENCE.clear()
        pallas_config._KERNEL_AUTO_EVIDENCE.update(prev_ev)


def test_fused_adam_flat_defers_to_table():
    import jax.numpy as jnp

    from apex_tpu.optimizers import fused_adam

    params = {"w": jnp.ones((64,), jnp.float32)}
    grads = {"w": jnp.full((64,), 1e-3, jnp.float32)}
    tx = fused_adam(lr=1e-3, flat=True)
    state = tx.init(params)
    # auto: table says off -> XLA chain; interpret: kernel body runs.
    # Both must agree numerically.
    with pallas_config.force("auto"):
        d_auto, _ = tx.update(grads, state, params)
    with pallas_config.force("interpret"):
        d_kern, _ = tx.update(grads, state, params)
    assert jnp.allclose(d_auto["w"], d_kern["w"], atol=1e-6)


def test_env_override_loading():
    import json as _json
    import subprocess
    import sys

    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from apex_tpu.ops import pallas_config as pc\n"
        "print(_sorted := sorted(pc.kernel_auto().items()))\n")
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**__import__('os').environ,
             "APEX_TPU_KERNEL_AUTO": _json.dumps(
                 {"layer_norm": False, "flat_adam": None})},
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-500:]
    # flat_adam's built-in verdict deleted by null; layer_norm pinned off
    assert "('layer_norm', False)" in out.stdout
    assert "flat_adam" not in out.stdout


def test_flash_tiles_env_override():
    import json as _json
    import os
    import subprocess
    import sys

    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from apex_tpu.ops import pallas_config as pc\n"
        "print('fwd', pc.flash_blocks('fwd', 4096, 4096, 128))\n"
        "print('bwd', pc.flash_blocks('bwd', 4096, 4096, 128))\n")
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "APEX_TPU_FLASH_TILES": _json.dumps(
            {"fwd": [1024, 256], "bwd": "auto"})},
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-500:]
    assert "fwd (1024, 256)" in out.stdout
    assert "bwd (256, 256)" in out.stdout  # auto default at this shape

    for payload in ('{"fwd": "big"}', '{"fwd": [true, 512]}',
                    '{"fwd": [512]}'):
        bad = subprocess.run(
            [sys.executable, "-c", code],
            env={**os.environ, "APEX_TPU_FLASH_TILES": payload},
            capture_output=True, text=True, timeout=120)
        assert bad.returncode != 0 and "2-int" in bad.stderr, payload
