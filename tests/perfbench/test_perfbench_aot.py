"""The kernels the cells lean on, compiled for a v5e that is described and not
attached, at the cells' own shapes: what Mosaic would refuse on the chip it
refuses here, at no chip time. The only file of these tests that loads the
TPU's compiler; the topology is described inside a fixture, never at import."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, one_chip, *shapes):
    from apex_tpu.ops import pallas_config

    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pallas_config.force("on"):
            return jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def test_flash_prefill_at_mistral_heads_compiles(one_chip):
    from apex_tpu.ops.flash_attention import flash_attention

    text = compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        scale=128 ** -0.5),
        one_chip, (1, 2560, 32, 128), (1, 2560, 8, 128), (1, 2560, 8, 128))
    assert "tpu_custom_call" in text and "apex_flash_fwd" in text


def test_causal_softmax_at_gpt2_345m_compiles(one_chip):
    from apex_tpu.transformer.functional.fused_softmax import (
        scaled_upper_triang_masked_softmax,
    )

    text = compiled_text(
        lambda x: scaled_upper_triang_masked_softmax(x, None, 0.125),
        one_chip, (16 * 16, 1024, 1024))
    assert "tpu_custom_call" in text and "apex_causal_softmax" in text


def test_layer_norm_backward_at_gpt2_345m_compiles(one_chip):
    from apex_tpu.normalization.fused_layer_norm import fused_layer_norm_affine

    def loss(x, w, b):
        y = fused_layer_norm_affine(x, w, b, (1024,), eps=1e-5)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                         (16 * 1024, 1024), (1024,), (1024,))
    assert "apex_ln_fwd" in text and "apex_ln_bwd" in text
