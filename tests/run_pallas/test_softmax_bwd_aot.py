"""The fused softmax's backward kernel where the GPT-2 345M cells run it,
compiled for a v5e that is described and not attached: the gradient of the
score path (QK^T -> causal softmax -> PV, bf16, 16 rows x 16 heads x 1024)
must hold the kernel and hand it its operands with no layout copy of the
[16,16,1024,1024] scores (PERF.md, PR 27: left to XLA the backward was a
`reduce-window` fusion behind a 1 GiB float32 copy, 26% of the step). The
topology is described inside a fixture, never at import."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROWS, HEADS, SEQ, HEAD_DIM = 16, 16, 1024, 64
SCORES = (f"[{ROWS},{HEADS},{SEQ},{SEQ}]", f"[{ROWS * HEADS},{SEQ},{SEQ}]")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, *structs):
    from apex_tpu.ops import pallas_config

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pallas_config.force("on"):
            return jax.jit(fn).lower(*structs).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def score_path_loss(q, k, v):
    """`models/_common.packed_qkv_attention`'s score path with GPT-2's
    softmax, as `models/gpt2.py` composes it."""
    from apex_tpu.transformer.functional.fused_softmax import (
        scaled_upper_triang_masked_softmax,
    )

    b, s, n, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    probs = scaled_upper_triang_masked_softmax(
        scores.reshape(b * n, s, s), None, d ** -0.5).reshape(b, n, s, s)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return jnp.sum(o.astype(jnp.float32) ** 2)


def instructions(text):
    """(name, shape, opcode) of every instruction of a compiled module."""
    return re.findall(r"^\s*(?:ROOT\s+)?%?(\S+) = (\S+) ([\w\-]+)\(", text,
                      re.M)


def test_score_path_gradient_runs_the_kernel_with_no_copy(one_chip):
    heads = jax.ShapeDtypeStruct((ROWS, SEQ, HEADS, HEAD_DIM), jnp.bfloat16,
                                 sharding=one_chip)
    text = compiled_text(jax.grad(score_path_loss, argnums=(0, 1, 2)),
                         heads, heads, heads)
    found = instructions(text)
    kernels = [name for name, _, op in found
               if op == "custom-call" and "apex_softmax_bwd" in name]
    assert len(kernels) == 1, kernels
    assert any("apex_causal_softmax" in name for name, _, _ in found)
    copies = [(name, shape) for name, shape, op in found
              if op.startswith("copy") and any(s in shape for s in SCORES)]
    assert not copies, copies
    # dP reaches the kernel as the configuration states it: bf16
    assert not [shape for _, shape, _ in found
                if shape.startswith("f32") and any(s in shape for s in SCORES)]


@pytest.mark.parametrize("y_dtype,g_dtype", [
    (jnp.bfloat16, jnp.bfloat16), (jnp.bfloat16, jnp.float32),
    (jnp.float32, jnp.float32)])
def test_kernel_fits_vmem_at_the_cell_shape(one_chip, y_dtype, g_dtype):
    """Mosaic refuses here what it would refuse on the chip: the row block
    with its double buffers and float32 temporaries, widest dtypes too."""
    from apex_tpu.transformer.functional import fused_softmax as fs

    y = jax.ShapeDtypeStruct((ROWS * HEADS, SEQ, SEQ), y_dtype,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((ROWS * HEADS, SEQ, SEQ), g_dtype,
                             sharding=one_chip)
    text = compiled_text(lambda y, g: fs._softmax_bwd(0.125, y, g), y, g)
    assert "tpu_custom_call" in text and "apex_softmax_bwd" in text
