"""GPT-2 345M's training step where its cells run it, at the published
widths with two layers, compiled for a v5e that is described and not
attached: B=16, S=1024, amp O2, remat per layer, the gradient and the
optimizer. Its attention runs through the flash kernels, forward (and its
recomputation) and backward, so no [16,16,1024,1024] score square exists in
the step, and q, k, v reach the kernels with few transposing copies. The
topology is described inside a fixture, never at import."""

import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding

ROWS, HEADS, SEQ, HEAD_DIM = 16, 16, 1024, 64
SQUARES = (f"[{ROWS},{HEADS},{SEQ},{SEQ}]", f"[{ROWS * HEADS},{SEQ},{SEQ}]")
HEAD_SHAPES = (f"[{ROWS},{HEADS},{SEQ},{HEAD_DIM}]",
               f"[{ROWS},{SEQ},{HEADS},{HEAD_DIM}]",
               f"[{ROWS * HEADS},{SEQ},{HEAD_DIM}]")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def step_text(one_chip):
    import chip_smoke  # repo root is on sys.path via tests/conftest.py
    from apex_tpu.models import gpt2
    from apex_tpu.ops import pallas_config

    cfg = gpt2.gpt2_345m(num_layers=2)
    handle, tx, init_state, batch = chip_smoke._gpt2_setup(cfg, ROWS)
    struct = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)
    args = jax.tree_util.tree_map(
        struct, (*jax.eval_shape(init_state), jax.eval_shape(lambda: batch)))
    step = jax.jit(chip_smoke._gpt2_step(handle, tx, cfg),
                   donate_argnums=(0, 1, 2))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pallas_config.force("on"):
            return step.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def instructions(text):
    """(name, shape, opcode) of every instruction of a compiled module."""
    return re.findall(r"^\s*(?:ROOT\s+)?%?(\S+) = (\S+) ([\w\-]+)\(", text,
                      re.M)


def test_attention_runs_the_flash_kernels_and_no_softmax_kernel(step_text):
    calls = [name for name, _, op in instructions(step_text)
             if op == "custom-call"]
    for kernel in ("apex_flash_fwd", "apex_flash_bwd_dq",
                   "apex_flash_bwd_dkv"):
        assert kernel in step_text, (kernel, calls)
    assert "apex_causal_softmax" not in step_text
    assert "apex_softmax_bwd" not in step_text


def test_no_score_square_in_the_step(step_text):
    squares = [(name, shape) for name, shape, _ in instructions(step_text)
               if any(s in shape for s in SQUARES)]
    assert not squares, squares


def test_heads_reach_the_kernels_with_few_transposing_copies(step_text):
    """q, k and v are written heads-major by the packed product itself:
    split as [b, s, n, d] first, the two layers' step held 12 copies of a
    head-sized operand round the kernels (0.1 ms each at 819 GB/s)."""
    copies = [(name, shape) for name, shape, op in instructions(step_text)
              if op in ("copy", "transpose")
              and any(s in shape for s in HEAD_SHAPES)]
    assert len(copies) <= 3, copies
