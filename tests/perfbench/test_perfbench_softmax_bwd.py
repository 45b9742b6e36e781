"""`apex_softmax_bwd_roofline` read from a small synthetic trace: the bytes by
hand, the kernel told from the forward by its stem, and no value (not a
failure) from a program that has no such kernel, as the parent has not."""

import importlib

import pytest

from perfbench import harness, peaks
from perfbench import trace_reduce as tr
from perfbench.readers import softmax_bwd_roofline

CELLS = ["gpt2_345m.pretrain_s1024", "gpt2_345m.pretrain_s1024_ddp4"]
NAME = "apex_softmax_bwd_roofline"
# 16 rows x 16 heads, the causal half of 1024 x 1024, three bf16 arrays
BYTES = 3 * 256 * (1024 * 1025 // 2) * 2
LEAST_NS = BYTES / 819e9 * 1e9


def read(run):
    """As `Run.per_layer_metrics` finds it: the metric's file names the
    reader and its arguments."""
    spec = harness.load_json(run.root, "perfbench", "metrics", NAME + ".json")
    reader = importlib.import_module("perfbench.readers." + spec["reader"])
    return reader.read(run, **spec["args"])


def traced_run(cell, ops):
    run = harness.Run(cell, 0, 1, 1, require_tpu=False)
    run.peak = peaks.peak("TPU v5 lite")
    end = max((s + d for events in ops.values() for _, s, d in events),
              default=1)
    run.trace = tr.Trace(ops=ops, window=(0, end))
    return run


def test_bytes_by_hand():
    assert softmax_bwd_roofline.causal_softmax_bwd_bytes(256, 1024) == BYTES
    assert BYTES == 806_092_800      # 0.984 ms at 819 GB/s


@pytest.mark.parametrize("cell", CELLS)
def test_reads_the_backward_kernel_alone(cell):
    took = int(2.5 * LEAST_NS)
    ops = {chip: [("apex_softmax_bwd.9", 0, took),
                  ("apex_causal_softmax.12", took, 7 * took),
                  ("apex_softmax_bwd_stats.1", 8 * took, 9 * took),
                  ("fusion.323", 17 * took, took),
                  ("apex_softmax_bwd", 18 * took, took)]
           for chip in range(4 if cell.endswith("ddp4") else 1)}
    assert read(traced_run(cell, ops)) == pytest.approx(40.0, rel=1e-6)


def test_streaming_the_whole_square_reads_half():
    """A kernel that moves all of y, g and dx at the peak rate reads just
    under 50%: the share cannot pass 100 by moving more."""
    whole = int(3 * 256 * 1024 * 1024 * 2 / 819e9 * 1e9)
    run = traced_run(CELLS[0], {0: [("apex_softmax_bwd.9", 0, whole)]})
    assert 50.0 < read(run) < 50.1


def test_no_value_where_the_kernel_never_ran():
    parent = traced_run(CELLS[0], {0: [("fusion.323", 0, 5_000_000),
                                       ("apex_causal_softmax.12", 0, 10)]})
    assert read(parent) is None
    untraced = traced_run(CELLS[0], {})
    untraced.trace = None
    assert read(untraced) is None
    unknown_chip = traced_run(CELLS[0], {0: [("apex_softmax_bwd.9", 0, 10)]})
    unknown_chip.peak = None
    assert read(unknown_chip) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_traced_result_line_carries_it(cell):
    took = int(3 * LEAST_NS)
    run = traced_run(cell, {0: [("apex_softmax_bwd.9", 0, took)]})
    run.device = {}
    metrics = run.per_layer_metrics()
    assert metrics[NAME]["unit"] == "%"
    assert metrics[NAME]["value"] == pytest.approx(100 / 3, rel=1e-6)
    serving = harness.Run("mistral7b_v03_d16.longgen_backlog", 0, 1, 1,
                          require_tpu=False)
    assert all(NAME != m["name"] for m in serving.bench["per_layer"]
               if serving._reports(m))
