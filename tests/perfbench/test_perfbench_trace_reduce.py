"""The trace reduction against the small trace recorded on a v5e and kept in
perfbench/testdata (three calls of a scan of LayerNorm, flash attention and a
matmul, under `bench:train_step`, with a sleeping `bench:make_batch` between),
and against hand-made events where the recording has nothing (collectives)."""

import os

import pytest

from perfbench import trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMALL = os.path.join(REPO, "perfbench", "testdata", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return tr.load(SMALL)


def test_names():
    text = "%fusion.12 = f32[8,128]{1,0} fusion(f32[8,128] %p), kind=kLoop"
    assert tr.op_name(text) == "fusion.12"
    assert tr.op_stem("apex_flash_fwd.3") == "apex_flash_fwd"
    assert tr.op_stem("apex_flash_fwd") == "apex_flash_fwd"
    assert tr.is_container("while") and tr.is_container("while.2")
    assert not tr.is_container("fusion.1")
    assert tr.is_collective("all-reduce.5")
    assert tr.is_collective("all-reduce-start.1")
    assert not tr.is_collective("fusion.3")


def test_union_and_busy():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    events = [("a", 0, 10), ("b", 5, 10), ("c", 100, 1)]
    assert tr.busy_intervals(events) == [(0, 15), (100, 101)]


def test_small_trace_programs_and_kernels(small):
    programs = tr.module_events(small, "small_step")
    assert len(programs) == 3
    assert all(60e-6 < s < 80e-6 for _, s in programs)
    flash = tr.kernel_events(small, "apex_flash_fwd")
    norm = tr.kernel_events(small, "apex_ln_fwd")
    assert len(flash) == len(norm) == 12            # 3 calls x 4 layers
    assert all(9e-6 < s < 12e-6 for s in flash)
    by_program = tr.kernel_events_by_module(small, "apex_flash_fwd",
                                            "small_step")
    assert [len(calls) for _, calls in by_program] == [4, 4, 4]
    assert tr.prefix_seconds(small, "apex_") == pytest.approx(
        sum(flash) + sum(norm))
    # the loop is a container, not work: it is not among the operations
    assert not any(tr.is_container(n) for n, _, _ in small.ops[0])


def test_small_trace_busy_and_idle(small):
    busy = tr.busy_s(small)
    assert busy == pytest.approx(sum(s for _, s in
                                     tr.module_events(small, "small_step")),
                                 rel=0.05)
    assert 0.97 < tr.idle_share(small) < 0.99       # a chip that mostly waits
    gaps = dict(tr.idle_by_span(small))
    # the host slept under bench:make_batch between the calls
    assert max(gaps, key=gaps.get) == "bench:make_batch"
    assert sum(gaps.values()) == pytest.approx(small.window_s - busy)
    top = tr.breakdown(small)
    assert top["device_ops"][0][0].startswith("apex_flash_fwd")
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10


def test_gaps_go_to_the_span_that_covers_most_of_them():
    trace = tr.Trace(ops={0: [("fusion.1", 0, 10), ("fusion.2", 50, 10),
                              ("fusion.3", 90, 10)]},
                     spans=[("bench:step_decode", 5, 30),
                            ("bench:step_prefill", 35, 70),
                            ("bench:submit", 62, 3)],
                     window=(0, 100))
    # gap 10-50: 25 under decode, 15 under prefill; gap 60-90: prefill
    by_span = tr.idle_by_span(trace)
    assert [name for name, _ in by_span] == ["bench:step_decode",
                                             "bench:step_prefill"]
    assert dict(by_span) == pytest.approx({"bench:step_decode": 40e-9,
                                           "bench:step_prefill": 30e-9})
    assert tr.busy_s(trace) == pytest.approx(30e-9)
    assert tr.idle_share(trace) == pytest.approx(0.7)
    none = tr.Trace(ops={0: [("fusion.1", 0, 10)]}, window=(0, 20))
    assert tr.idle_by_span(none) == [("_no_span_", 10e-9)]


def test_collective_time_counts_only_where_nothing_else_runs():
    trace = tr.Trace(
        ops={0: [("fusion.1", 0, 100), ("all-reduce-done.1", 100, 20),
                 ("fusion.2", 120, 30)]},
        async_ops={0: [("all-reduce-start.1", 60, 60)]},
        window=(0, 150))
    # the collective is under way 60-120; compute covers 60-100
    assert tr.collective_exposed_s(trace) == pytest.approx(20e-9)
    assert len(tr.collective_events(trace)) == 2
    quiet = tr.Trace(ops={0: [("fusion.1", 0, 10)]}, window=(0, 10))
    assert tr.collective_events(quiet) == []


def test_clip_cuts_events_to_the_window():
    trace = tr.clip(tr.Trace(ops={0: [("a", 0, 10), ("b", 8, 10),
                                      ("c", 30, 5)]}, window=(5, 15)))
    assert trace.ops[0] == [("a", 5, 5), ("b", 8, 7)]
