"""The readers of the serving engine's own spans (`serving_spans`,
`serving_idle`, over `program_ring`) on a hand-made ring and the small trace
recorded on a v5e: the two-anchor map between the clocks, idle gaps booked to
the innermost program span, the clock's slack, and no value (never a number)
where the ring dropped records, there is no trace, or no span matches."""

import os
import types

import pytest

from apex_tpu.observability import set_tracer
from apex_tpu.observability.profiling import Span
from perfbench import trace_reduce as tr
from perfbench.readers import program_ring, serving_idle, serving_spans

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMALL = os.path.join(REPO, "perfbench", "testdata", "small_trace.xplane.pb")
T0 = 1000.0                      # host seconds at which the trace "began"


class Ring:
    """A ring made by hand: what the readers ask of the program's tracer."""

    def __init__(self, spans, dropped=0):
        self.spans, self.n_dropped = spans, dropped

    def completed(self, since=0):
        return list(self.spans)

    def dropped(self, since=0):
        return self.n_dropped


@pytest.fixture
def ring():
    installed = []

    def install(spans, dropped=0):
        ring = Ring(spans, dropped)
        installed.append(set_tracer(ring))
        return ring

    yield install
    if installed:
        set_tracer(installed[0])


def make_run(trace=None, window_s=None, **counters):
    if trace is not None:
        length = trace.window_s if window_s is None else window_s
        counters.update(trace_t0=T0, trace_t1=T0 + length)
    return types.SimpleNamespace(trace=trace, counters=counters)


def spans_on_trace_clock(trace, rows):
    """(name, start, end, id, parent[, rid, args]) with stamps on the trace's
    clock -> the ring's Spans on the host's, `T0` being the window's start."""
    out = []
    for seq, (name, start, end, ident, parent, *rest) in enumerate(rows):
        rid, args = (rest + [None, None])[:2]
        host = [int(T0 * 1e9) + t - trace.window[0] for t in (start, end)]
        out.append(Span(name, 1, host[0], host[1], 0, seq, ident, parent,
                        rid, args, name == "serving/queue_wait"))
    return out


@pytest.fixture(scope="module")
def small():
    return tr.load(SMALL)


def serving_rows(small):
    """Three engine steps laid over the small trace's three programs (P1 at
    the window's start, P2 in the middle, P3 at its end, 5 ms of sleep
    between): step 1 decodes (P1) and then admits, its page write covering
    the whole first sleep; step 2 decodes (P2) and idles; step 3 decodes."""
    lo, hi = small.window
    (_, p1, d1), (_, p2, d2), (_, p3, _) = small.modules[0]
    return [
        ("serving/decode_upload", lo - 1000, p1 + 12000, 3, 2),
        ("serving/decode_fetch", p1 + 12000, p1 + d1 + 600, 4, 2),
        ("serving/decode", lo - 1000, p1 + d1 + 1000, 2, 1),
        ("serving/queue_wait", lo - 9000, p1 + d1 + 1100, 5, 6, 42),
        ("serving/prefill_dispatch", p1 + d1 + 1200, p1 + d1 + 3000, 7, 6, 42),
        ("serving/write_prompt", p1 + d1 + 3000, p2 + 100, 8, 6, 42,
         {"pages": 3}),
        ("serving/admit", p1 + d1 + 1100, p2 + 200, 6, 1, 42,
         {"prompt_tokens": 20, "bucket": 24, "rows": 2}),
        ("serving/step", lo - 1000, p2 + 300, 1, None),
        ("serving/decode_fetch", p2 + 5000, p2 + d2 + 713, 11, 10),
        ("serving/decode", p2 + 400, p2 + d2 + 2000, 10, 9),
        ("serving/step", p2 + 350, p3 - 5000, 9, None),
        ("serving/decode_fetch", p3 + 5000, hi - 40, 14, 13),
        ("serving/decode", p3 - 4000, hi - 20, 13, 12),
        ("serving/step", p3 - 4500, hi - 10, 12, None),
    ]


def test_the_two_anchors_map_exactly():
    trace = tr.Trace(window=(5_000, 6_005_000))
    run = make_run(trace, window_s=0.006001)     # the host's window: 1 us more
    to_trace, disagree = program_ring.clock_map(run)
    h0, h1 = T0 * 1e9, (T0 + 0.006001) * 1e9
    assert to_trace(h0) == 5_000
    assert to_trace(h1) == pytest.approx(6_005_000, abs=1e-3)
    assert to_trace((h0 + h1) / 2) == pytest.approx(3_005_000, abs=1e-3)
    assert disagree == pytest.approx(-1000, abs=1e-3)
    assert program_ring.clock_map(make_run(None)) is None
    assert program_ring.clock_map(
        types.SimpleNamespace(trace=trace, counters={"trace_t0": T0})) is None


def test_idle_time_lands_on_the_innermost_span_open_then(small, ring, capsys):
    ring(spans_on_trace_clock(small, serving_rows(small)))
    run = make_run(small)
    table = program_ring.idle_by_program_span(run)
    (_, p1, d1), (_, p2, d2), (_, p3, _) = small.modules[0]
    # the first sleep begins under step 1's fetch (600 ns of it), runs on
    # through the decode's and the step's own time and the admission's
    # dispatch (1,800 ns), and spends the rest under the page write
    assert table["serving/prefill_dispatch"] == [pytest.approx(1.8e-6), 1, 1]
    assert table["serving/admit"] == [pytest.approx(1e-7), 1, 1]
    assert table["serving/write_prompt"][0] == pytest.approx(
        (p2 - p1 - d1 - 3000) / 1e9, rel=1e-5)
    # the second is step 2's own but for the 2 us its decode was still open,
    # the 0.5 us between the steps and the 4.5 us of step 3 before P3 began
    # (0.5 us of them the step's own, as were 0.1 us of the first sleep)
    assert table["serving/step"][0] == pytest.approx(
        (p3 - p2 - d2 - 2000 - 5000 + 500 + 100) / 1e9, rel=1e-6)
    assert table[program_ring.NO_SPAN] == [pytest.approx(5e-7), 1, 0]
    assert table["serving/decode"][2] == table["serving/step"][2] == 3
    # the 11 ns gaps inside the programs: under the fetch that waited
    assert table["serving/decode_fetch"][1] > 50
    # every instant is booked once: the rows add up to the device's idle time
    assert sum(row[0] for row in table.values()) == pytest.approx(
        tr.idle_share(small) * small.window_s, rel=1e-9)
    printed = capsys.readouterr().err
    assert "serving/write_prompt" in printed and "residual" in printed
    # one admission, three decode steps ended in the traced window
    assert serving_idle.read(run, "per_admission") == pytest.approx(
        1e3 * sum(table[n][0] for n in program_ring.ADMIT if n in table))
    assert serving_idle.read(run, "per_decode_step") == pytest.approx(
        1e3 * sum(table[n][0] for n in program_ring.DECODE if n in table) / 3)
    assert capsys.readouterr().err == ""       # the table is printed once


def test_innermost_segments_split_time_between_nested_spans():
    spans = [("step", 0, 100, 1, None), ("admit", 10, 60, 2, 1),
             ("write", 20, 50, 3, 2), ("decode", 60, 90, 4, 1),
             ("step", 130, 150, 5, None)]
    assert program_ring.innermost_segments(spans) == [
        (0, 10, "step"), (10, 20, "admit"), (20, 50, "write"),
        (50, 60, "admit"), (60, 90, "decode"), (90, 100, "step"),
        (130, 150, "step")]


def test_clock_slack_pairs_a_program_with_its_fetch(small, ring, monkeypatch,
                                                    capsys):
    monkeypatch.setattr(serving_idle, "PROGRAM", "small_step")
    ring(spans_on_trace_clock(small, serving_rows(small)))
    # P1 and P3 are cut by the window's edges; P2's fetch returned 713 ns
    # after the program ended on the device
    assert serving_idle.read(make_run(small), "clock_slack_us") == \
        pytest.approx(0.713 - 0.001, abs=2e-3)
    printed = capsys.readouterr().err
    assert "anchors disagree by" in printed
    # P2 began 5.35 us after step 2's decode did; that decode has no upload
    # span of its own here, P3's neither: nothing to pair on that side
    assert "paired with their upload" in printed
    # a host clock that runs 2 us behind the device's reads negative
    late = make_run(small)
    late.counters["trace_t0"] += 2e-6
    late.counters["trace_t1"] += 2e-6
    assert serving_idle.read(late, "clock_slack_us") == pytest.approx(
        0.712 - 2.0, abs=2e-3)


def test_span_readers_over_the_measured_window(ring):
    def span(name, start_ms, end_ms, ident, rid=None, **args):
        return Span(name, 1, int(T0 * 1e9 + start_ms * 1e6),
                    int(T0 * 1e9 + end_ms * 1e6), 0, ident, ident, None, rid,
                    args, name == "serving/queue_wait")

    spans = [span("serving/queue_wait", -50, 1, 0, rid=9),     # ends before
             span("serving/admit", 100, 300, 1, rid=1, prompt_tokens=2000,
                  bucket=2048, rows=4),
             span("serving/admit", 400, 450, 2, rid=2, prompt_tokens=500,
                  bucket=512, rows=0)]
    spans += [span("serving/queue_wait", 10 * i, 10 * i + i, 10 + i, rid=i)
              for i in range(1, 11)]
    spans += [span("serving/decode", 500 + 60 * i, 550 + 60 * i, 30 + i,
                   rows=8, pages_live=40, pages_gathered=160)
              for i in range(4)]
    spans.append(span("serving/decode", 5000, 5050, 99, rows=8, pages_live=1,
                      pages_gathered=160))                     # ends after
    ring(spans)
    run = make_run(None, t_open=T0 + 0.002, t_close=T0 + 1.0)
    assert serving_spans.read(run, "queue_wait_ms", q=90) == \
        pytest.approx(9.0)
    assert serving_spans.read(run, "admit_ms_per_ktok") == \
        pytest.approx(250 / 2.5)
    assert serving_spans.read(run, "admit_stall_share") == \
        pytest.approx(100 * 800 / (800 + 4 * 50 * 8))
    assert serving_spans.read(run, "gather_useful_share") == \
        pytest.approx(25.0)


@pytest.mark.parametrize("what,args", [
    ("queue_wait_ms", {"q": 90}), ("admit_ms_per_ktok", {}),
    ("admit_stall_share", {}), ("gather_useful_share", {})])
def test_span_readers_return_no_value(ring, what, args):
    run = make_run(None, t_open=T0, t_close=T0 + 1.0)
    ring([Span("pp/forward", 1, int(T0 * 1e9) + 5, int(T0 * 1e9) + 9, 0, 0)])
    assert serving_spans.read(run, what, **args) is None    # no such span
    ring([Span("serving/decode", 1, int(T0 * 1e9) + 5, int(T0 * 1e9) + 9, 0,
               7, 1, None, None, {"rows": 1, "pages_live": 1,
                                  "pages_gathered": 2})], dropped=3)
    assert serving_spans.read(run, what, **args) is None    # the ring dropped
    assert serving_spans.read(make_run(None), what, **args) is None


@pytest.mark.parametrize("what", ["per_admission", "per_decode_step",
                                  "clock_slack_us"])
def test_idle_readers_return_no_value(small, ring, what, monkeypatch):
    monkeypatch.setattr(serving_idle, "PROGRAM", "small_step")
    rows = spans_on_trace_clock(small, serving_rows(small))
    ring(rows)
    assert serving_idle.read(make_run(None), what) is None   # no trace
    empty = tr.Trace(window=small.window)                    # no device plane
    assert serving_idle.read(make_run(empty), what) is None
    ring(rows, dropped=1)                                    # the ring dropped
    assert serving_idle.read(make_run(small), what) is None
    ring([Span("pp/forward", 1, r.start_ns, r.end_ns, 0, r.seq, r.id)
          for r in rows])                                    # no such span
    assert serving_idle.read(make_run(small), what) is None


def test_a_ring_that_dropped_only_before_the_window_still_reads(ring):
    before = Span("serving/step", 1, int(T0 * 1e9) - 900, int(T0 * 1e9) - 100,
                  0, 50, 1)
    inside = Span("serving/decode", 1, int(T0 * 1e9) + 5, int(T0 * 1e9) + 9,
                  0, 51, 2, None, None, {"rows": 1, "pages_live": 1,
                                         "pages_gathered": 4})
    ring([before, inside], dropped=50)
    run = make_run(None, t_open=T0, t_close=T0 + 1.0)
    assert serving_spans.read(run, "gather_useful_share") == 25.0
