"""The split of the device's idle time into the program's own pauses, launch
gaps, the host's wait and time with nothing to run (`idle_split`), and the
pairing of programs with their dispatching spans by `program_seq`
(`program_pairs`), on a trace and a ring made by hand: one decode step in
flight when the trace begins and one cut at its end, a host pause after which
the nearest fetch end is the step before's, an admission, and an interval
with nothing to run. Every number below is worked out from the timeline."""

import types

import pytest

from apex_tpu.observability import set_tracer
from apex_tpu.observability.profiling import Span
from perfbench import trace_reduce as tr
from perfbench.readers import idle_split, program_pairs, serving_idle

T0 = 1000.0                      # host seconds at which the trace began
W = 5_000_000                    # the window's start on the trace's clock
LENGTH = 60_000                  # ns


class Ring:
    def __init__(self, spans):
        self.spans = spans

    def completed(self, since=0):
        return list(self.spans)

    def dropped(self, since=0):
        return 0


@pytest.fixture
def ring():
    installed = []

    def install(spans):
        installed.append(set_tracer(Ring(spans)))

    yield install
    if installed:
        set_tracer(installed[0])


def device():
    """Programs (name, start, end) and their ops, ns from the window's start:
    D0 began before the window, D4 runs past its end."""
    programs = [("jit__decode_step(7)", -2000, 8000),
                ("jit__decode_step(7)", 9000, 17000),
                ("jit__decode_step(7)", 25300, 26300),
                ("jit__serving_prefill_s16(3)", 30000, 36000),
                ("jit__serving_write_pages(5)", 36000, 36500),
                ("jit__serving_write_pages(5)", 36500, 37000),
                ("jit__decode_step(7)", 38500, 46000),
                ("jit__decode_step(7)", 58000, 62000)]
    ops = [("fusion.1", -2000, 3000), ("fusion.2", 3500, 8000),
           ("fusion.1", 9000, 12000), ("apex_gmm.3", 12200, 17000),
           ("fusion.1", 25300, 26300), ("fusion.5", 30000, 36000),
           ("scatter.1", 36000, 36500), ("scatter.2", 36500, 37000),
           ("fusion.1", 38500, 46000), ("fusion.1", 58000, 62000)]
    return programs, ops


def host(seq=True):
    """(name, start, end, id, parent, args) on the trace's clock, ns from
    the window's start; without `seq`, as a program that records no
    `program_seq` writes them."""
    def s(n):
        return {"program_seq": n} if seq else {}

    rows = [
        ("serving/decode_upload", 1600, 2600, 3, 2, s(1)),
        ("serving/decode_fetch", 2600, 8300, 4, 2, {}),      # lands D0
        ("serving/decode_bookkeep", 8300, 8400, 5, 2, {}),
        ("serving/decode", 1600, 8600, 2, 1, {"rows": 2}),
        ("serving/step", 1500, 8700, 1, None, {}),
        # the host pauses 8,700 -> 20,000 with no span open
        ("serving/decode_upload", 20100, 25000, 8, 7, s(2)),
        ("serving/decode_fetch", 25000, 25400, 9, 7, {}),    # lands D1
        ("serving/decode_bookkeep", 25400, 25500, 10, 7, {}),
        ("serving/decode", 20100, 27400, 7, 6, {"rows": 2}),
        ("serving/step", 20000, 27500, 6, None, {}),
        ("serving/prefill_dispatch", 27600, 29700, 13, 12,
         {"bucket": 16, **s(0)}),
        ("serving/write_prompt", 29700, 29900, 14, 12, s(0)),
        ("serving/first_token_fetch", 29900, 36300, 15, 12, {}),
        ("serving/admit", 27600, 36400, 12, 11,
         {"prompt_tokens": 10, "bucket": 16, "rows": 0}),
        ("serving/decode_upload", 38200, 38400, 17, 16, s(3)),
        ("serving/decode_fetch", 38400, 38600, 18, 16, {}),  # lands D2
        ("serving/decode_bookkeep", 38600, 38700, 19, 16, {}),
        ("serving/decode", 38150, 38800, 16, 11, {"rows": 1}),
        ("serving/step", 27500, 38900, 11, None, {}),
        # no row left: the last step is landed under the iteration
        ("serving/decode_fetch", 46100, 46300, 21, 20, {}),  # lands D3
        ("serving/decode_bookkeep", 46300, 46400, 22, 20, {}),
        ("serving/step", 46050, 47050, 20, None, {}),
        ("serving/empty", 47000, 55000, 30, None, {}),
        ("serving/decode_upload", 55600, 57900, 25, 24, s(4)),
        ("serving/decode", 55600, 58050, 24, 23, {"rows": 1}),
        ("serving/step", 55500, 58100, 23, None, {}),
    ]
    return [Span(name, 1, int(T0 * 1e9) + a, int(T0 * 1e9) + b, 0, i, ident,
                 parent, None, args, name == "serving/empty")
            for i, (name, a, b, ident, parent, args) in enumerate(rows)]


def make_run(ring, seq=True, offset=0):
    """The run the readers see; `offset` ns moves every span on the host's
    clock against the device's (the window map does not see it)."""
    programs, ops = device()
    trace = tr.clip(tr.Trace(
        ops={0: [(n, W + a, b - a) for n, a, b in ops]},
        modules={0: [(n, W + a, b - a) for n, a, b in programs]},
        window=(W, W + LENGTH)))
    spans = host(seq)
    for s in spans:
        s.start_ns += offset
        s.end_ns += offset
    ring(spans)
    return types.SimpleNamespace(trace=trace, counters={
        "trace_t0": T0, "trace_t1": T0 + LENGTH / 1e9})


def test_programs_pair_with_their_dispatch_by_order(ring):
    run = make_run(ring)
    fit = program_pairs.fit(run, 0)
    # D0 was dispatched before the window: no pair, dispatched before it
    assert fit.kinds[0] == "_decode_step" and fit.ready[0] == -float("inf")
    assert fit.pairs == {"_decode_step": 4, "_serving_prefill_s16": 1,
                         "_serving_write_pages": 2}
    # D3 starts 300 ns after its upload began and ends 300 before its fetch
    # ends (the prefill too): the offset lies in [-300, 300]
    assert (fit.lo, fit.hi, fit.delta, fit.broken) == (-300, 300, 0, False)
    assert fit.ready[1] == W + 2600 and fit.ready[7] == W + 57900
    # the nearest fetch end pairs D2 with the fetch that landed D1: the old
    # check reads the clocks 900 ns apart where they are not
    assert serving_idle.read(run, "clock_slack_us") == pytest.approx(-0.9)
    assert idle_split.read(run, "clock_bound_us") == pytest.approx(0.6)


def test_the_four_classes_add_up_to_the_idle_time(ring, capsys):
    run = make_run(ring)
    table, counts = idle_split.split(run)
    got = {cls: {row: v[0] * 1e9 for row, v in rows.items()}
           for cls, rows in table.items()}
    approx = lambda d: pytest.approx(d, abs=1e-3)
    assert got["pause"] == approx({"_decode_step before fusion": 500,
                                   "_decode_step before apex_gmm": 200})
    assert got["launch"] == approx({"before _decode_step": 1500,
                                    "before _serving_prefill_s16": 300})
    assert got["host"] == approx({
        "_no_span_": 3550, "serving/step": 2200,
        "serving/decode_upload": 7400, "serving/decode": 1150,
        "serving/prefill_dispatch": 2100, "serving/decode_fetch": 200,
        "serving/decode_bookkeep": 100})
    assert got["empty"] == approx({"serving/empty": 8000})
    idle = tr.idle_share(run.trace) * run.trace.window_s * 1e9
    assert idle == pytest.approx(27200)
    assert sum(sum(rows.values()) for rows in got.values()) \
        == pytest.approx(idle, abs=1e-3)
    assert counts["decode_programs"] == 5
    printed = capsys.readouterr().err
    assert "0.0000% of it" in printed and "width 0.6 us" in printed
    # worked out once: the second reading prints nothing
    idle_split.read(run, "host_wait_share")
    assert capsys.readouterr().err == ""


@pytest.mark.parametrize("what,value", [
    ("pause_ms_per_decode_step", 700 / 5 / 1e6),
    ("host_wait_ms_per_decode_step", (1150 + 7400 + 200 + 100) / 4 / 1e6),
    ("host_wait_ms_per_admission", 2100 / 1e6),
    ("host_wait_share", 100 * 16700 / LENGTH),
    ("clock_bound_us", 0.6),
])
def test_the_readings(ring, what, value):
    assert idle_split.read(make_run(ring), what) == pytest.approx(value)


def test_an_offset_between_the_clocks_is_found_and_taken_out(ring):
    """The host's clock 200 ns behind the device's beyond what the window
    map knows: the pairs find it, and the split is the one above."""
    run = make_run(ring, offset=-200)
    fit = program_pairs.fit(run, 0)
    assert (fit.lo, fit.hi, fit.delta) == (-100, 500, 200)
    table, _ = idle_split.split(run)
    assert table["empty"]["serving/empty"][0] == pytest.approx(8e-6)
    assert table["launch"]["before _decode_step"][0] == pytest.approx(1.5e-6)


def test_a_pair_that_breaks_causality_gives_no_bound(ring, capsys):
    """D3's fetch made to end 400 ns before D3 does, where D3 starts 300 ns
    after its upload began: no offset satisfies both. The pairing stays by
    order (a shift of one step would read a whole step off the map)."""
    run = make_run(ring)
    spans = set_tracer(None).spans
    for s in spans:
        if s.id == 21:                      # D3's landing, 700 ns early
            s.start_ns -= 700
            s.end_ns -= 700
    set_tracer(Ring(spans))
    fit = program_pairs.fit(run, 0)
    assert fit.broken and (fit.lo, fit.hi) == (400, 300)
    assert fit.pairs["_decode_step"] == 4
    assert idle_split.read(run, "clock_bound_us") is None
    assert "causality broken" in capsys.readouterr().err
    assert idle_split.read(run, "host_wait_share") is not None


def test_without_program_seq_or_a_trace_nothing_is_read(ring):
    run = make_run(ring, seq=False)
    assert program_pairs.fit(run, 0) is None
    for what in ("pause_ms_per_decode_step", "host_wait_share",
                 "clock_bound_us"):
        assert idle_split.read(run, what) is None
    blank = types.SimpleNamespace(trace=tr.Trace(window=(0, 1)),
                                  counters={"trace_t0": T0,
                                            "trace_t1": T0 + 1e-9})
    assert idle_split.read(blank, "host_wait_share") is None
    with pytest.raises(ValueError):
        idle_split.read(make_run(ring), "nothing")


def simulate(seed, steps=300):
    """An engine and a device in lockstep, one decode step in flight: the
    host dispatches step k, lands step k-1, pauses at random, admits now and
    then (a prefill, its two page writes, its blocking fetch) and runs dry
    once; the device runs what was queued in order, each program two ops
    with a pause between. Returns the trace, the ring's spans (host clock =
    trace clock - W + T0) and what the split should find."""
    import random

    rng = random.Random(seed)
    rows, programs, ops = [], [], []
    ids = iter(range(1, 10 ** 6))
    state = {"t": 0, "free": 0, "pause": 0, "empty": 0}

    def span(name, a, b, parent, **args):
        ident = next(ids)
        rows.append((name, a, b, ident, parent, args))
        return ident

    def queue(name, at):
        start = max(state["free"], at + rng.randint(20, 80))
        first = rng.randint(2000, 9000)
        gap = rng.randint(0, 300)
        end = start + first + gap + rng.randint(2000, 9000)
        programs.append((name, start, end))
        ops.extend([("fusion.1", start, start + first),
                    ("fusion.2", start + first + gap, end)])
        state["free"], state["pause"] = end, state["pause"] + gap
        return end

    seq = {"d": 0, "p": 0, "w": 0}
    unlanded = None
    for k in range(steps):
        t = state["t"]
        step = span("serving/step", t, None, None)
        t += rng.randint(5, 50)
        if k % 23 == 5:                     # an admission
            admit = span("serving/admit", t, None, step, rows=1)
            a, t = t, t + rng.randint(200, 900)
            span("serving/prefill_dispatch", a, t, admit, bucket=64,
                 program_seq=seq["p"])
            p_end = queue("jit__serving_prefill_s64(1)", t)
            seq["p"] += 1
            a, t = t, t + rng.randint(50, 200)
            span("serving/write_prompt", a, t, admit, program_seq=seq["w"])
            queue("jit__serving_write_pages(2)", t)
            queue("jit__serving_write_pages(2)", t)
            seq["w"] += 2
            a, t = t, max(t, p_end) + rng.randint(100, 600)
            span("serving/first_token_fetch", a, t, admit)
            rows[[r[3] for r in rows].index(admit)] = (
                "serving/admit", rows[-4][1], t, admit, step, {"rows": 1})
        if k == steps // 2:                  # the batch runs dry
            if unlanded is not None:
                a, t = t, max(t, unlanded) + rng.randint(100, 600)
                span("serving/decode_fetch", a, t, step)
                unlanded = None
            rows[[r[3] for r in rows].index(step)] = (
                "serving/step", state["t"], t + 10, step, None, {})
            dry = t + 5
            t += rng.randint(20000, 60000)
            span("serving/empty", dry, t, None)
            state["empty"] += t - max(dry, state["free"])
            state["t"] = t + 5
            continue
        decode = span("serving/decode", t, None, step)
        a, t = t, t + rng.randint(300, 2500)
        span("serving/decode_upload", a, t, decode, program_seq=seq["d"])
        d_end = queue("jit__decode_step(9)", t)
        seq["d"] += 1
        if unlanded is not None:
            a, t = t, max(t, unlanded) + rng.randint(100, 600)
            span("serving/decode_fetch", a, t, decode)
        unlanded = d_end
        a, t = t, t + rng.randint(20, 80)
        span("serving/decode_bookkeep", a, t, decode)
        for i, r in enumerate(rows):
            if r[3] in (decode, step):
                rows[i] = (r[0], r[1], t + (r[3] == step) * 5, r[3], r[4],
                           r[5])
        t += 5 + (rng.randint(3000, 30000) if rng.random() < 0.05 else 0)
        state["t"] = t
    return rows, programs, ops, state


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_long_simulated_run_pairs_every_program_and_adds_up(ring, seed,
                                                              capsys):
    rows, programs, ops, state = simulate(seed)
    lo = programs[3][1] - 100               # the window cuts a program
    hi = programs[-4][2] - 50               # at each end
    trace = tr.clip(tr.Trace(
        ops={0: [(n, W + a - lo, b - a) for n, a, b in ops]},
        modules={0: [(n, W + a - lo, b - a) for n, a, b in programs]},
        window=(W, W + hi - lo)))
    ring([Span(name, 1, int(T0 * 1e9) + a - lo, int(T0 * 1e9) + b - lo, 0,
               i, ident, parent, None, args, name == "serving/empty")
          for i, (name, a, b, ident, parent, args) in enumerate(rows)])
    run = types.SimpleNamespace(trace=trace, counters={
        "trace_t0": T0, "trace_t1": T0 + (hi - lo) / 1e9})
    fit = program_pairs.fit(run, 0)
    assert not fit.broken and fit.lo <= 0 <= fit.hi
    inside = [p for p in programs if p[1] >= lo and p[2] <= hi]
    decodes = sum(p[0].startswith("jit__decode") for p in inside)
    assert fit.pairs["_decode_step"] >= decodes
    assert all(r is not None for r in fit.ready)
    table, counts = idle_split.split(run)
    idle = tr.idle_share(trace) * trace.window_s
    total = sum(v[0] for rows_ in table.values() for v in rows_.values())
    assert total == pytest.approx(idle, rel=1e-9)
    assert table["empty"]["serving/empty"][0] * 1e9 == pytest.approx(
        state["empty"], abs=fit.hi - fit.lo + 1)
    pause = sum(v[0] for v in table["pause"].values()) * 1e9
    assert 0 < pause <= state["pause"]
    assert 0 <= idle_split.read(run, "host_wait_share") < 100
