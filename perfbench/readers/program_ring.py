"""What the serving readers share: the program's own span ring
(`apex_tpu.observability.get_tracer()`: `serving/*` spans with a request id
and integer fields, on `time.monotonic_ns`, the clock `harness.py` stamps
`t_open`, `t_close`, `trace_t0` and `trace_t1` with), the map from that clock
onto the device trace's, and the device's idle time booked to program spans.

The two clocks meet at two anchors the harness leaves: the `bench:window`
annotation (`run.trace.window`, on the trace's clock) is entered just before
`trace_t0` is read and left just before `trace_t1` is. The map is the line
through both; by how much the two anchors disagree about the window's length
is printed beside the metrics that lean on it. Not a reader itself.
"""

import bisect
import sys

from perfbench import trace_reduce

NO_SPAN = "_no_span_"
ADMIT = ("serving/admit", "serving/prefill_dispatch", "serving/write_prompt",
         "serving/first_token_fetch")
DECODE = ("serving/decode", "serving/decode_upload", "serving/decode_fetch",
          "serving/decode_bookkeep")


def ended_between(run, lo_key, hi_key):
    """The ring's records that ended between two of the run's stamps (host
    seconds), oldest first; None where a stamp is missing, or where the ring
    has overwritten records that may have ended after the first stamp."""
    lo, hi = run.counters.get(lo_key), run.counters.get(hi_key)
    if lo is None or hi is None:
        return None
    from apex_tpu.observability import get_tracer

    tracer = get_tracer()
    spans = tracer.completed()
    if tracer.dropped() and (not spans or spans[0].end_ns >= lo * 1e9):
        return None
    return [s for s in spans if lo * 1e9 <= s.end_ns <= hi * 1e9]


def named(spans, name):
    return [s for s in spans or () if s.name == name]


def clock_map(run):
    """(host nanoseconds -> trace nanoseconds, anchors' disagreement in
    nanoseconds): the line through (`trace_t0`, window start) and
    (`trace_t1`, window end). None without a trace or either stamp."""
    t0, t1 = run.counters.get("trace_t0"), run.counters.get("trace_t1")
    if run.trace is None or t0 is None or t1 is None or t1 <= t0:
        return None
    w0, w1 = run.trace.window
    h0, h1 = t0 * 1e9, t1 * 1e9
    slope = (w1 - w0) / (h1 - h0)
    return (lambda ns: w0 + (ns - h0) * slope), (w1 - w0) - (h1 - h0)


def traced_spans(run):
    """The `serving/*` spans of the program's stack (not the detached
    per-request records) that ended in the traced window, as
    (name, start, end, id, parent) on the trace's clock."""
    mapping = clock_map(run)
    spans = ended_between(run, "trace_t0", "trace_t1")
    if mapping is None or spans is None:
        return None
    to_trace = mapping[0]
    return [(s.name, to_trace(s.start_ns), to_trace(s.end_ns), s.id, s.parent)
            for s in spans if s.name.startswith("serving/")
            and not s.detached]


def innermost_segments(spans):
    """(start, end, name) pieces in order of time, none overlapping another:
    at every instant some span covers, the innermost span open there. Spans
    nest (one thread's stack), so a sweep with a stack of the open ones does
    it. Time that no span covers is in no piece."""
    pieces, open_, at = [], [], None

    def close(upto):
        nonlocal at
        while open_ and open_[-1][0] <= upto:
            end, name = open_.pop()
            if end > at:
                pieces.append((at, end, name))
                at = end

    for name, start, end, _, _ in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(start)
        if open_ and start > at:
            pieces.append((at, start, open_[-1][1]))
        at = start
        open_.append((end, name))
    close(float("inf"))
    return pieces


def idle_by_program_span(run):
    """{span name: [idle seconds, gaps, spans]} over the traced window,
    averaged over the devices: every instant of a device's idle gaps booked
    to the innermost `serving/*` span open on the host then (`NO_SPAN` where
    none was), so the rows add up to the device's idle time; `gaps` counts
    the gaps, or parts of gaps, that fell to the row, `spans` the spans of
    that name that ended in the traced window. A gap is split where the
    host went from one span to the next: the gap between two decode programs
    begins under one step's fetch and ends under the next step's upload, and
    no one span covers most of it but the steps themselves. None without
    device operations in the trace or without `serving/*` spans in the ring.
    Worked out once a run, and printed whole on standard error then:
    PERF.md's section 5 is written from it."""
    if "program_idle" not in vars(run):
        run.program_idle = _idle_by_program_span(run)
    return run.program_idle


def _idle_by_program_span(run):
    spans = traced_spans(run)
    if not spans or not run.trace.ops:
        return None
    pieces = innermost_segments(spans)
    starts = [p[0] for p in pieces]
    table, n = {}, len(run.trace.ops)
    for name, *_ in spans:
        table.setdefault(name, [0.0, 0, 0])[2] += 1

    def book(name, ns):
        row = table.setdefault(name, [0.0, 0, 0])
        row[0] += ns / 1e9 / n
        row[1] += 1

    for device in run.trace.ops:
        for a, b in trace_reduce.gaps(run.trace, device):
            left = b - a
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(pieces) and pieces[i][0] < b:
                overlap = min(b, pieces[i][1]) - max(a, pieces[i][0])
                if overlap > 0:
                    book(pieces[i][2], overlap)
                    left -= overlap
                i += 1
            if left > 0:
                book(NO_SPAN, left)
    idle = trace_reduce.idle_share(run.trace) * run.trace.window_s
    booked = sum(row[0] for row in table.values())
    print("device idle by program span (span, idle seconds, gaps, spans):",
          file=sys.stderr)
    for name, (seconds, gaps, count) in sorted(table.items(),
                                               key=lambda kv: -kv[1][0]):
        print(f"  {name} {seconds:.6f} {gaps} {count}", file=sys.stderr)
    print(f"  all rows {booked:.6f}; device_idle_share x traced seconds "
          f"{idle:.6f}; residual {booked - idle:+.6f}", file=sys.stderr)
    return table
