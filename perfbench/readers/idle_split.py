"""The device's idle time over the traced window split into four parts that
add up to it, every instant of every gap in exactly one:

- (a) `pause`: inside a running program, between its ops; booked to the
  program and the op that follows the pause (`_decode_step before fusion`).
- (b) `launch`: between programs, once the next program had been
  dispatched (its span paired by `program_seq`, `program_pairs`): the
  runtime's time; booked to the next program.
- (c) `host`: between programs, before the next one had been dispatched:
  the host's wait, booked to the innermost `serving/*` span open on the host
  then (`program_ring.NO_SPAN` where none was).
- (d) `empty`: between programs, inside a `serving/empty` record: the
  engine had nothing to run.

The host's spans are on the device's clock by the window map and the offset
that `program_pairs` fits. Worked out once a run and printed whole on
standard error then. Nothing without a device trace, without `serving/*`
spans, or where no span carries a `program_seq`.

Readings (`what`):
- `pause_ms_per_decode_step`: (a) inside `_decode_step`, milliseconds per
  `_decode_step` program in the trace.
- `host_wait_ms_per_decode_step`: (c) under `serving/decode` and its
  children (a step's landing under `serving/step` included), per
  `serving/decode` record.
- `host_wait_ms_per_admission`: (c) under `serving/admit` and its children,
  per admission; nothing where no admission ended in the window.
- `host_wait_share`: (c) over the traced seconds, in percent.
- `clock_bound_us`: the width of the offset interval every pair allows;
  nothing where a pair breaks causality (the pairs at the two ends printed).
"""

import bisect
import sys

from perfbench import trace_reduce
from perfbench.readers import program_pairs, program_ring

CLASSES = ("pause", "launch", "host", "empty")
TITLES = {"pause": "(a) the program's own pauses between its ops",
          "launch": "(b) launch gaps: the next program already dispatched",
          "host": "(c) the host's wait, by the innermost serving/* span",
          "empty": "(d) nothing to run (serving/empty)"}
ADMIT = program_ring.ADMIT + ("serving/write_state",)
EMPTY = "serving/empty"


def split(run):
    """({class: {row: [seconds, gaps]}}, counts) or None; seconds averaged
    over the devices."""
    if "idle_split" not in vars(run):
        run.idle_split = _split(run)
    return run.idle_split


def _intervals(rows):
    """Sorted starts, ends and payloads of intervals none of which overlaps
    another."""
    rows = sorted(rows)
    return [r[0] for r in rows], [r[1] for r in rows], [r[2] for r in rows]


def _cut(x, y, starts, ends):
    """(lo, hi, i) pieces that cover [x, y] in order: i the interval that
    holds the piece, or None."""
    i = max(0, bisect.bisect_right(starts, x) - 1)
    while x < y:
        while i < len(starts) and ends[i] <= x:
            i += 1
        if i < len(starts) and starts[i] <= x:
            z = min(y, ends[i])
            yield x, z, i
        else:
            z = min(y, starts[i]) if i < len(starts) else y
            yield x, z, None
        x = z


def _empty_records(run, to_device):
    stop = "t_end" if "t_end" in run.counters else "trace_t1"
    spans = program_ring.ended_between(run, "trace_t0", stop) or []
    return [(to_device(s.start_ns), to_device(s.end_ns), EMPTY)
            for s in spans if s.name == EMPTY]


def _split(run):
    trace = run.trace
    spans = program_ring.traced_spans(run)
    if trace is None or not trace.ops or not spans:
        return None
    to_trace = program_ring.clock_map(run)[0]
    table = {c: {} for c in CLASSES}
    n, stems, first = len(trace.ops), {}, None

    def book(cls, row, ns):
        entry = table[cls].setdefault(row, [0.0, 0])
        entry[0] += ns / 1e9 / n
        entry[1] += 1

    for device in trace.ops:
        fit = program_pairs.fit(run, device)
        if fit is None:
            return None
        first = first or fit
        modules = sorted(trace.modules.get(device, []), key=lambda m: m[1])
        m_starts = [s for _, s, _ in modules]
        m_ends = [s + d for _, s, d in modules]
        ops = sorted((s, name) for name, s, _ in trace.ops[device])
        op_starts = [s for s, _ in ops]
        segs = _intervals((a + fit.delta, b + fit.delta, name) for a, b, name
                          in program_ring.innermost_segments(spans))
        empty = _intervals(_empty_records(
            run, lambda ns: to_trace(ns) + fit.delta))

        def stem_at(t, j):
            k = bisect.bisect_left(op_starts, t)
            if k == len(ops) or ops[k][0] >= m_ends[j]:
                return "(program end)"
            name = ops[k][1]
            if name not in stems:
                stems[name] = trace_reduce.op_stem(name)
            return stems[name]

        def host(x, y):
            for lo, hi, i in _cut(x, y, *segs[:2]):
                book("host", program_ring.NO_SPAN if i is None
                     else segs[2][i], hi - lo)

        def between(x, y, j):
            ready = None if j is None else fit.ready[j]
            for lo, hi, i in _cut(x, y, *empty[:2]):
                if i is not None:
                    book("empty", EMPTY, hi - lo)
                    continue
                mid = hi if ready is None else min(hi, max(lo, ready))
                if mid > lo:
                    host(lo, mid)
                if hi > mid:
                    book("launch", "before " + fit.kinds[j], hi - mid)

        for a, b in trace_reduce.gaps(trace, device):
            for lo, hi, j in _cut(a, b, m_starts, m_ends):
                if j is not None:
                    book("pause", f"{fit.kinds[j]} before {stem_at(hi, j)}",
                         hi - lo)
                else:
                    nxt = bisect.bisect_left(m_starts, hi)
                    between(lo, hi, nxt if nxt < len(modules) else None)

    counts = {
        "decode_programs": sum(k == program_pairs.DECODE
                               for k in first.kinds),
        "decode_records": sum(s[0] == "serving/decode" for s in spans),
        "admissions": sum(s[0] == "serving/admit" for s in spans),
        "fit": first}
    _print(run, table, counts)
    return table, counts


def _print(run, table, counts):
    idle = trace_reduce.idle_share(run.trace) * run.trace.window_s
    booked = sum(v[0] for rows in table.values() for v in rows.values())
    print("device idle split (class, seconds, gaps; leading rows):",
          file=sys.stderr)
    for cls in CLASSES:
        rows = sorted(table[cls].items(), key=lambda kv: -kv[1][0])
        seconds = sum(v[0] for _, v in rows)
        gaps = sum(v[1] for _, v in rows)
        print(f"  {TITLES[cls]}: {seconds:.6f} s in {gaps} gaps", file=sys.stderr)
        for row, (s, g) in rows[:6]:
            print(f"    {row} {s:.6f} {g}", file=sys.stderr)
    share = 100 * abs(booked - idle) / idle if idle else 0.0
    print(f"  all four {booked:.6f}; device_idle_share x traced seconds "
          f"{idle:.6f}; residual {booked - idle:+.9f} s, {share:.4f}% of it",
          file=sys.stderr)
    fit = counts["fit"]
    paired = ", ".join(f"{k} {v}" for k, v in sorted(fit.pairs.items()))
    print(f"clock by program_seq: pairs {paired}; offset {fit.delta / 1e3:+.1f}"
          f" us from the window map, allowed [{fit.lo / 1e3:+.1f}, "
          f"{fit.hi / 1e3:+.1f}] us, width {(fit.hi - fit.lo) / 1e3:.1f} us",
          file=sys.stderr)
    for end, pair in fit.offenders.items():
        print(f"  causality broken: the pair that sets {end} is {pair[0]} "
              f"#{pair[1]} at {pair[2]} against its host stamp {pair[3]:.0f}",
              file=sys.stderr)


def read(run, what):
    got = split(run)
    if got is None:
        return None
    table, counts = got
    host = table["host"]
    if what == "pause_ms_per_decode_step":
        pause = sum(v[0] for row, v in table["pause"].items()
                    if row.startswith(program_pairs.DECODE + " "))
        n = counts["decode_programs"]
        return 1e3 * pause / n if n else None
    if what == "host_wait_ms_per_decode_step":
        n = counts["decode_records"]
        return 1e3 * sum(host[r][0] for r in program_ring.DECODE
                         if r in host) / n if n else None
    if what == "host_wait_ms_per_admission":
        n = counts["admissions"]
        return 1e3 * sum(host[r][0] for r in ADMIT if r in host) / n \
            if n else None
    if what == "host_wait_share":
        return 100.0 * sum(v[0] for v in host.values()) / run.trace.window_s
    if what == "clock_bound_us":
        fit = counts["fit"]
        return None if fit.broken or abs(fit.hi - fit.lo) == float("inf") \
            else (fit.hi - fit.lo) / 1e3
    raise ValueError(what)
