"""Each serving program of a device trace paired with the span that
dispatched it, and the one offset between the device's clock and the host's
that every pair allows. Not a reader itself: `idle_split` reads it.

A span that dispatches a serving program carries `program_seq`, the ordinal
of that dispatch among the process's dispatches of the program:
`serving/decode_upload` one `_decode_step`, `serving/prefill_dispatch` one
`_serving_prefill_s<bucket>` (its `bucket` names the program),
`serving/write_prompt` two `_serving_write_pages` (K's, then V's),
`serving/write_state` one `_serving_write_state`. The device runs a kind's
programs in the order they were dispatched, so the i-th program of a kind in
the trace is the dispatch of ordinal i + c, for one shift c a kind: pairs by
order, not by nearest time. A program already running when the trace began
was dispatched before any span of the window and pairs with none; one cut by
the window's end is paired and bounds nothing on the side it was cut.

The host's spans are put on the trace's clock by the window map
(`program_ring.clock_map`). What is left is a constant offset d (device =
host + d), and every pair bounds it: a program cannot start before its
dispatch began (d <= start - dispatch start), and the fetch that lands its
output cannot end before it does (d >= end - fetch end; the decode step's and
the prefill's outputs are fetched). The shift of a kind with fetches is the
one under which the window map alone (offset 0) breaks the bounds least, by
ns (a shift one too far pairs a step with the next one's dispatch, begun a
fetch after the step started; one too short puts the fetch a whole step
early: both by about a step, where the map is off by microseconds); a kind
without fetches takes, of the shifts that the others' interval allows, the
one that pairs most, and of those the largest. The offset taken is the middle
of what every pair allows together, and the interval's width is how well the
clocks are known.
"""

from collections import namedtuple

from perfbench.readers import program_ring

# span -> (program it dispatches, programs a span dispatches); the prefill's
# program is named by the span's `bucket`
DISPATCH = {"serving/decode_upload": ("_decode_step", 1),
            "serving/prefill_dispatch": ("_serving_prefill_s", 1),
            "serving/write_prompt": ("_serving_write_pages", 2),
            "serving/write_state": ("_serving_write_state", 1)}
DECODE = "_decode_step"
INF = float("inf")

Dispatch = namedtuple("Dispatch", "start end landed")   # trace clock, ns
Fit = namedtuple("Fit", "delta lo hi broken ready kinds pairs offenders")


def program_kind(module):
    """`jit__decode_step(1234)` -> `_decode_step`."""
    name = module.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def dispatches(run):
    """{program: {program_seq: Dispatch}} from the spans that ended in the
    traced window, on the trace's clock (the window map only); None where
    the ring holds no span with a `program_seq`, as a program that records
    none, or where the map or the ring is missing."""
    mapping = program_ring.clock_map(run)
    spans = program_ring.ended_between(run, "trace_t0", "trace_t1")
    if mapping is None or not spans:
        return None
    to_trace = mapping[0]
    spans = sorted((s for s in spans if not s.detached),
                   key=lambda s: s.start_ns)
    out, prefills, landed, upload = {}, {}, {}, None
    for s in spans:
        if s.name in DISPATCH and "program_seq" in s.args:
            program, count = DISPATCH[s.name]
            if s.name == "serving/prefill_dispatch":
                program += str(s.args["bucket"])
                prefills[s.parent] = (program, s.args["program_seq"])
            table = out.setdefault(program, {})
            for k in range(count):
                table[s.args["program_seq"] + k] = [
                    to_trace(s.start_ns), to_trace(s.end_ns), None]
            if s.name == "serving/decode_upload":
                upload = s
        elif s.name == "serving/decode_fetch" and upload is not None:
            # a fetch beside an upload in one `serving/decode` lands the step
            # before that upload's; one under `serving/step` (the batch ran
            # dry) lands the newest step
            seq = upload.args["program_seq"] - (s.parent == upload.parent)
            landed[(DECODE, seq)] = to_trace(s.end_ns)
        elif s.name == "serving/first_token_fetch" and s.parent in prefills:
            landed[prefills[s.parent]] = to_trace(s.end_ns)
    if not out:
        return None
    for (program, seq), end in landed.items():
        if seq in out.get(program, {}):
            out[program][seq][2] = end
    return {p: {q: Dispatch(*d) for q, d in t.items()}
            for p, t in out.items()}


def _bounds(programs, table, c):
    """(lo, hi, pairs, lo's pair, hi's pair) of the offset under shift c."""
    lo, hi, n, at_lo, at_hi = -INF, INF, 0, None, None
    for i, (start, end, cut_start, cut_end) in enumerate(programs):
        d = table.get(i + c)
        if d is None:
            continue
        n += 1
        if not cut_start and start - d.start < hi:
            hi, at_hi = start - d.start, (i + c, start, d.start)
        if d.landed is not None and not cut_end and end - d.landed > lo:
            lo, at_lo = end - d.landed, (i + c, end, d.landed)
    return lo, hi, n, at_lo, at_hi


def _candidates(programs, table):
    """_bounds of every shift that pairs nearly as many as the best one (a
    program in flight at either end of the window leaves at most one pair
    out of the right shift that its neighbours pair)."""
    seqs = sorted(table)
    out = [(c,) + _bounds(programs, table, c)
           for c in range(seqs[0] - len(programs) + 1, seqs[-1] + 1)]
    most = max(x[3] for x in out)
    return [x for x in out if x[3] >= max(1, most - 2)]


def _violation(lo, hi):
    """By how much the window map alone (offset 0) breaks the bounds."""
    return max(lo, 0) + max(-hi, 0)


def fit(run, device):
    """The pairs of one device's trace: a `Fit` whose `delta` is the offset
    (ns), [`lo`, `hi`] what every pair allows (`broken` where that is empty),
    `ready[j]` the time on the device's clock by which the device's j-th
    program (modules in order of start) had been dispatched (the end of its
    span; -inf where it was dispatched before the window's spans; None where
    no span is known for it), `kinds[j]` its program, `pairs` the pairs by
    program and `offenders` the pairs that set the two ends. None where no
    span carries a `program_seq` or the device ran no program."""
    table = dispatches(run)
    modules = sorted(run.trace.modules.get(device, []), key=lambda m: m[1])
    if table is None or not modules:
        return None
    w0, w1 = run.trace.window
    kinds = [program_kind(name) for name, _, _ in modules]
    by_kind = {}
    for j, (kind, (_, start, dur)) in enumerate(zip(kinds, modules)):
        by_kind.setdefault(kind, []).append(
            (j, (start, start + dur, start <= w0, start + dur >= w1)))
    shifts, lo, hi, ends = {}, -INF, INF, {}
    fetched = [k for k in table if k in by_kind and any(
        d.landed is not None for d in table[k].values())]
    for kind in fetched:
        shifts[kind] = min(
            _candidates([p for _, p in by_kind[kind]], table[kind]),
            key=lambda x: (_violation(x[1], x[2]), -x[3]))
    for kind, x in shifts.items():
        if x[1] > lo:
            lo, ends["lo"] = x[1], (kind,) + x[4]
        if x[2] < hi:
            hi, ends["hi"] = x[2], (kind,) + x[5]
    for kind in table:
        if kind in by_kind and kind not in shifts:
            cands = _candidates([p for _, p in by_kind[kind]], table[kind])
            causal = [x for x in cands if x[2] >= lo]
            shifts[kind] = max(causal, key=lambda x: (x[3], x[0])) if causal \
                else max(cands, key=lambda x: x[2])
            if shifts[kind][2] < hi:
                hi, ends["hi"] = shifts[kind][2], (kind,) + shifts[kind][5]
    delta = (lo + hi) / 2 if abs(lo) < INF and abs(hi) < INF \
        else lo if abs(lo) < INF else hi if abs(hi) < INF else 0.0
    ready, pairs = [None] * len(modules), {}
    for kind, (c, *_) in shifts.items():
        first = min(table[kind])
        for i, (j, _) in enumerate(by_kind[kind]):
            d = table[kind].get(i + c)
            if d is not None:
                ready[j] = d.end + delta
                pairs[kind] = pairs.get(kind, 0) + 1
            elif i + c < first:
                ready[j] = -INF
    return Fit(delta, lo, hi, lo > hi, ready, kinds, pairs,
               ends if lo > hi else {})
