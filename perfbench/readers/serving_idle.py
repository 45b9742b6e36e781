"""The device's idle time by what the serving engine was doing, and the check
on the clock that booking leans on. Every instant of the traced window's idle
gaps goes to the innermost program span (`serving/*`, from the program's
ring) open on the host then, on the trace's clock (`program_ring`). Nothing
without a device trace, without such spans, or where the ring overflowed.

- `per_admission`: idle milliseconds booked to `serving/admit` or a child of
  it, per admission that ended in the traced window.
- `per_decode_step`: the same under `serving/decode` and its children, per
  decode step.
- `clock_slack_us`: every `_decode_step` program of the trace is paired with
  the `serving/decode_fetch` span that ended nearest its end; the smallest,
  over those pairs, of (the fetch returns on the host, mapped onto the
  trace's clock) - (the program ends on the device), in microseconds. The
  tokens cannot be on the host before the device has made them: a sound map
  reads a small positive number (the copy of the tokens), a negative one says
  the clocks disagree by at least that much. Printed beside it: the same
  bound from the other side (a program cannot start on the device before the
  `serving/decode_upload` that dispatches it began on the host), and by how
  much the two anchors disagree. A constant offset between the clocks lies
  between minus the one slack and plus the other.
"""

import bisect
import sys

from perfbench.readers import program_ring

PROGRAM = "_decode_step"


def _idle_ms_per(run, names, count_name):
    table = program_ring.idle_by_program_span(run)
    if not table or count_name not in table:
        return None
    return 1e3 * sum(table[name][0] for name in names if name in table) \
        / table[count_name][2]


def _clock_slack_us(run):
    spans = program_ring.traced_spans(run)
    if not spans or not run.trace.modules:
        return None
    lo, hi = run.trace.window
    programs = sorted(
        (start, start + dur) for events in run.trace.modules.values()
        for name, start, dur in events
        if name.startswith((PROGRAM, "jit_" + PROGRAM))
        and start > lo and start + dur < hi)      # not cut by the window
    fetched = _slacks([end for _, end in programs], sorted(
        end for name, _, end, _, _ in spans
        if name == "serving/decode_fetch"))
    if not fetched:
        return None
    dispatched = _slacks(sorted(
        start for name, start, _, _, _ in spans
        if name == "serving/decode_upload"), [start for start, _ in programs])
    print(f"clock: {len(fetched)} decode programs paired with their fetch: "
          f"the fetch returns after the program ends by {_least(fetched)}; "
          f"{len(dispatched)} paired with their upload: the program starts "
          f"after the upload begins by {_least(dispatched)}; the two anchors "
          f"disagree by {program_ring.clock_map(run)[1] / 1e3:+.1f} us over "
          f"{run.trace.window_s:.3f} s", file=sys.stderr)
    return min(fetched) / 1e3


def _slacks(earlier, later):
    """For each of `earlier` (sorted nanoseconds) the nearest of `later`, as
    later - earlier; pairs further apart than half the least distance between
    two of `earlier` are left out (programs run one after another, so a
    nearer one can belong to one program only)."""
    if not earlier or not later:
        return []
    reach = min((b - a for a, b in zip(earlier, earlier[1:])),
                default=float("inf")) / 2
    out = []
    for t in earlier:
        i = bisect.bisect_left(later, t)
        near = min(later[max(0, i - 1):i + 1], key=lambda x: abs(x - t))
        if abs(near - t) < reach:
            out.append(near - t)
    return out


def _least(slacks):
    if not slacks:
        return "(none)"
    return (f"{min(slacks) / 1e3:.1f} us at least, "
            f"{sorted(slacks)[len(slacks) // 2] / 1e3:.1f} us in the median")


def read(run, what):
    if what == "per_admission":
        return _idle_ms_per(run, program_ring.ADMIT, "serving/admit")
    if what == "per_decode_step":
        return _idle_ms_per(run, program_ring.DECODE, "serving/decode")
    if what == "clock_slack_us":
        return _clock_slack_us(run)
    raise ValueError(what)
