"""From a profiler trace (`*.xplane.pb`) to what the per-layer readers read.

A trace has a plane for each device (`/device:TPU:<n>`) with a line of leaf
operations ("XLA Ops", named by their HLO text, `%fusion.12 = ...`) and a line
of whole programs ("XLA Modules", `jit_step(<hash>)`), and a host plane whose
thread lines hold the benchmark's `jax.profiler.TraceAnnotation` spans
(`bench:*`). Everything is on one clock, in nanoseconds.

Busy time is the union of the leaf operations' intervals on a device; idle is
the rest of the window, each gap booked to the `bench:` span that covers most
of it. Loops and calls (`while`, `conditional`, `call`) are containers, not
work, and are left out so that a loop does not hide the gaps inside it.
"""

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench:window"
_CONTAINERS = ("while", "conditional", "call")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_OP_NAME = re.compile(r"^%?([^\s=]+)")


def op_name(event_name):
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return _OP_NAME.match(event_name).group(1)


def op_stem(name):
    """`fusion.12` -> `fusion`, `apex_flash_fwd.3` -> `apex_flash_fwd`."""
    return re.sub(r"\.\d+$", "", name)


def is_container(name):
    return op_stem(name).split("-")[0] in _CONTAINERS


def is_collective(name):
    return any(op_stem(name).startswith(c) for c in _COLLECTIVES)


@dataclass
class Trace:
    """Events as (name, start_ns, duration_ns) tuples."""
    ops: dict = field(default_factory=dict)        # device id -> leaf ops
    modules: dict = field(default_factory=dict)    # device id -> programs
    async_ops: dict = field(default_factory=dict)  # device id -> async ops
    spans: list = field(default_factory=list)      # host `bench:` spans
    window: tuple = None                           # (start_ns, end_ns)

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    """Read an `.xplane.pb`, keep what falls inside the `bench:window` span
    (or, where no such span was recorded, between the first and the last
    device operation)."""
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        device = re.match(r"^/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if device and line.name in ("XLA Ops", "XLA Modules",
                                        "Async XLA Ops"):
                events = [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events]
                if line.name == "Async XLA Ops":
                    trace.async_ops[int(device.group(1))] = [
                        (op_name(n), s, d) for n, s, d in events]
                elif line.name == "XLA Ops":
                    events = [(op_name(n), s, d) for n, s, d in events]
                    events = [e for e in events if not is_container(e[0])]
                    trace.ops[int(device.group(1))] = events
                else:
                    trace.modules[int(device.group(1))] = events
            elif plane.name.startswith("/host:"):
                trace.spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events
                                if e.name.startswith("bench:")]
    windows = [s for s in trace.spans if s[0] == WINDOW_SPAN]
    trace.spans = [s for s in trace.spans if s[0] != WINDOW_SPAN]
    if windows:
        trace.window = (windows[0][1], windows[0][1] + windows[0][2])
    else:
        every = [e for ops in trace.ops.values() for e in ops]
        trace.window = (min(e[1] for e in every),
                        max(e[1] + e[2] for e in every))
    return clip(trace)


def clip(trace):
    """Cut every event to the window; drop what lies outside."""
    lo, hi = trace.window

    def cut(events):
        out = []
        for name, start, dur in events:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                out.append((name, a, b - a))
        return out

    trace.ops = {d: cut(e) for d, e in trace.ops.items()}
    trace.modules = {d: cut(e) for d, e in trace.modules.items()}
    trace.async_ops = {d: cut(e) for d, e in trace.async_ops.items()}
    trace.spans = cut(trace.spans)
    return trace


def union(intervals):
    """Merged (start, end) intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _length(intervals):
    return sum(b - a for a, b in intervals)


def busy_intervals(events):
    return union((s, s + d) for _, s, d in events)


def busy_s(trace):
    """Seconds in which an operation ran, averaged over the devices used."""
    if not trace.ops:
        return 0.0
    return sum(_length(busy_intervals(ops))
               for ops in trace.ops.values()) / len(trace.ops) / 1e9


def idle_share(trace):
    return 1.0 - busy_s(trace) / trace.window_s


def gaps(trace, device):
    """The idle (start, end) intervals of one device inside the window."""
    lo, hi = trace.window
    out, at = [], lo
    for a, b in busy_intervals(trace.ops.get(device, [])):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _covering_span(spans, a, b):
    """The `bench:` span that overlaps (a, b) most; of equals, the shortest,
    which is the innermost."""
    best, best_key = "_no_span_", (0, 0)
    for name, start, dur in spans:
        overlap = min(b, start + dur) - max(a, start)
        if overlap > 0 and (overlap, -dur) > best_key:
            best, best_key = name, (overlap, -dur)
    return best


def idle_by_span(trace):
    """Idle seconds by the host span that covers each gap, averaged over the
    devices, largest first."""
    totals = {}
    spans = sorted(trace.spans, key=lambda s: s[1])
    for device in trace.ops:
        for a, b in gaps(trace, device):
            name = _covering_span(spans, a, b)
            totals[name] = totals.get(name, 0.0) + (b - a) / 1e9
    n = max(1, len(trace.ops))
    return sorted(((k, v / n) for k, v in totals.items()),
                  key=lambda kv: -kv[1])


def op_seconds(trace):
    """Device seconds by operation name, averaged over the devices."""
    totals = {}
    for ops in trace.ops.values():
        for name, _, dur in ops:
            totals[name] = totals.get(name, 0.0) + dur / 1e9
    n = max(1, len(trace.ops))
    return sorted(((k, v / n) for k, v in totals.items()),
                  key=lambda kv: -kv[1])


def kernel_events(trace, stem):
    """Durations in seconds of every call of the kernel whose instruction
    name is `stem` or `stem.<n>`, on every device."""
    return [dur / 1e9 for ops in trace.ops.values()
            for name, _, dur in ops if op_stem(name) == stem]


def prefix_seconds(trace, prefix):
    """Device seconds of operations whose name starts with `prefix`, averaged
    over the devices."""
    total = sum(dur for ops in trace.ops.values()
                for name, _, dur in ops if name.startswith(prefix))
    return total / max(1, len(trace.ops)) / 1e9


def _is_program(name, prefix):
    return name.startswith(prefix) or name.startswith("jit_" + prefix)


def module_events(trace, prefix):
    """(name, seconds) of whole programs whose name starts with
    `jit_<prefix>` or `<prefix>`, on the first device that ran any."""
    for events in trace.modules.values():
        hits = [(name, dur / 1e9) for name, _, dur in events
                if _is_program(name, prefix)]
        if hits:
            return hits
    return []


def kernel_events_by_module(trace, stem, prefix):
    """For each run of a program `prefix*` on the first device that ran any:
    (program name, [seconds of each call of kernel `stem` inside it])."""
    for device, events in trace.modules.items():
        programs = [(n, s, s + d) for n, s, d in events
                    if _is_program(n, prefix)]
        if not programs:
            continue
        calls = sorted((s, d) for n, s, d in trace.ops.get(device, [])
                       if op_stem(n) == stem)
        out = []
        for name, a, b in programs:
            inside = [d / 1e9 for s, d in calls if a <= s < b]
            if inside:
                out.append((name, inside))
        return out
    return []


def collective_events(trace):
    """Every collective's (name, start, duration), synchronous or not."""
    return [e for group in (trace.ops, trace.async_ops)
            for ops in group.values() for e in ops if is_collective(e[0])]


def collective_exposed_s(trace):
    """Seconds, averaged over devices, in which a collective was under way
    on a device (from its start to its done, where it is asynchronous) and
    no other operation ran there."""
    total = 0
    for device, ops in trace.ops.items():
        coll = union((s, s + d) for n, s, d in
                     ops + trace.async_ops.get(device, [])
                     if is_collective(n))
        work = union((s, s + d) for n, s, d in ops if not is_collective(n))
        exposed = 0
        for a, b in coll:
            covered = 0
            for wa, wb in work:
                if wb <= a:
                    continue
                if wa >= b:
                    break
                covered += min(b, wb) - max(a, wa)
            exposed += (b - a) - covered
        total += exposed
    return total / max(1, len(trace.ops)) / 1e9


def breakdown(trace, top=10):
    return {"device_ops": [[k, v] for k, v in op_seconds(trace)[:top]],
            "idle_gaps": [[k, v] for k, v in idle_by_span(trace)[:top]]}
