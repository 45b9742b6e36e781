"""Hierarchical host-side span tracer (ISSUE 7 tentpole piece 1).

One :func:`span` context manager does three things at once:

- records a (name, thread, start, end, depth, id, parent, request id,
  integer fields) entry into a fixed-size **ring buffer** on the process
  tracer — always on and thread-safe (slots are preallocated lists
  mutated in place), so production steps can stay instrumented;
- keeps a per-thread stack of **open** spans, which is what the flight
  recorder snapshots when a step hangs (a completed-spans-only log says
  nothing about *where* a stuck step is stuck);
- enters the existing :func:`apex_tpu.observability.scope` pair
  (``TraceAnnotation`` for the live ``jax.profiler`` host timeline,
  ``named_scope`` for HLO metadata), so the one call site feeds the
  ring buffer, the xplane capture AND the compiled program's op names.

:func:`host_span` is the same entry for call sites that build no HLO
(the serving engine's host loop): ring + ``TraceAnnotation``, no
``named_scope``. Code that runs under ``jit`` uses the bare
:func:`~apex_tpu.observability.scope` instead: there the host clock
would time the trace, once, and never a step.
:meth:`SpanTracer.record` commits a span from stamps already taken (a
queue wait begins in ``submit`` and ends steps later).

The ring exports as Chrome/Perfetto **trace-event JSON** (``B``/``E``
duration events plus ``M`` thread-name metadata) — load the file at
``ui.perfetto.dev`` or ``chrome://tracing``. ``python -m
apex_tpu.observability trace`` wraps the export for saved dumps and
xplane captures.

Clock: ``time.monotonic_ns`` (this module lives under observability/,
one of the sanctioned raw-clock owners). Span times are HOST times —
device work launched inside a span completes asynchronously; device
attribution comes from :mod:`~apex_tpu.observability.profiling.xplane`.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from typing import List, Optional

__all__ = [
    "Span", "SpanTracer", "span", "host_span", "get_tracer",
    "set_tracer", "to_trace_events", "write_chrome_trace", "load_spans",
    "spans_from_dicts",
]

# ring slot layout (a plain list, mutated in place — no per-span object
# allocation once the ring has wrapped): Span's constructor arguments, in
# order; an empty slot has no name and seq -1
_EMPTY_SLOT = (None, 0, 0, 0, 0, -1, None, None, None, None, False)

# One run of a serving benchmark cell, with room: a decode step commits 5
# records (step, decode, upload, fetch, bookkeep) and a request 6 (queue
# wait, admit, prefill dispatch, write prompt, first-token fetch,
# request); 60 s of lead-in, window and drain at 48 ms a step is 1,250
# steps and at most 270 requests, some 7,900 records.
_DEFAULT_CAPACITY = 32768

# span-dump schema: 2 adds id, parent, rid, args and detached to a span;
# a version-1 dump (name, tid, stamps, depth, seq) still loads
SCHEMA_VERSION = 2


class Span:
    """Read-only view of one completed span (built lazily by readers —
    the hot path never constructs these). ``id`` is unique in the
    process, ``parent`` is the ``id`` of the span that was open around
    it on its thread (None at top level), ``rid`` the request it served
    and ``args`` its integer fields; ``detached`` marks a span committed
    by :meth:`SpanTracer.record`, whose stamps need not nest inside its
    parent's."""

    __slots__ = ("name", "tid", "start_ns", "end_ns", "depth", "seq",
                 "id", "parent", "rid", "args", "detached")

    def __init__(self, name, tid, start_ns, end_ns, depth, seq, id=None,
                 parent=None, rid=None, args=None, detached=False):
        self.name = name
        self.tid = tid
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.depth = depth
        self.seq = seq
        self.id = id
        self.parent = parent
        self.rid = rid
        self.args = args or {}
        self.detached = detached

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    def to_dict(self) -> dict:
        return {"name": self.name, "tid": self.tid,
                "start_ns": self.start_ns, "end_ns": self.end_ns,
                "depth": self.depth, "seq": self.seq, "id": self.id,
                "parent": self.parent, "rid": self.rid,
                "args": dict(self.args), "detached": self.detached}


class SpanTracer:
    """Fixed-capacity ring of completed spans + per-thread open stacks.

    ``capacity`` bounds memory forever: a week-long run keeps the last
    ``capacity`` spans, which is exactly what a post-mortem needs. The
    ring slots are preallocated lists; recording a span mutates one
    slot under a short lock — no unbounded growth.

    Open-span stacks are kept in a shared ``{tid: stack}`` dict rather
    than ``threading.local`` so the flight recorder's watchdog THREAD
    can snapshot every other thread's in-flight spans mid-hang; each
    stack is only ever mutated by its owner thread.
    """

    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._ring: List[list] = [list(_EMPTY_SLOT)
                                  for _ in range(capacity)]
        self._lock = threading.Lock()
        self._next = 0          # monotonically increasing write seq
        self._ids = itertools.count(1)   # next() is atomic in CPython
        # tid -> [[name, start_ns, id, rid, args], ...] (open)
        self._stacks: dict = {}
        # every thread that ever recorded, for stable tid numbering
        self._tids: dict = {}

    # ------------------------------------------------------- hot path

    def _stack(self, tid: int) -> list:
        stack = self._stacks.get(tid)
        if stack is None:
            with self._lock:
                stack = self._stacks.setdefault(tid, [])
                self._tids.setdefault(
                    tid, threading.current_thread().name)
        return stack

    def _commit(self, name, tid, start_ns, end_ns, depth, span_id,
                parent, rid, args, detached) -> None:
        with self._lock:
            seq = self._next
            self._next = seq + 1
            self._ring[seq % self.capacity][:] = (
                name, tid, start_ns, end_ns, depth, seq, span_id, parent,
                rid, args, detached)

    def begin(self, name: str, rid: Optional[int] = None, **args) -> int:
        """Open a span on the calling thread and return its id. Prefer
        ``with span(...)``; the paired :meth:`end` MUST run (the
        ``unclosed-span`` lint polices call sites). ``args`` are integer
        fields (counts, lengths): names stay constant strings."""
        span_id = next(self._ids)
        self._stack(threading.get_ident()).append(
            [name, time.monotonic_ns(), span_id, rid, args or None])
        return span_id

    def annotate(self, **args) -> None:
        """Add integer fields to the innermost span open on the calling
        thread: counts that are known only once its work has come back."""
        stack = self._stacks.get(threading.get_ident())
        if stack:
            stack[-1][4] = {**(stack[-1][4] or {}), **args}

    def end(self) -> None:
        """Close the innermost open span on the calling thread and
        commit it to the ring."""
        tid = threading.get_ident()
        stack = self._stacks.get(tid)
        if not stack:
            return  # unbalanced end: drop rather than corrupt the ring
        name, start_ns, span_id, rid, args = stack.pop()
        end_ns = time.monotonic_ns()
        parent = stack[-1][2] if stack else None
        self._commit(name, tid, start_ns, end_ns, len(stack), span_id,
                     parent, rid, args, False)

    def record(self, name: str, start_ns: int, end_ns: int,
               rid: Optional[int] = None, **args) -> int:
        """Commit a span from stamps already taken on this module's
        clock (``time.monotonic_ns``; ``time.monotonic()`` seconds are
        the same clock). Its parent is the span open on the calling
        thread now, though its stamps may lie outside that span's."""
        tid = threading.get_ident()
        stack = self._stack(tid)
        span_id = next(self._ids)
        parent = stack[-1][2] if stack else None
        self._commit(name, tid, int(start_ns), int(end_ns), len(stack),
                     span_id, parent, rid, args or None, True)
        return span_id

    # -------------------------------------------------------- readers

    def mark(self) -> int:
        """Current write position — pass to :meth:`completed` to read
        only spans recorded after this point."""
        with self._lock:
            return self._next

    def completed(self, since: int = 0) -> List[Span]:
        """Completed spans with ``seq >= since`` still in the ring, in
        commit order. Spans older than the ring's capacity are gone —
        that is the ring's contract, not an error."""
        with self._lock:
            oldest = max(since, self._next - self.capacity, 0)
            slots = [list(self._ring[seq % self.capacity])
                     for seq in range(oldest, self._next)]
        return [Span(*s) for s in slots]

    def dropped(self, since: int = 0) -> int:
        """How many spans recorded after ``since`` have already been
        overwritten (readers must know when the window overflowed)."""
        with self._lock:
            oldest = max(0, self._next - self.capacity)
        return max(0, oldest - since)

    def open_spans(self) -> dict:
        """{tid: [(name, age_s), ...]} of currently-open spans across
        ALL threads — innermost last. This is the flight recorder's
        'where is everyone stuck' snapshot; it is safe to call from any
        thread mid-hang (stacks are copied, owners keep mutating)."""
        now = time.monotonic_ns()
        with self._lock:
            stacks = {tid: list(stack)
                      for tid, stack in self._stacks.items()}
        out = {}
        for tid, stack in stacks.items():
            frames = [(frame[0], (now - frame[1]) / 1e9)
                      for frame in stack]
            if frames:
                out[tid] = frames
        return out

    def thread_names(self) -> dict:
        with self._lock:
            return dict(self._tids)

    def clear(self) -> None:
        with self._lock:
            for slot in self._ring:
                slot[:] = _EMPTY_SLOT
            self._next = 0
            self._tids.clear()
            self._stacks.clear()

    # --------------------------------------------------------- export

    def to_trace_events(self, since: int = 0) -> List[dict]:
        """Chrome trace-event list (see :func:`to_trace_events`)."""
        return to_trace_events(self.completed(since),
                               thread_names=self.thread_names())

    def write_chrome_trace(self, path: str, since: int = 0) -> int:
        """Write the ring as a Perfetto-loadable trace; returns the
        number of spans exported."""
        spans = self.completed(since)
        write_chrome_trace(path, spans, thread_names=self.thread_names())
        return len(spans)

    @staticmethod
    def save_path(path: str) -> str:
        """Where :meth:`save` actually lands for ``path`` — the
        ``.rank{i}``-suffixed variant for fleet members, ``path``
        verbatim for solo processes (the
        :meth:`MetricRegistry.dump_path` analog)."""
        from apex_tpu.observability.fleet.identity import rank_path
        return rank_path(path)

    def save(self, path: str, since: int = 0) -> int:
        """Persist the raw ring as a span-dump JSON (re-exportable with
        ``python -m apex_tpu.observability trace``); returns the span
        count. Fleet members (ISSUE 12) write the ``.rank{i}``-suffixed
        variant of ``path`` (:meth:`save_path` resolves it) with the
        ``{process_index, process_count, run_id}`` stamp, so concurrent
        rank dumps never clobber and the fleet CLI can join them
        rank→pid."""
        from apex_tpu.observability.fleet.identity import (
            identity_fields,
            is_fleet_member,
            process_identity,
            rank_path,
        )

        spans = self.completed(since)
        payload = {
            "kind": "apex_tpu.spans",
            "schema_version": SCHEMA_VERSION,
            "pid": os.getpid(),
            "thread_names": {str(k): v
                             for k, v in self.thread_names().items()},
            "dropped": self.dropped(since),
            "spans": [s.to_dict() for s in spans],
        }
        ident = process_identity()
        if is_fleet_member(ident):
            payload.update(identity_fields(ident))
        with open(rank_path(path, ident), "w") as f:
            json.dump(payload, f, indent=1)
        return len(spans)


def spans_from_dicts(dicts) -> List[Span]:
    """Decode :meth:`Span.to_dict` records (a span dump's or a flight
    record's ``spans`` list) back into :class:`Span` objects — the ONE
    deserializer for the serialized span schema."""
    return [Span(d["name"], d["tid"], d["start_ns"], d["end_ns"],
                 d.get("depth", 0), d.get("seq", i), d.get("id"),
                 d.get("parent"), d.get("rid"), d.get("args"),
                 d.get("detached", False))
            for i, d in enumerate(dicts)
            if d.get("name") is not None]


def decode_span_payload(payload, where: str = "<payload>",
                        kinds=("apex_tpu.spans",)):
    """(spans, thread_names) from an already-parsed dump payload — the
    ONE schema gate + decoder behind :func:`load_spans` and the CLI's
    trace export (flight records embed the identical span layout under
    their own ``kind``, passed via ``kinds``)."""
    if not isinstance(payload, dict) or payload.get("kind") not in kinds:
        raise ValueError(f"{where}: not an apex_tpu span dump")
    version = payload.get("schema_version")
    # a flight record embeds the span layout under its own schema, still 1
    # (its spans gained keys, nothing it had moved)
    known = (1, SCHEMA_VERSION) if payload["kind"] == "apex_tpu.spans" \
        else (1,)
    if version not in known:
        raise ValueError(f"{where}: span-dump schema_version {version} "
                         f"is unknown to this reader (knows "
                         f"{list(known)})")
    spans = spans_from_dicts(payload.get("spans", []))
    names = {int(k): v for k, v in
             (payload.get("thread_names") or {}).items()}
    return spans, names


def load_spans(path: str):
    """Read a :meth:`SpanTracer.save` dump back as
    (spans, thread_names); raises ValueError on any other JSON."""
    with open(path) as f:
        payload = json.load(f)
    return decode_span_payload(payload, where=path)


# ------------------------------------------------- trace-event export

def to_trace_events(spans, thread_names: Optional[dict] = None,
                    pid: Optional[int] = None) -> List[dict]:
    """Spans → Chrome trace-event dicts (``B``/``E`` pairs + thread-name
    metadata), ready for ``json.dump({"traceEvents": [...]})``. A span's
    request id and fields ride on its opening event as ``args``.

    Ordering contract (validated by tests/run_observability):
    ``ts`` is non-decreasing across the whole list, and per (pid, tid)
    every ``B`` has a matching later ``E`` with correct nesting — even
    when a coarse monotonic clock collapses several begins/ends onto
    one timestamp (zero-duration spans included). tids are renumbered
    to small stable ints (sorted by first appearance) so repeated
    exports of the same dump are byte-identical.

    Per thread, the true begin/end sequence is RECONSTRUCTED from the
    ring's commit order: spans commit in post-order (``end()`` pops),
    and a span's descendants commit contiguously just before it at
    greater depths — so nesting never depends on timestamp tie-breaks,
    which cannot disambiguate events a coarse clock stamped alike."""
    pid = os.getpid() if pid is None else pid
    thread_names = thread_names or {}
    spans = sorted(spans, key=lambda s: s.seq)
    # stable small tids: order of first appearance in commit order
    tid_map: dict = {}
    per_tid: dict = {}
    for s in spans:
        if s.tid not in tid_map:
            tid_map[s.tid] = len(tid_map) + 1
        per_tid.setdefault(s.tid, []).append(s)

    def with_args(event, s):
        if s.rid is not None or s.args:
            event["args"] = dict(s.args)
            if s.rid is not None:
                event["args"]["rid"] = s.rid
        return event

    def rebuild(tid_spans, tid):
        """Post-order + depth → the chronological event list."""
        pending = []  # chronological [(depth, [event, ...]), ...]
        for s in tid_spans:
            # this span's subtree roots: the trailing pending entries
            # at greater depth (they committed just before it)
            kids = []
            while pending and pending[-1][0] > s.depth:
                kids.append(pending.pop())
            kids.reverse()
            ev = [with_args({"name": s.name, "ph": "B",
                             "ts": s.start_ns / 1e3, "pid": pid,
                             "tid": tid}, s)]
            for _d, sub in kids:
                ev.extend(sub)
            ev.append({"name": s.name, "ph": "E", "ts": s.end_ns / 1e3,
                       "pid": pid, "tid": tid})
            pending.append((s.depth, ev))
        # leftovers are chronological top-level siblings (orphans whose
        # parent never committed — ring wrap — stay top-level)
        return [e for _d, sub in pending for e in sub]

    def detached(s, tid):
        """A recorded span as an async pair (``b``/``e``): its stamps
        need not nest in the thread's stack, and several may overlap
        (requests queued together), which ``B``/``E`` cannot say."""
        key = s.rid if s.rid is not None else s.id
        common = {"name": s.name, "cat": "request", "id": key,
                  "pid": pid, "tid": tid}
        return [with_args({**common, "ph": "b", "ts": s.start_ns / 1e3},
                          s),
                {**common, "ph": "e", "ts": s.end_ns / 1e3}]

    events = []
    for real_tid, tid in sorted(tid_map.items(), key=lambda kv: kv[1]):
        events.extend(rebuild(
            [s for s in per_tid[real_tid] if not s.detached], tid))
        for s in per_tid[real_tid]:
            if s.detached:
                events.extend(detached(s, tid))
    # global ts ordering across threads; sorted() is stable, so each
    # thread's reconstructed order (non-decreasing ts by construction)
    # survives ties
    events.sort(key=lambda ev: ev["ts"])
    out = []
    for real_tid, tid in sorted(tid_map.items(), key=lambda kv: kv[1]):
        out.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid,
                    "args": {"name": thread_names.get(
                        real_tid, f"thread-{tid}")}})
    out.extend(events)
    return out


def write_chrome_trace(path: str, spans,
                       thread_names: Optional[dict] = None,
                       pid: Optional[int] = None) -> None:
    """Write spans as a Perfetto/chrome://tracing-loadable JSON file."""
    payload = {
        "traceEvents": to_trace_events(spans, thread_names, pid=pid),
        "displayTimeUnit": "ms",
    }
    with open(path, "w") as f:
        json.dump(payload, f)


# ---------------------------------------------------- process default

_TRACER = SpanTracer()
_TRACER_LOCK = threading.Lock()


def get_tracer() -> SpanTracer:
    """The always-on process tracer every :func:`span` records into."""
    return _TRACER


def set_tracer(tracer: SpanTracer) -> SpanTracer:
    """Swap the process tracer (tests, multi-run tools); returns the
    previous one."""
    global _TRACER
    with _TRACER_LOCK:
        prev, _TRACER = _TRACER, tracer
    return prev


@contextlib.contextmanager
def span(name: str, rid: Optional[int] = None, **args):
    """Open a named region on every timeline at once: the span ring
    buffer (host post-mortem), the live profiler host timeline
    (``TraceAnnotation``) and the compiled program's HLO metadata
    (``named_scope``). The drop-in successor of
    :func:`apex_tpu.observability.scope` — same device semantics, plus
    the always-on host record — for host code that also builds HLO."""
    from apex_tpu.observability.scope import scope as _scope

    tracer = get_tracer()
    tracer.begin(name, rid, **args)
    try:
        with _scope(name):
            yield
    finally:
        tracer.end()


@contextlib.contextmanager
def host_span(name: str, rid: Optional[int] = None, **args):
    """:func:`span` for call sites that build no HLO: the ring and the
    live profiler's host timeline, no ``named_scope``."""
    from apex_tpu.observability.scope import host_annotation

    tracer = get_tracer()
    tracer.begin(name, rid, **args)
    try:
        with host_annotation(name):
            yield
    finally:
        tracer.end()
