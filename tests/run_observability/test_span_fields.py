"""The span ring's second schema (ISSUE 26): identifiers and parents, request
ids and integer fields, spans recorded from stamps already taken, the
host-only entry, and all of it through `save`/`load_spans` and the Chrome
export; a version-1 dump still loads; an overflowed ring says so."""

import json
import time

import pytest

from apex_tpu.observability import host_span, span
from apex_tpu.observability.profiling import (
    SpanTracer,
    load_spans,
    set_tracer,
    to_trace_events,
)
from apex_tpu.observability.profiling.spans import SCHEMA_VERSION


@pytest.fixture
def tracer():
    t = SpanTracer(capacity=64)
    prev = set_tracer(t)
    yield t
    set_tracer(prev)


def fill(tracer):
    """step > (admit[rid 7] > write_prompt, a recorded queue wait), decode."""
    t0 = time.monotonic_ns()
    with host_span("serving/step"):
        with host_span("serving/admit", rid=7, prompt_tokens=20, bucket=24):
            tracer.record("serving/queue_wait", t0 - 5000, t0, rid=7)
            with host_span("serving/write_prompt", rid=7, pages=3):
                pass
        with host_span("serving/decode", rows=2, pages_live=5,
                       pages_gathered=15):
            pass
    return {s.name: s for s in tracer.completed()}


def test_ids_parents_rid_and_fields(tracer):
    got = fill(tracer)
    step, admit = got["serving/step"], got["serving/admit"]
    assert len({s.id for s in got.values()}) == 5
    assert step.parent is None and step.rid is None and step.args == {}
    assert admit.parent == got["serving/decode"].parent == step.id
    assert got["serving/write_prompt"].parent == admit.id
    assert admit.rid == 7
    assert admit.args == {"prompt_tokens": 20, "bucket": 24}
    assert got["serving/decode"].args == {"rows": 2, "pages_live": 5,
                                          "pages_gathered": 15}
    wait = got["serving/queue_wait"]
    assert wait.detached and wait.parent == admit.id and wait.rid == 7
    assert wait.duration_ns == 5000 and wait.start_ns < step.start_ns
    assert not any(s.detached for s in got.values() if s is not wait)
    # begin hands the identifier out; nothing is left open
    assert tracer.begin("x") == max(s.id for s in got.values()) + 1
    tracer.end()
    assert not tracer.open_spans()


def test_fields_survive_save_and_load(tracer, tmp_path):
    fill(tracer)
    path = tmp_path / "spans.json"
    assert tracer.save(str(path)) == 5
    assert json.loads(path.read_text())["schema_version"] == SCHEMA_VERSION
    loaded, _ = load_spans(str(path))
    assert [s.to_dict() for s in loaded] == \
        [s.to_dict() for s in tracer.completed()]


def test_version_1_dump_still_loads(tmp_path):
    path = tmp_path / "old.json"
    path.write_text(json.dumps({
        "kind": "apex_tpu.spans", "schema_version": 1, "pid": 1,
        "thread_names": {"5": "MainThread"}, "dropped": 0,
        "spans": [{"name": "pp/forward", "tid": 5, "start_ns": 10,
                   "end_ns": 30, "depth": 0, "seq": 0}]}))
    (old,), names = load_spans(str(path))
    assert (old.name, old.duration_ns, names) == ("pp/forward", 20,
                                                  {5: "MainThread"})
    assert old.id is None and old.parent is None and old.rid is None
    assert old.args == {} and not old.detached
    assert [e["ph"] for e in to_trace_events([old])] == ["M", "B", "E"]


def test_chrome_export_carries_rid_and_fields(tracer):
    fill(tracer)
    events = tracer.to_trace_events()
    begins = {e["name"]: e for e in events if e["ph"] == "B"}
    assert begins["serving/admit"]["args"] == {
        "prompt_tokens": 20, "bucket": 24, "rid": 7}
    assert begins["serving/decode"]["args"]["pages_gathered"] == 15
    assert "args" not in begins["serving/step"]
    # a recorded span is an async pair keyed by its request: it may begin
    # before the span it was recorded under, which B/E nesting cannot say
    opened, closed = [e for e in events if e["ph"] in ("b", "e")]
    assert (opened["name"], opened["id"], opened["args"]) == (
        "serving/queue_wait", 7, {"rid": 7})
    assert closed["id"] == 7 and closed["ts"] - opened["ts"] == 5.0
    assert "serving/queue_wait" not in begins
    stack = []
    for e in events:
        if e["ph"] == "B":
            stack.append(e["name"])
        elif e["ph"] == "E":
            assert stack.pop() == e["name"]
    assert not stack
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_an_overflowed_ring_says_so():
    t = SpanTracer(capacity=8)
    for i in range(8):
        t.record("r", i, i + 1, rid=i, n=i)
    assert t.dropped() == 0
    t.record("r", 8, 9)
    assert t.dropped() == 1 and t.dropped(since=1) == 0
    assert [s.rid for s in t.completed()] == [1, 2, 3, 4, 5, 6, 7, None]


def test_host_span_names_no_hlo(tracer):
    """`span` tags the compiled program, `host_span` does not; both reach
    the ring."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with span("tagged_region"):
            x = x * 2
        with host_span("host_only_region"):
            return x + 1

    text = jax.jit(f).lower(jnp.ones(())).as_text(debug_info=True)
    assert "tagged_region" in text and "host_only_region" not in text
    assert [s.name for s in tracer.completed()] == [
        "tagged_region", "host_only_region"]
