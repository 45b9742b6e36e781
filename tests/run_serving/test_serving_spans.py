"""What the serving engine writes into the span ring (ISSUE 26): one
`serving/step` per iteration with the admissions and the decode it made as
its children, every request's queue wait, admission and whole life under one
request id, and the decode step's page counts. Counts and order only: a CPU
run says nothing about a time."""

import math

import jax
import numpy as np
import pytest

from apex_tpu import observability as obs
from apex_tpu.models import llama
from apex_tpu.observability import SpanTracer, set_tracer
from apex_tpu.serving import ServingEngine, scheduler

PAGE = 8
JOBS = ((3, 4), (8, 7), (11, 4), (5, 7), (8, 4), (20, 1))


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny()
    return llama.init_params(jax.random.PRNGKey(0), cfg), cfg


@pytest.fixture
def tracer():
    t = SpanTracer(capacity=4096)
    prev = set_tracer(t)
    yield t
    set_tracer(prev)


@pytest.fixture
def served(model, tracer):
    """A tiny engine driven to the end, with, per iteration, the page count
    the slots' positions give just before the step."""
    params, cfg = model
    engine = ServingEngine(params, cfg, page_size=PAGE, max_batch=3,
                           num_pages=32, max_prompt_len=24, max_new_cap=16,
                           registry=obs.MetricRegistry())
    rng = np.random.default_rng(0)
    for p, max_new in JOBS:
        engine.submit(rng.integers(0, cfg.vocab_size, size=p).astype(
            np.int32), max_new)
    iterations = 0
    while engine.pending:
        engine.step()
        iterations += 1
    return engine, tracer.completed(), iterations


def by_name(spans, name):
    return [s for s in spans if s.name == name]


def test_one_step_per_iteration_with_its_admissions_and_decode(served):
    engine, spans, iterations = served
    steps = by_name(spans, "serving/step")
    assert len(steps) == iterations == engine.iteration
    assert all(s.parent is None and s.depth == 0 for s in steps)
    step_ids = {s.id for s in steps}
    admits, decodes = (by_name(spans, "serving/admit"),
                       by_name(spans, "serving/decode"))
    assert len(admits) == len(JOBS) == engine.scheduler.prefill_count
    assert len(decodes) == engine.scheduler.decode_steps
    assert all(s.parent in step_ids for s in admits + decodes)
    # a step decodes at most once, after its admissions: commit order
    for step in steps:
        kids = [s.name for s in spans if s.parent == step.id]
        assert kids.count("serving/decode") <= 1
        assert kids == sorted(kids, key=lambda n: n == "serving/decode")
    # the first step admitted three requests and decoded: both under it
    first = [s.name for s in spans if s.parent == steps[0].id]
    assert first == ["serving/admit"] * 3 + ["serving/decode"]
    for parent, children in (
            (admits, ("serving/prefill_dispatch", "serving/write_prompt",
                      "serving/first_token_fetch")),
            (decodes, ("serving/decode_upload", "serving/decode_fetch",
                       "serving/decode_bookkeep"))):
        ids = {s.id for s in parent}
        for name in children:
            kids = by_name(spans, name)
            assert len(kids) == len(parent)
            assert all(k.parent in ids for k in kids)


def test_every_request_has_its_spans_under_one_rid(served):
    engine, spans, _ = served
    assert len(engine.completed) == len(JOBS)
    for req in engine.completed:
        assert req.submit_s <= req.admit_s <= req.first_token_s \
            <= req.finish_s
        mine = {s.name: s for s in spans if s.rid == req.rid}
        assert {"serving/queue_wait", "serving/admit", "serving/request",
                "serving/prefill_dispatch", "serving/write_prompt",
                "serving/first_token_fetch"} == set(mine)
        wait, admit, whole = (mine["serving/queue_wait"],
                              mine["serving/admit"], mine["serving/request"])
        assert wait.detached and whole.detached and not admit.detached
        # the stamps are the request's own, on the ring's clock
        assert wait.start_ns == whole.start_ns == int(req.submit_s * 1e9)
        assert wait.end_ns == int(req.admit_s * 1e9)
        assert whole.end_ns == int(req.finish_s * 1e9)
        assert admit.start_ns >= wait.end_ns
        assert whole.args == {"tokens": len(req.tokens)}
        p = len(req.prompt)
        assert admit.args["prompt_tokens"] == p
        assert admit.args["bucket"] == math.ceil(p / PAGE) * PAGE
        assert 0 <= admit.args["rows"] < 3
        assert mine["serving/write_prompt"].args == {
            "pages": math.ceil(p / PAGE), "cache_layers": 2}
        assert (admit.args["layer_passes"], admit.args["cache_layers"]) \
            == (2, 2)
    # the request that finished at its first token never decoded
    assert sum(s.args["rows"] for s in by_name(spans, "serving/decode")) \
        == sum(len(r.tokens) - 1 for r in engine.completed)


def test_decode_records_count_live_and_gathered_pages(model, tracer,
                                                      monkeypatch):
    """`pages_gathered` is what the step reads in a cache layer: the whole
    chunks of its list of live pages (ISSUE 32; chunks of 4 here, so that the
    15 slots of these tables are more than one). One compiled step serves
    every count of chunks."""
    monkeypatch.setattr(scheduler, "LIST_CHUNK", 4)
    params, cfg = model
    engine = ServingEngine(params, cfg, page_size=PAGE, max_batch=3,
                           num_pages=32, max_prompt_len=24, max_new_cap=16,
                           registry=obs.MetricRegistry())
    sched = engine.scheduler
    rng = np.random.default_rng(1)
    for p, max_new in JOBS:
        engine.submit(rng.integers(0, cfg.vocab_size, size=p).astype(
            np.int32), max_new)
    chunks = set()
    while engine.pending:
        before, done = sched.decode_steps, len(engine.completed)
        mark = tracer.mark()
        engine.step()
        if sched.decode_steps == before:
            continue
        (record,) = by_name(tracer.completed(mark), "serving/decode")
        # after the step each surviving row's position has moved by one,
        # so its count before the step is ceil(pos / page); rows the step
        # retired are gone from the slots and counted from their requests
        live = sum(math.ceil(int(sched._pos[i]) / PAGE)
                   for i, r in enumerate(sched.slots) if r is not None)
        live += sum(math.ceil((len(r.prompt) + len(r.tokens) - 1) / PAGE)
                    for r in engine.completed[done:] if len(r.tokens) > 1)
        assert record.args["pages_live"] == live
        assert 0 < record.args["pages_live"] <= record.args["pages_gathered"]
        assert record.args["pages_gathered"] == math.ceil(live / 4) * 4
        assert record.args["pages_gathered"] < 3 * sched.max_pages_per_req
        assert 0 < record.args["rows"] <= 3
        chunks.add(record.args["pages_gathered"] // 4)
    assert len(chunks) > 1 and sched.decode_retraces() == 0
