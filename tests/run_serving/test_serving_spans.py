"""What the serving engine writes into the span ring (ISSUE 26): one
`serving/step` per iteration with the admissions and the decode it made as
its children, every request's queue wait, admission and whole life under one
request id, and the decode step's page counts. Since ISSUE 34 a
`serving/decode` record is a dispatched step and covers the landing of the
step before: the fields only a landed array can give are that step's. Counts
and order only: a CPU run says nothing about a time."""

import math

import jax
import numpy as np
import pytest

from apex_tpu import observability as obs
from apex_tpu.models import llama
from apex_tpu.observability import SpanTracer, set_tracer
from apex_tpu.serving import ServingEngine, kv_cache, scheduler

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
            (decodes, ("serving/decode_upload",))):
        ids = {s.id for s in parent}
        for name in children:
            kids = by_name(spans, name)
            assert len(kids) == len(parent)
            assert all(k.parent in ids for k in kids)
    # every dispatched step's array is landed once: under the record of the
    # step dispatched next, or, with no row left to run, under the iteration
    decode_ids = {s.id: s for s in decodes}
    for name in ("serving/decode_fetch", "serving/decode_bookkeep"):
        kids = by_name(spans, name)
        assert len(kids) == len(decodes)
        assert all(k.parent in decode_ids or k.parent in step_ids
                   for k in kids)
        under = [k.parent for k in kids if k.parent in decode_ids]
        assert len(under) == len(set(under))
        # a record landed a step exactly where one was in flight
        assert {i for i, s in decode_ids.items() if s.args["in_flight"]} \
            == set(under)
    assert not decodes[0].args["in_flight"]
    assert sum(s.args["in_flight"] for s in decodes) \
        == engine.scheduler.steps_in_flight >= len(decodes) - 2


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
        writes = mine["serving/write_prompt"].args
        assert writes == {"pages": math.ceil(p / PAGE), "cache_layers": 2,
                          "program_seq": writes["program_seq"]}
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
        before = sched.decode_steps
        mark = tracer.mark()
        engine.step()
        if sched.decode_steps == before:
            continue
        (record,) = by_name(tracer.completed(mark), "serving/decode")
        # the record counts the step it dispatched, whose rows are those
        # of the array now unlanded: each one's position has moved by one,
        # so its count before the step is ceil(pos / page)
        rows = sched._unlanded[1]
        live = sum(math.ceil(int(sched._pos[slot]) / PAGE)
                   for slot, _ in rows)
        assert record.args["rows"] == len(rows)
        assert record.args["pages_live"] == live
        assert 0 < record.args["pages_live"] <= record.args["pages_gathered"]
        assert record.args["pages_gathered"] == math.ceil(live / 4) * 4
        assert record.args["pages_gathered"] < 3 * sched.max_pages_per_req
        assert 0 < record.args["rows"] <= 3
        chunks.add(record.args["pages_gathered"] // 4)
    assert len(chunks) > 1 and sched.decode_retraces() == 0


DENSE_FIELDS = {"rows", "pages_live", "pages_gathered", "layer_passes",
                "cache_layers", "in_flight", "rows_past_eos"}
EXPERT_FIELDS = DENSE_FIELDS | {
    "expert_layers", "experts_held", "expert_tokens", "experts_hit",
    "pages_live_window", "pages_gathered_window", "positions",
    "positions_window"}


def stack_of(kind):
    """(params, cfg, vocabulary) of a tiny model of each kind served."""
    if kind == "expert":
        from perfbench.references import afmoe as ref
        from perfbench.references.common import seed_words
        from perfbench.runners import serve_afmoe
        from test_afmoe_serving import TINY

        return (ref.init(*seed_words(2 ** 31 + 33), TINY),
                serve_afmoe.model_config(TINY), TINY["vocab_size"])
    cfg = llama.tiny() if kind == "dense" else llama.tiny(
        num_passes=3, sandwich_norm=True)
    return llama.init_params(jax.random.PRNGKey(0), cfg), cfg, cfg.vocab_size


@pytest.mark.parametrize("kind", ["dense", "looped", "expert"])
def test_every_decode_record_carries_every_field(kind, tracer):
    """ISSUE 34 (f): a `serving/decode` record has the fields it had and
    `in_flight` and `rows_past_eos`, on every record, the first (nothing
    landed) and the rest alike: a reader that finds one record without a
    field gives no value for the whole window. An expert model's routing
    counts are those of the array landed during the record: each step's own,
    one record on, zeros on the first, the last step's on none."""
    params, cfg, vocab = stack_of(kind)
    engine = ServingEngine(params, cfg, page_size=4, max_batch=3,
                           num_pages=27, max_prompt_len=20, max_new_cap=12,
                           registry=obs.MetricRegistry())
    sched = engine.scheduler
    outputs, decode = [], sched._decode

    def dispatch(*args):
        out = decode(*args)
        outputs.append(out[0])
        return out

    sched._decode = dispatch
    rng = np.random.default_rng(2)
    for p, new in ((3, 6), (8, 10), (20, 10), (11, 9), (5, 12)):
        engine.submit(rng.integers(0, vocab, size=p).astype(np.int32), new)
    engine.run()
    decodes = [s.args for s in by_name(tracer.completed(), "serving/decode")]
    assert len(decodes) == len(outputs) == sched.decode_steps > 10
    want = EXPERT_FIELDS if kind == "expert" else DENSE_FIELDS
    assert all(set(a) == want for a in decodes)
    assert all(type(v) is int for a in decodes for v in a.values())
    assert {a["layer_passes"] for a in decodes} == {
        cfg.num_passes * cfg.num_layers}
    assert [a["in_flight"] for a in decodes] == [0] + [1] * (len(decodes) - 1)
    if kind == "expert":
        own = np.stack([np.asarray(o)[3:] for o in outputs])   # [steps, 2]
        got = np.array([[a["expert_tokens"], a["experts_hit"]]
                        for a in decodes])
        assert (got[0] == 0).all() and (got[1:] == own[:-1]).all()
        assert (got.sum(axis=0) == own.sum(axis=0) - own[-1]).all()
        assert own.sum() > 0


def test_each_dispatching_span_says_which_program_it_queued(model, tracer):
    """`program_seq` is the ordinal of the program a span dispatched among
    the process's dispatches of that program: one counter a kind (a bucket's
    prefill, the decode step, the page writes, two a prompt), counted
    across admissions and decode steps alike, so a device trace's k-th
    program of a kind pairs with the span of ordinal k + a shift."""
    params, cfg = model
    before = dict(kv_cache.DISPATCHES)
    engine = ServingEngine(params, cfg, page_size=PAGE, max_batch=3,
                           num_pages=32, max_prompt_len=24, max_new_cap=16,
                           registry=obs.MetricRegistry())
    rng = np.random.default_rng(3)
    for p, max_new in JOBS + JOBS:
        engine.submit(rng.integers(0, cfg.vocab_size, size=p).astype(
            np.int32), max_new)
    engine.run()
    spans = sorted(tracer.completed(), key=lambda s: s.start_ns)
    after = kv_cache.DISPATCHES

    def seqs(name):
        return [s.args["program_seq"] for s in spans if s.name == name]

    uploads = seqs("serving/decode_upload")
    assert len(uploads) == engine.scheduler.decode_steps
    assert uploads == list(range(before.get("_decode_step", 0),
                                 after["_decode_step"]))
    writes = seqs("serving/write_prompt")
    assert writes == list(range(before.get("_serving_write_pages", 0),
                                after["_serving_write_pages"], 2))
    admits = [s for s in spans if s.name == "serving/admit"]
    assert len(writes) == len(admits) == 2 * len(JOBS)
    dispatches = [s for s in spans if s.name == "serving/prefill_dispatch"]
    by_id = {s.id: s for s in admits}
    for bucket in {s.args["bucket"] for s in dispatches}:
        mine = [s.args["program_seq"] for s in dispatches
                if s.args["bucket"] == bucket]
        program = f"_serving_prefill_s{bucket}"
        assert mine == list(range(before.get(program, 0), after[program]))
    assert all(by_id[s.parent].args["bucket"] == s.args["bucket"]
               for s in dispatches)


@pytest.mark.parametrize("ends_by", ["length", "eos"])
def test_an_engine_that_runs_dry_records_the_time_it_had_nothing_to_run(
        model, tracer, ends_by):
    """One detached `serving/empty` a dry spell: from the iteration that left
    nothing queued, running or unlanded to the next submit. A row that ends
    by its EOS leaves a step in flight that the next iteration lands with
    nothing finished: the spell begins there."""
    params, cfg = model
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab_size

    def engine_for(eos_id=None):
        return ServingEngine(params, cfg, page_size=PAGE, max_batch=3,
                             num_pages=32, max_prompt_len=24, max_new_cap=16,
                             eos_id=eos_id, registry=obs.MetricRegistry())

    eos = None
    if ends_by == "eos":
        probe = engine_for()
        rid = probe.submit(prompt, 8)
        tokens = probe.run()[rid]["tokens"]
        eos = next(t for i, t in enumerate(tokens) if i >= 2
                   and t not in tokens[:i])
    engine = engine_for(eos)
    engine.submit(prompt, 8)
    mark = tracer.mark()
    engine.run()
    if ends_by == "eos":
        assert engine.scheduler.rows_past_eos == 1
    last = by_name(tracer.completed(mark), "serving/step")[-1]
    assert not by_name(tracer.completed(), "serving/empty")
    engine.submit(prompt, 3)
    (empty,) = by_name(tracer.completed(), "serving/empty")
    req = engine.scheduler.queue[-1]
    assert empty.detached and empty.args == {}
    assert last.start_ns <= empty.start_ns <= last.end_ns
    assert int(req.submit_s * 1e9) <= empty.end_ns
    engine.run()
    # no second spell is closed until something is submitted again
    assert len(by_name(tracer.completed(), "serving/empty")) == 1
