"""One decode step in flight (ISSUE 34): the scheduler dispatches step k
before it reads step k-1's tokens, which stay on the device as step k's
input. What that must not change (every request's tokens, the dump), what it
changes by one step (when an EOS is seen, when a finish is stamped) and the
order itself, on the CPU with a tiny model: counts and order, never a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import observability as obs
from apex_tpu.models import generate as gen
from apex_tpu.models import llama
from apex_tpu.observability import SpanTracer, set_tracer
from apex_tpu.resilience.faults import FaultPlan
from apex_tpu.resilience.loop import Preempted
from apex_tpu.serving import ServingEngine, scheduler
from apex_tpu.serving.scheduler import ContinuousBatchScheduler, Request

PAGE = 8
# (prompt, new, the iteration it is submitted at): admissions staggered over
# the run, short and long answers beside each other in three rows
JOBS = ((3, 9, 0), (8, 14, 0), (11, 5, 0), (5, 12, 2), (8, 2, 2), (20, 1, 5),
        (6, 16, 5), (13, 7, 9), (4, 11, 14))


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


def work(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, size=p).astype(np.int32), new,
             at) for p, new, at in JOBS]


def generated(params, cfg, prompt, new):
    out = gen.generate(params, jnp.asarray(prompt)[None], cfg, new)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def an_eos_that_fires_mid_batch(params, cfg, jobs):
    """A token that some requests make before their length and others never
    do, and each request's tokens cut at it."""
    whole = [generated(params, cfg, prompt, new) for prompt, new, _ in jobs]
    for eos in sorted({t for tokens in whole for t in tokens[1:-1]}):
        cut = [tokens[:tokens.index(eos) + 1] if eos in tokens else tokens
               for tokens in whole]
        early = [len(c) < len(w) and len(c) > 1 for c, w in zip(cut, whole)]
        if 2 <= sum(early) <= len(jobs) - 3:
            return eos, cut
    raise AssertionError("no token of these answers serves as an EOS")


def sched_for(params, cfg, eos_id=None):
    return ContinuousBatchScheduler(
        params, cfg, num_pages=32, page_size=PAGE, max_batch=3,
        max_prompt_len=24, max_new_cap=16, eos_id=eos_id)


def serve(s, jobs, land_at_once=False):
    """Drive a scheduler as the engine does, submitting each job at its
    iteration. `land_at_once` reads every step's tokens before the next is
    dispatched: the order the engine had before ISSUE 34."""
    requests = [Request(rid, prompt, new)
                for rid, (prompt, new, _) in enumerate(jobs)]
    iteration, finished = 0, []
    while s.has_work() or iteration <= max(at for *_, at in jobs):
        for req, (*_, at) in zip(requests, jobs):
            if at == iteration:
                s.submit(req)
        finished += s.try_admit()[1]
        finished += s.step_decode()
        if land_at_once:
            finished += s.land()
        iteration += 1
    assert sorted(r.rid for r in finished) == list(range(len(jobs)))
    return requests


@pytest.mark.parametrize("with_eos", [False, True], ids=["length", "eos"])
def test_every_request_gets_the_tokens_it_got(model, with_eos):
    """(a) Request by request: the tokens of the order with a step in flight
    are those of the order that lands every step at once, and `generate`'s,
    cut at the EOS where one is set."""
    params, cfg = model
    jobs = work(cfg)
    eos, want = None, [generated(params, cfg, p, n) for p, n, _ in jobs]
    if with_eos:
        eos, want = an_eos_that_fires_mid_batch(params, cfg, jobs)
    s = sched_for(params, cfg, eos)
    before = serve(s, jobs, land_at_once=True)
    assert (s.steps_in_flight, s.rows_past_eos) == (0, 0)
    s = sched_for(params, cfg, eos)
    after = serve(s, jobs)
    for rid, (old, new) in enumerate(zip(before, after)):
        assert new.tokens == old.tokens == want[rid], rid
        assert new.state == "done" and new.finish_s >= new.first_token_s
    assert s.decode_retraces() == 0
    assert s.cache.alloc.num_free == 32
    assert (s.rows_past_eos > 0) == with_eos


def test_an_eos_costs_one_row_step_and_a_length_finish_none(model, tracer):
    """(b) A row that ends by EOS runs exactly one step more than it has
    tokens to show, nothing of that step reaches its tokens, and its pages
    are freed once; a row that ends by length runs none."""
    params, cfg = model
    jobs = work(cfg)
    eos, want = an_eos_that_fires_mid_batch(params, cfg, jobs)
    s = sched_for(params, cfg, eos)
    freed = []
    free_owner = s.cache.alloc.free_owner
    s.cache.alloc.free_owner = lambda rid: (freed.append(rid),
                                            free_owner(rid))[1]
    requests = serve(s, jobs)
    decodes = [sp.args for sp in tracer.completed()
               if sp.name == "serving/decode"]
    by_eos = [r for r, (_, new, _) in zip(requests, jobs)
              if len(r.tokens) < new]
    assert by_eos and all(r.tokens[-1] == eos and eos not in r.tokens[:-1]
                          for r in by_eos)
    assert [r.tokens for r in requests] == want
    assert sorted(freed) == list(range(len(jobs)))
    row_steps = sum(a["rows"] for a in decodes)
    assert row_steps == sum(len(r.tokens) - 1 for r in requests) + len(by_eos)
    assert s.rows_past_eos == len(by_eos) \
        == sum(a["rows_past_eos"] for a in decodes)
    # without an EOS every row-step is a token
    tracer.clear()
    s = sched_for(params, cfg)
    requests = serve(s, jobs)
    decodes = [sp.args for sp in tracer.completed()
               if sp.name == "serving/decode"]
    assert sum(a["rows"] for a in decodes) \
        == sum(len(r.tokens) - 1 for r in requests)
    assert s.rows_past_eos == 0 == sum(a["rows_past_eos"] for a in decodes)


class NumpyThatTellsWhatItReads:
    """`numpy` for the scheduler module, noting every device array read."""

    def __init__(self, events, step_of):
        self.events, self.step_of = events, step_of

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kwargs):
        if isinstance(a, jax.Array) and id(a) in self.step_of:
            self.events.append(("read", self.step_of[id(a)]))
        return np.asarray(a, *args, **kwargs)


def test_step_k_is_dispatched_before_step_k_minus_1_is_read(model, tracer,
                                                            monkeypatch):
    """(c) The order itself, with the dispatch and the fetch wrapped: every
    step's array is read once, after the next step's dispatch; the last is
    read with no dispatch; `in_flight` says so on every record."""
    params, cfg = model
    events, step_of, kept = [], {}, []
    s = sched_for(params, cfg)
    decode = s._decode

    def dispatch(*args):
        out = decode(*args)
        events.append(("dispatch", len(kept)))
        step_of[id(out[0])] = len(kept)
        kept.append(out[0])                 # its id stays its own
        return out

    s._decode = dispatch
    monkeypatch.setattr(scheduler, "np",
                        NumpyThatTellsWhatItReads(events, step_of))
    jobs = [(p, n, 0) for p, n, _ in work(cfg)]      # rows never run dry
    serve(s, jobs)
    steps = len(kept)
    assert steps == s.decode_steps > 10
    assert sorted(events) == sorted(
        [("dispatch", k) for k in range(steps)]
        + [("read", k) for k in range(steps)])
    for k in range(1, steps):
        assert events.index(("dispatch", k)) + 1 \
            == events.index(("read", k - 1))
    assert events[-1] == ("read", steps - 1)
    decodes = [sp.args for sp in tracer.completed()
               if sp.name == "serving/decode"]
    assert [a["in_flight"] for a in decodes] == [0] + [1] * (steps - 1)
    assert s.steps_in_flight == steps - 1


@pytest.mark.parametrize("with_eos", [False, True], ids=["length", "eos"])
def test_a_preemption_with_a_step_in_flight_resumes_to_the_bit(
        model, tmp_path, with_eos):
    """(d) The drain lands the step in flight before it dumps: a record's
    tokens and position agree, what that landing finished is completed, and
    the resumed engine makes the tokens the uninterrupted one made."""
    params, cfg = model
    jobs = [(p, n) for p, n, _ in work(cfg)]
    eos = an_eos_that_fires_mid_batch(
        params, cfg, [(p, n, 0) for p, n in jobs])[0] if with_eos else None

    def engine_for(**kw):
        return ServingEngine(params, cfg, page_size=PAGE, max_batch=3,
                             num_pages=32, max_prompt_len=24, max_new_cap=16,
                             eos_id=eos, registry=obs.MetricRegistry(), **kw)

    twin = engine_for()
    for prompt, new in jobs:
        twin.submit(prompt, new)
    want = twin.run()
    for at in (3, 6, 9):
        d = str(tmp_path / f"dump{at}")
        engine = engine_for(fault_plan=FaultPlan.parse(f"seed=1,preempt@{at}"),
                            dump_dir=d)
        for prompt, new in jobs:
            engine.submit(prompt, new)
        for _ in range(at):
            engine.step()
        unlanded = engine.scheduler._unlanded
        assert unlanded is not None and unlanded[1]
        done = len(engine.results)
        with pytest.raises(Preempted):
            engine.step()
        assert engine.scheduler._unlanded is None
        # the rows of the step that was in flight each got its token
        for _, req in unlanded[1]:
            assert (req.state == "done") == (req.rid in engine.results)
        resumed = ServingEngine.resume(d, params, cfg,
                                       registry=obs.MetricRegistry())
        for slot, req in enumerate(resumed.scheduler.slots):
            if req is not None:
                assert resumed.scheduler._pos[slot] \
                    == len(req.prompt) + len(req.tokens) - 1
                assert len(req.tokens) < req.max_new_tokens
        assert len(resumed.results) >= done
        assert resumed.run() == want
        assert resumed.scheduler.decode_retraces() == 0


def test_export_refuses_while_tokens_are_unlanded(model):
    params, cfg = model
    s = sched_for(params, cfg)
    s.submit(Request(0, np.arange(5, dtype=np.int32), 6))
    s.try_admit()
    s.step_decode()
    with pytest.raises(RuntimeError, match="unlanded"):
        s.export_requests()
    assert s.land() == []
    _, (record,), _ = s.export_requests()
    assert len(record["tokens"]) == 2 and record["pos"] == 5 + 1


def test_the_last_array_is_landed_by_whoever_loops_on_has_work(model):
    """(e) With no row left to run and nothing queued the scheduler still
    has work while a step's tokens are unlanded; `engine.run` and any loop
    on `pending` land them."""
    params, cfg = model
    prompt = np.arange(6, dtype=np.int32)
    s = sched_for(params, cfg)
    req = Request(0, prompt, 3)
    s.submit(req)
    s.try_admit()
    assert s.step_decode() == [] and s.step_decode() == []
    # both decode tokens are dispatched, one is landed, no row is active
    assert (len(req.tokens), s.num_active(), s.decode_steps) == (2, 0, 2)
    assert s.has_work() and not s.queue
    assert s.step_decode() == [req] and not s.has_work()
    assert req.tokens == generated(params, cfg, prompt, 3)
    assert s.decode_steps == 2 and s.slots == [None] * 3
    engine = ServingEngine(params, cfg, page_size=PAGE, max_batch=3,
                           num_pages=32, max_prompt_len=24, max_new_cap=16,
                           registry=(registry := obs.MetricRegistry()))
    engine.submit(prompt, 3)
    assert engine.run() == {0: {"prompt": list(range(6)),
                                "tokens": req.tokens}}
    assert not engine.pending and engine.scheduler._unlanded is None
    totals = {r["name"]: r["value"] for r in registry.to_records()
              if r.get("name") in ("serving/decode_steps_in_flight",
                                   "serving/rows_past_eos")}
    assert totals == {"serving/decode_steps_in_flight": 1,
                      "serving/rows_past_eos": 0}
