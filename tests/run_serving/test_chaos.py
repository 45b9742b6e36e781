"""Serving preemption chaos (ISSUE 20): a seeded fault plan preempts
the engine mid-decode — it must stop admitting, drain, emergency-dump
queue + KV pages, raise Preempted (exit code 75), and a resumed
engine must complete every request with BIT-identical tokens to an
uninterrupted run (greedy decode + scatter-restored pages)."""

import json
import os

import jax
import numpy as np
import pytest

from apex_tpu import observability as obs
from apex_tpu.models import llama
from apex_tpu.resilience.faults import FaultPlan
from apex_tpu.resilience.loop import Preempted
from apex_tpu.resilience.preemption import EXIT_PREEMPTED
from apex_tpu.serving import ServingEngine, scheduler
from apex_tpu.serving.engine import (
    _PAGES_FILE,
    _STATE_FILE,
    DUMP_SCHEMA_VERSION,
)


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _engine(params, cfg, **kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 2)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("max_new_cap", 16)
    kw.setdefault("registry", obs.MetricRegistry())
    return ServingEngine(params, cfg, **kw)


def _jobs(cfg, n=6, seed=7):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size,
                          size=int(rng.integers(3, 12))).astype(np.int32),
             int(rng.integers(4, 9))) for _ in range(n)]


def _submit_all(engine, jobs):
    for prompt, max_new in jobs:
        engine.submit(prompt, max_new)


@pytest.mark.parametrize("chunk", [scheduler.LIST_CHUNK, 3])
def test_preempt_drain_dump_resume_bit_identical(model, tmp_path, chunk,
                                                 monkeypatch):
    """Also with the decode step's list of live pages in chunks of 3 under
    tables of 8 slots: resumed requests land in other rows, beside other
    requests, with their pages in other chunks, and get the same tokens."""
    monkeypatch.setattr(scheduler, "LIST_CHUNK", chunk)
    params, cfg = model
    jobs = _jobs(cfg)

    # the uninterrupted twin defines the expected tokens
    twin = _engine(params, cfg)
    _submit_all(twin, jobs)
    want = twin.run()

    d = str(tmp_path / "dump")
    plan = FaultPlan.parse("seed=1,preempt@4")
    engine = _engine(params, cfg, fault_plan=plan, dump_dir=d)
    _submit_all(engine, jobs)
    with pytest.raises(Preempted) as exc:
        engine.run()
    assert exc.value.exit_code == EXIT_PREEMPTED == 75
    assert engine.draining
    with pytest.raises(RuntimeError, match="draining"):
        engine.submit(jobs[0][0], 4)

    # the dump is complete: state.json (the completeness marker) +
    # one k/v pair per in-flight request
    state_path = os.path.join(d, _STATE_FILE)
    with open(state_path) as f:
        state = json.load(f)
    assert state["schema_version"] == DUMP_SCHEMA_VERSION
    assert state["reason"].startswith("fault-plan preempt")
    inflight = state["inflight"]
    assert inflight, "preempt@4 must catch requests mid-decode"
    with np.load(os.path.join(d, _PAGES_FILE)) as pages:
        for rec in inflight:
            assert f"k_{rec['rid']}" in pages
            assert f"v_{rec['rid']}" in pages
            assert rec["tokens"], "mid-decode request has tokens"
    # every request is either completed, in flight, or still queued
    accounted = (set(int(r) for r in state["completed"])
                 | {r["rid"] for r in inflight}
                 | {r["rid"] for r in state["queued"]})
    assert accounted == set(range(len(jobs)))

    # resume: same geometry from the dump, KV pages restored by
    # scatter — remaining tokens must be bit-identical to the twin
    resumed = ServingEngine.resume(d, params, cfg,
                                   registry=obs.MetricRegistry())
    got = resumed.run()
    assert got == want
    assert resumed.scheduler.decode_retraces() == 0


def test_exit_on_preempt_exits_75(model, tmp_path):
    """Process-supervisor contract: exit_on_preempt=True turns the
    drain into sys.exit(75) instead of raising."""
    params, cfg = model
    engine = _engine(params, cfg, fault_plan=FaultPlan.parse(
        "seed=1,preempt@2"), dump_dir=str(tmp_path / "d"),
        exit_on_preempt=True)
    _submit_all(engine, _jobs(cfg, n=3))
    with pytest.raises(SystemExit) as exc:
        engine.run()
    assert exc.value.code == 75
    assert os.path.exists(str(tmp_path / "d" / _STATE_FILE))


def test_drain_publishes_preemption_telemetry(model, tmp_path):
    params, cfg = model
    reg = obs.MetricRegistry()
    engine = _engine(params, cfg, registry=reg,
                     fault_plan=FaultPlan.parse("seed=1,preempt@3"),
                     dump_dir=str(tmp_path / "d"))
    _submit_all(engine, _jobs(cfg, n=4))
    with pytest.raises(Preempted):
        engine.run()
    records = reg.to_records()
    names = {r["name"]: r for r in records if "name" in r}
    assert names["serving/requests_preempted"]["value"] >= 1
    events = [r for r in records if r.get("type") == "event"
              and r.get("name") == "serving_drain"]
    assert events
    assert events[0]["fields"]["iteration"] == engine.iteration


def test_resume_rejects_schema_drift(model, tmp_path):
    params, cfg = model
    d = str(tmp_path / "d")
    engine = _engine(params, cfg, fault_plan=FaultPlan.parse(
        "seed=1,preempt@2"), dump_dir=d)
    _submit_all(engine, _jobs(cfg, n=3))
    with pytest.raises(Preempted):
        engine.run()
    state_path = os.path.join(d, _STATE_FILE)
    with open(state_path) as f:
        state = json.load(f)
    state["schema_version"] = 999
    with open(state_path, "w") as f:
        json.dump(state, f)
    with pytest.raises(ValueError, match="schema_version"):
        ServingEngine.resume(d, params, cfg,
                             registry=obs.MetricRegistry())


def test_fault_plan_does_not_refire_on_resume(model, tmp_path):
    """should_fire spends the event: passing the SAME plan instance to
    the resumed engine must not re-preempt at the same iteration."""
    params, cfg = model
    d = str(tmp_path / "d")
    plan = FaultPlan.parse("seed=1,preempt@3")
    engine = _engine(params, cfg, fault_plan=plan, dump_dir=d)
    _submit_all(engine, _jobs(cfg, n=4))
    with pytest.raises(Preempted):
        engine.run()
    resumed = ServingEngine.resume(d, params, cfg, fault_plan=plan,
                                   registry=obs.MetricRegistry())
    results = resumed.run()  # completes — the spent plan stays quiet
    assert len(results) == 4
