"""StepReporter: record schema, throughput/MFU derivation, the MFU>1
suspect trap, and scaler-state readout (ISSUE 2 test satellite)."""

import jax.numpy as jnp
import pytest

from apex_tpu.amp.scaler import LossScaler
from apex_tpu.observability import (
    STEP_RECORD_FIELDS,
    MetricRegistry,
    StepReporter,
    peak_flops,
    transformer_step_flops,
)


def test_record_carries_documented_schema():
    reg = MetricRegistry()
    rec = StepReporter("m", registry=reg).step(0.01)
    for field in STEP_RECORD_FIELDS:
        assert field in rec, field
    assert rec["step"] == 0
    assert rec["step_time_ms"] == pytest.approx(10.0)


def test_throughput_and_mfu():
    reg = MetricRegistry()
    rep = StepReporter("m", registry=reg, tokens_per_step=1000,
                       flops_per_step=1e12, peak=1e13,
                       device_kind="test-chip")
    rec = rep.step(0.5, loss=2.0)
    assert rec["tokens_per_sec"] == pytest.approx(2000.0)
    assert rec["tflops_per_sec"] == pytest.approx(2.0)
    assert rec["mfu"] == pytest.approx(0.2)
    assert "mfu_suspect" not in rec
    assert rec["loss"] == 2.0


def test_impossible_mfu_is_flagged():
    rep = StepReporter("m", registry=MetricRegistry(),
                       flops_per_step=1e15, peak=1e12)
    rec = rep.step(0.001)
    assert rec["mfu"] > 1.0
    assert "mfu_suspect" in rec  # the r5 MFU=330 trap, now structural


def test_scaler_state_readout_after_overflow():
    scaler = LossScaler(loss_scale="dynamic", init_scale=2.0 ** 8)
    state = scaler.init()
    grads = {"w": jnp.array([jnp.inf, 1.0])}
    _, overflow = scaler.unscale(grads, state)
    state = scaler.update(state, overflow)
    assert scaler.overflow_count(state) == 1

    rec = StepReporter("m", registry=MetricRegistry()).step(
        0.01, scaler_state=state)
    assert rec["overflow_count"] == 1
    assert rec["loss_scale"] == pytest.approx(2.0 ** 7)  # halved


def test_scaler_report_publishes_gauges():
    scaler = LossScaler(loss_scale="dynamic")
    state = scaler.init()
    reg = MetricRegistry()
    values = scaler.report(state, registry=reg)
    assert values["overflow_count"] == 0
    assert reg.gauge("amp/loss_scale").value == pytest.approx(2.0 ** 16)
    assert reg.gauge("amp/overflow_count").value == 0


def test_records_land_in_registry_metrics_and_events():
    reg = MetricRegistry()
    rep = StepReporter("llama", registry=reg)
    rep.step(0.02)
    rep.step(0.04)
    assert reg.counter("llama/steps").value == 2
    assert reg.histogram("llama/step_time_ms").count == 2
    events = [e for e in reg.events() if e["name"] == "step"]
    assert len(events) == 2
    assert events[1]["fields"]["step"] == 1
    summary = rep.summary()
    assert summary["steps"] == 2
    assert summary["step_time_ms_min"] == pytest.approx(20.0)


def test_nonpositive_step_time_rejected():
    with pytest.raises(ValueError):
        StepReporter("m", registry=MetricRegistry()).step(0.0)


def test_flops_accounting_matches_bench_formula():
    # B*S*(6N + 12*L*h*S) — the PaLM-appendix accounting bench.py used
    n_params, L, h, S, B = 350_000_000, 24, 1024, 1024, 8
    assert transformer_step_flops(n_params, L, h, S, B) == \
        B * S * (6 * n_params + 12 * L * h * S)


def test_peak_flops_table():
    assert peak_flops("TPU v5 lite") == 197e12
    assert peak_flops("TPU v4") == 275e12
    assert peak_flops("cpu") is None
    assert peak_flops("") is None
    # a TPU nobody entered a peak for is an error, not "no MFU"
    with pytest.raises(ValueError, match="TPU v9"):
        peak_flops("TPU v9")
