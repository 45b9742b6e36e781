"""The report CLI and bench.py's telemetry glue (ISSUE 2 acceptance:
bench emits a metrics JSONL that ``python -m apex_tpu.observability
report`` summarizes; a run that finds no TPU refuses)."""

import json
import os
import subprocess
import sys

import pytest

import bench  # repo root on sys.path via tests/conftest.py
from apex_tpu.observability import MetricRegistry, read_jsonl
from apex_tpu.observability.cli import main as cli_main


def _write_sample(path):
    reg = MetricRegistry()
    reg.counter("jax/compiles", fn="train_step").inc(2)
    reg.gauge("optimizer/fused_adam/choice").set("flat")
    reg.histogram("llama/step_time_ms").observe(30.0)
    reg.event("step", reporter="llama", step_time_ms=30.0)
    reg.dump(str(path))


def test_report_cli_in_process(tmp_path, capsys):
    path = tmp_path / "m.jsonl"
    _write_sample(path)
    assert cli_main(["report", str(path)]) == 0
    out = capsys.readouterr().out
    assert "jax/compiles{fn=train_step}" in out
    assert "optimizer/fused_adam/choice" in out
    assert "llama/step_time_ms" in out


def test_report_cli_json_mode_subprocess(tmp_path):
    path = tmp_path / "m.jsonl"
    _write_sample(path)
    proc = subprocess.run(
        [sys.executable, "-m", "apex_tpu.observability", "report",
         "--json", str(path)],
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-800:]
    summary = json.loads(proc.stdout)
    assert summary["counters"]["jax/compiles{fn=train_step}"] == 2
    assert summary["gauges"]["optimizer/fused_adam/choice"] == "flat"


def test_report_cli_empty_file_exits_1(tmp_path):
    path = tmp_path / "empty.jsonl"
    path.write_text("")
    assert cli_main(["report", str(path)]) == 1


def test_metrics_report_tool_wrapper(tmp_path):
    path = tmp_path / "m.jsonl"
    _write_sample(path)
    import os
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tools",
        "metrics_report.py")
    proc = subprocess.run([sys.executable, tool, str(path)],
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert "llama/step_time_ms" in proc.stdout


def test_bench_metrics_path_env_override(tmp_path, monkeypatch):
    monkeypatch.setenv("APEX_TPU_METRICS", str(tmp_path / "x.jsonl"))
    assert bench._metrics_path() == str(tmp_path / "x.jsonl")
    monkeypatch.delenv("APEX_TPU_METRICS")
    assert bench._metrics_path().endswith("BENCH_METRICS.jsonl")


def test_bench_peak_flops_delegates_to_observability():
    from apex_tpu.observability import peak_flops
    assert bench._peak_flops("TPU v5 lite") == peak_flops("TPU v5 lite")
    assert bench._peak_flops("cpu") is None


def test_bench_refuses_a_non_tpu_backend(tmp_path):
    """Asked for the chip (no BENCH_FORCE_CPU) on a host where jax
    selects the CPU: exit non-zero, say no TPU was found, print no
    metric — never a CPU number under a device metric's name."""
    env = {k: v for k, v in os.environ.items() if k != "BENCH_FORCE_CPU"}
    env.update(JAX_PLATFORMS="cpu",
               APEX_TPU_METRICS=str(tmp_path / "m.jsonl"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    proc = subprocess.run(
        [sys.executable, "bench.py"], capture_output=True, text=True,
        timeout=300, env=env,
        cwd=os.path.dirname(os.path.abspath(bench.__file__)))
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert "{" not in proc.stdout
    assert not (tmp_path / "m.jsonl").exists()


@pytest.mark.slow
def test_bench_cpu_mode_emits_metrics_jsonl(tmp_path):
    """End-to-end: a BENCH_FORCE_CPU run writes a metrics JSONL
    whose records include step time, recompile count, and the
    kernel-dispatch choice (the ISSUE acceptance criterion), and the
    report CLI summarizes it."""
    path = tmp_path / "bench_metrics.jsonl"
    env = {**os.environ, "BENCH_FORCE_CPU": "1",
           "APEX_TPU_METRICS": str(path), "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True, text=True, timeout=1200, env=env,
        cwd=os.path.dirname(os.path.abspath(bench.__file__)))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    result = json.loads(line)
    assert "recompiles" in result and result["recompiles"] > 0
    assert result["fused_adam_dispatch_choice"] in ("tree", "flat")
    assert result["metrics_jsonl"] == path.name

    back = read_jsonl(str(path))
    types = {r["type"] for r in back}
    assert {"counter", "gauge", "event"} <= types
    steps = [r for r in back if r["type"] == "event"
             and r["name"] == "step"]
    assert steps and steps[0]["fields"]["step_time_ms"] > 0
    choice = [r for r in back if r["type"] == "gauge"
              and r["name"] == "optimizer/fused_adam/choice"]
    assert choice and choice[0]["value"] in ("tree", "flat")
    dispatch = [r for r in back if r["type"] == "counter"
                and r["name"] == "optimizer/fused_adam/dispatch"]
    assert dispatch  # trace-time path tags (tree / flat_xla / flat_pallas)
    compiles = [r for r in back if r["type"] == "counter"
                and r["name"] == "jax/compiles"]
    assert compiles

    proc = subprocess.run(
        [sys.executable, "-m", "apex_tpu.observability", "report",
         str(path)], capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert "optimizer/fused_adam/dispatch" in proc.stdout
