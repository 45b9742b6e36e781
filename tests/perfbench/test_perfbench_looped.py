"""The looped configuration's part of the benchmark (ISSUE 29): the cell
`ouro_2p6b.reasoning_backlog` end to end at a size the CPU holds, traced and
untraced; the configuration file against the published numbers; the
ops-and-bytes count of a stack that is run several times; the reader of the
device time per layer pass; and a checkout whose program has no loop refused
before anything runs."""

import dataclasses
import json
import os
import types

import pytest

import perfbench_tiny as tiny
from apex_tpu.observability import set_tracer
from apex_tpu.observability.profiling import Span
from perfbench import opsbytes, opsbytes_looped, trace_reduce as tr
from perfbench import run as run_module
from perfbench.readers import decode_looped
from perfbench.runners import serve, serve_looped

CELL = "ouro_2p6b.reasoning_backlog"
SEED = 2 ** 31 + 2929
BACKLOG = {"batch_occupancy_mean", "kv_page_utilization",
           "decode_step_ms_p50", "decode_program_ms_p50", "device_idle_share",
           "idle_ms_per_decode_step", "decode_gather_useful_share",
           "span_clock_slack_us", "peak_hbm_gib", "compiles_in_window"}
LOOPED = {"decode_mfu", "decode_hbm_share", "decode_ms_per_layer_pass"}
# https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
PUBLISHED = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
             "max_position_embeddings": 65536, "max_window_layers": 48,
             "num_attention_heads": 16, "num_hidden_layers": 48,
             "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
             "rope_theta": 1000000, "total_ut_steps": 4,
             "early_exit_threshold": 1, "vocab_size": 49152}


def config():
    return tiny._load("perfbench", "configs", "ouro_2p6b.json")


def build(dst):
    """`perfbench_tiny.build` plus the looped configuration and its mix, cut
    the same way: hidden 64, 2 layers x 4 passes, 4 heads of 16, float32."""
    root = tiny.build(dst)
    cfg = config()
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, num_hidden_layers=2,
               vocab_size=256, max_position_embeddings=256,
               torch_dtype="float32")
    tiny._dump(cfg, root, "perfbench", "configs", "ouro_2p6b.json")
    mix = tiny._load("perfbench", "traffic", "reasoning_backlog.json")
    mix["arrivals"]["requests"] = 600
    mix["prompt_len"] = {"dist": "uniform", "range": [8, 32],
                         "snap": [8, 16, 24, 32]}
    mix["output_len"] = {"dist": "uniform", "range": [8, 16]}
    mix.update(lead_in_s=0.3, trace_s=0.5)
    mix["engine"] = dict(max_batch=4, page_size=8, max_prompt_len=32,
                         max_new_cap=16, num_pages=24)
    mix["check"] = {"sample_tokens": 20, "logit_gap_limit": 0.01}
    tiny._dump(mix, root, "perfbench", "traffic", "reasoning_backlog.json")
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return build(tmp_path_factory.mktemp("checkout"))


def drive(root, trace, capsys, seed=SEED):
    line = run_module.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "1.5",
         "--trace", str(trace)], require_tpu=False, root=root)
    return line, capsys.readouterr()


def test_the_cell_runs_and_prints_the_result_line(root, capsys):
    line, printed = drive(root, 0, capsys)
    assert json.loads(printed.out.strip().splitlines()[-1]) == \
        json.loads(json.dumps(line))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["compared"]["served_logit_gap"]["value"] <= 1e-3
    assert printed.err.strip().splitlines()[-1] == "correct: True"


def test_the_traced_run_reports_what_the_host_can_read(root, capsys):
    """On the CPU there is no device plane and no peak: the readers of the
    device trace and of the peaks return nothing; what is left is the host's
    counters and spans, under the `.backlog` names the cell was appended to.
    Every metric the cell can report is one BENCHMARK.json lists for it."""
    line, _ = drive(root, 1, capsys)
    assert line["correct"] is True
    listed = {m["name"] for m in tiny._load("BENCHMARK.json")["per_layer"]
              if CELL in m["workloads"]}
    assert listed == {n + ".backlog" for n in BACKLOG} | {
        n + ".looped_backlog" for n in LOOPED}
    assert {"batch_occupancy_mean.backlog", "kv_page_utilization.backlog",
            "decode_step_ms_p50.backlog", "compiles_in_window.backlog",
            "decode_gather_useful_share.backlog"} <= set(line["metrics"]) \
        <= listed
    assert line["metrics"]["compiles_in_window.backlog"]["value"] == 0
    assert "decode_mfu.backlog" not in line["metrics"]


def test_a_program_without_the_loop_is_refused_before_anything_runs(
        root, monkeypatch, capsys):
    """What the parent commit does with this cell: `LlamaConfig` has no
    `num_passes`, and the runner says so and exits before the device opens."""
    from apex_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class OldConfig:
        vocab_size: int = 0
        hidden_size: int = 0
        intermediate_size: int = 0
        num_layers: int = 0
        num_heads: int = 1
        num_kv_heads: int = 0
        max_seq_len: int = 0
        rope_theta: float = 0.0
        rms_eps: float = 0.0
        dtype: object = None
        tie_embeddings: bool = False

        head_dim = llama.LlamaConfig.head_dim

    monkeypatch.setattr(llama, "LlamaConfig", OldConfig)
    opened = []
    monkeypatch.setattr("perfbench.harness.Run.open_device",
                        lambda self: opened.append(1))
    with pytest.raises(SystemExit) as refusal:
        drive(root, 0, capsys)
    assert "num_passes" in str(refusal.value) and not opened
    assert capsys.readouterr().out == ""


def test_the_runner_builds_the_looped_config_from_the_files_keys():
    cfg = serve_looped.model_config(config())
    plain = serve.model_config(config())
    assert (cfg.num_passes, cfg.sandwich_norm, cfg.cache_layers) == (4, True,
                                                                     192)
    assert dataclasses.replace(cfg, num_passes=1, sandwich_norm=False) == plain
    assert (cfg.num_layers, cfg.head_dim, cfg.rms_eps) == (48, 128, 1e-6)
    with pytest.raises(SystemExit, match="head_dim"):
        serve_looped.model_config({**config(), "head_dim": 64})


def test_the_configuration_keeps_every_published_number():
    bench = tiny._load("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "ouro_2p6b")
    body = config()
    assert entry["reduced"] == [] and body["published"] == {}
    assert entry["source"] == body["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert [k for k, v in PUBLISHED.items() if body.get(k) != v] == []
    assert body["layer_types"] == ["full_attention"] * 48
    assert body["sliding_window"] is None and body["rope_scaling"] is None
    assert not body["tie_word_embeddings"] and not body["use_sliding_window"]
    assert (body["hidden_act"], body["model_type"], body["torch_dtype"]) == (
        "silu", "ouro", "bfloat16")
    assert {"sandwich_norm", "norm_between_passes", "cache_index",
            "exit_gate", "exit_rule", "weights"} <= set(body["assumed"])
    assert "whole model" in body["deployment"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro_2p6b", "reasoning_backlog", 1)


def test_the_mix_is_the_issues():
    mix = tiny._load("perfbench", "traffic", "reasoning_backlog.json")
    assert mix["arrivals"] == {"process": "backlog", "requests": 96}
    assert mix["prompt_len"] == {"dist": "uniform", "range": [32, 128],
                                 "snap": [32, 64, 96, 128]}
    assert mix["output_len"] == {"dist": "uniform", "range": [96, 192]}
    assert (mix["lead_in_s"], mix["trace_s"]) == (12.0, 6.0)
    engine = mix["engine"]
    assert engine["max_batch"] * 5 == engine["num_pages"]
    assert (engine["page_size"], engine["max_prompt_len"],
            engine["max_new_cap"]) == (64, 128, 192)
    # the pages, with the trash page, in GiB: what `engine_note` states
    pages = (engine["num_pages"] + 1) * engine["page_size"] \
        * opsbytes_looped.kv_bytes_per_token(config())
    assert round(pages / 2 ** 30, 2) == 3.84


def test_ops_and_bytes_of_a_stack_that_is_run_four_times():
    cfg = config()
    assert opsbytes_looped.layer_parameters(cfg) == 51_388_416
    assert opsbytes_looped.parameter_count(cfg) == 2_667_974_657
    assert opsbytes_looped.layer_passes(cfg) == 192
    assert opsbytes_looped.kv_bytes_per_token(cfg) == 1_572_864
    assert opsbytes_looped.kv_bytes_per_token(cfg) == \
        4 * opsbytes.kv_bytes_per_token(cfg)
    weights = opsbytes_looped.decode_bytes(cfg, [])
    assert weights == 19_931_332_608            # 19.9 GB a decode step
    assert weights == 2 * (4 * 48 * opsbytes.llama_layer_weights(cfg)
                           + 2048 * 49152)
    assert opsbytes_looped.decode_bytes(cfg, [100, 220]) == \
        weights + 320 * 1_572_864
    assert opsbytes_looped.decode_flops(cfg, 0) == weights   # 2 a weight
    assert opsbytes_looped.decode_flops(cfg, 10) - weights == \
        192 * 4 * 16 * 128 * 10
    # one pass is the plain count
    once = {**cfg, "total_ut_steps": 1}
    assert opsbytes_looped.decode_bytes(once, [7]) == \
        opsbytes.llama_decode_bytes(once, [7])
    assert opsbytes_looped.decode_flops(once, 7) == \
        opsbytes.llama_decode_flops(once, 7)


class Ring:
    def __init__(self, spans):
        self.spans = spans

    def completed(self, since=0):
        return list(self.spans)

    def dropped(self, since=0):
        return 0


def decode_span(seq, end_s, **args):
    return Span("serving/decode", 1, int((end_s - 0.01) * 1e9),
                int(end_s * 1e9), 0, seq, seq + 1, None, None, args, False)


@pytest.mark.parametrize("args,expect", [
    ({"rows": 8, "layer_passes": 192, "cache_layers": 192}, 96.0 / 192),
    ({"rows": 8}, None),                     # the parent's spans: no field
])
def test_ms_per_layer_pass_reads_the_spans_field(args, expect):
    trace = tr.Trace(window=(0, 6_000_000_000), modules={0: [
        ("jit__decode_step", i * 100_000_000, d)
        for i, d in enumerate((95_000_000, 96_000_000, 99_000_000))]})
    run = types.SimpleNamespace(
        trace=trace, peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        config=config(), window_s=6.0,
        counters={"trace_t0": 1000.0, "trace_t1": 1006.0},
        series={"steps": [{"t": 1001.0 + i, "rows": 8, "context": 1600}
                          for i in range(3)]},
        in_trace=lambda t: 1000.0 <= t <= 1006.0)
    previous = set_tracer(Ring([decode_span(i, 1001.0 + i, **args)
                                for i in range(3)]))
    try:
        got = decode_looped.read(run, "ms_per_layer_pass")
        share = decode_looped.read(run, "hbm_share")
        mfu = decode_looped.read(run, "mfu")
    finally:
        set_tracer(previous)
    assert got == (pytest.approx(expect) if expect else None)
    nbytes = 19_931_332_608 + 1600 * 1_572_864
    assert share == pytest.approx(
        100 * nbytes / 819e9 / ((0.095 + 0.096 + 0.099) / 3))
    assert mfu == pytest.approx(100 * 3 * (8 * 19_931_332_608 + 1600
                                           * 192 * 4 * 16 * 128)
                                / (6.0 * 197e12))
    run.trace = None
    assert decode_looped.read(run, "hbm_share") is None
    assert decode_looped.read(run, "ms_per_layer_pass") is None
