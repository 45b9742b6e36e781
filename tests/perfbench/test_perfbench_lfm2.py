"""The `lfm2_moe` configuration's part of the benchmark (ISSUE 35): the cell
`lfm2_24b_a2b_d9.docextract_backlog` end to end at a size the CPU holds,
traced and untraced. Its entries are BENCHMARK.json's last (a seventh cell:
`test_perfbench_afmoe.py` still ends one test on PR 33's count of six, which
a `benchmark` PR has to lift). The configuration file against the catalog's numbers and
its parameter count; the mix, number for number; the ops-and-bytes count of a
prefill by its true tokens and of a step by its routing and its state; the
readers on made-up spans and programs; and a checkout whose program cannot
state the model refused before anything runs."""

import dataclasses
import json
import types

import numpy as np
import pytest

import perfbench_tiny as tiny
from apex_tpu.models import generate as gen
from apex_tpu.observability import set_tracer
from apex_tpu.observability.profiling import Span
from perfbench import opsbytes_lfm2 as ob, trace_reduce as tr, traffic
from perfbench import run as run_module
from perfbench.readers import hybrid
from perfbench.references import lfm2_moe as ref
from perfbench.runners import serve_lfm2

CONFIG, MIX = "lfm2_24b_a2b_d9", "docextract_backlog"
CELL = CONFIG + "." + MIX
SEED = 2 ** 31 + 3535
BACKLOG = {"batch_occupancy_mean", "kv_page_utilization",
           "decode_step_ms_p50", "decode_program_ms_p50", "device_idle_share",
           "idle_ms_per_decode_step", "decode_gather_useful_share",
           "span_clock_slack_us", "peak_hbm_gib", "compiles_in_window"}
MOE = {"experts_hit_share", "expert_tokens_per_layer_step"}
HYBRID = {"prefill_mfu", "prefill_ms_per_ktok", "prefill_device_share",
          "decode_mfu", "decode_hbm_share", "state_write_ms_per_admission"}
PERIOD = ["full_attention", "conv", "conv", "conv"]
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return tiny._load("perfbench", "configs", CONFIG + ".json")


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("the catalog of architectures is not on this machine")
    return next(r for r in rows if r["name"] == "LFM2-24B-A2B")


def build(dst):
    """`perfbench_tiny.build` plus this configuration and its mix, cut the
    same way: hidden 64, 8 heads of 32 over 4 (a position's heads lie side by
    side in a page, as the cell's do), 8 experts all held, prompts that end 2
    to 5 short of their buckets, float32."""
    root = tiny.build(dst)
    cfg = config()
    cfg.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=48,
               num_attention_heads=8, num_key_value_heads=4, head_dim=32,
               num_experts=8, vocab_size=256, max_position_embeddings=256,
               torch_dtype="float32")
    tiny._dump(cfg, root, "perfbench", "configs", CONFIG + ".json")
    mix = tiny._load("perfbench", "traffic", MIX + ".json")
    mix["arrivals"]["requests"] = 600
    mix["prompt_len"] = {"dist": "uniform", "range": [8, 32],
                         "snap": [6, 13, 19, 30]}
    mix["output_len"] = {"dist": "uniform", "range": [4, 12]}
    mix.update(lead_in_s=0.3, trace_s=0.5)
    mix["engine"] = dict(max_batch=4, page_size=8, max_prompt_len=32,
                         max_new_cap=12, num_pages=24)
    mix["check"] = {"sample_tokens": 20, "logit_gap_limit": 0.01,
                    "logit_gap_mean_limit": 0.001,
                    "logit_gap_first_steps_limit": 0.001}
    tiny._dump(mix, root, "perfbench", "traffic", MIX + ".json")
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return build(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr("perfbench.runners.serve.PAD_TO", 8)


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
    assert line["compared"]["served_logit_gap_mean"]["value"] <= 1e-4
    assert line["compared"]["served_logit_gap_first_steps"]["value"] <= 1e-4
    assert printed.err.strip().splitlines()[-1] == "correct: True"


def test_the_calibration_plants_the_state_at_the_buckets_end(root):
    """A control seed gets a second window with the fault the padding
    invites planted in the program: the gap over each request's first two
    decode steps sees it where the mean over the whole sample moves less."""
    records = []
    serve_lfm2.calibrate(CELL, [SEED], {SEED}, 1.0, records.append,
                         root=root, require_tpu=False)
    sound, planted = records
    assert (sound["planted"], planted["planted"]) == (False, True)
    assert {"control_fp8_gap", "random_token_gap"} <= set(sound)
    assert sound["served_logit_gap"]["first_steps"] < 1e-4
    assert planted["served_logit_gap"]["first_steps"] > 0.01
    assert planted["served_logit_gap"]["first_steps"] > \
        2 * planted["served_logit_gap"]["mean"]
    assert gen._prefill_attend.__name__ == "_prefill_attend"


def test_the_traced_run_reports_what_the_host_can_read(root, capsys):
    """On the CPU there is no device plane and no peak: the readers of the
    device trace and of the peaks return nothing; occupancy, the routing's
    counts and the cost of the state's write come from the program's spans."""
    line, _ = drive(root, 1, capsys)
    assert line["correct"] is True
    listed = {m["name"] for m in tiny._load("BENCHMARK.json")["per_layer"]
              if CELL in m["workloads"]}
    assert listed == {n + ".backlog" for n in BACKLOG} | {
        n + ".moe_backlog" for n in MOE} | {
        n + ".hybrid_backlog" for n in HYBRID}
    got = line["metrics"]
    assert {"batch_occupancy_mean.backlog", "compiles_in_window.backlog",
            "decode_gather_useful_share.backlog",
            "experts_hit_share.moe_backlog",
            "expert_tokens_per_layer_step.moe_backlog",
            "state_write_ms_per_admission.hybrid_backlog"} <= set(got) \
        <= listed
    assert got["compiles_in_window.backlog"]["value"] == 0
    assert not {"decode_mfu.hybrid_backlog", "prefill_mfu.hybrid_backlog",
                "prefill_device_share.hybrid_backlog"} & set(got)
    assert 0 < got["experts_hit_share.moe_backlog"]["value"] <= 100
    # every expert is held: 4 rows x 4 assignments a layer and step when full
    assert 8 < got["expert_tokens_per_layer_step.moe_backlog"]["value"] <= 16
    assert got["state_write_ms_per_admission.hybrid_backlog"]["value"] > 0


def test_a_program_that_cannot_state_the_model_is_refused_before_anything_runs(
        root, monkeypatch, capsys):
    """What the parent commit does with this cell: its `LlamaConfig` states
    windows and experts held and nothing of conv layers; the runner says so
    and exits before the device opens. A program that knows the fields and
    refuses the value is refused the same way."""
    from apex_tpu.models import llama

    parent = dataclasses.make_dataclass("ParentConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)
        if f.name not in ("conv_L_cache", "conv_bias", "route_norm_eps")],
        frozen=True)
    opened = []
    monkeypatch.setattr("perfbench.harness.Run.open_device",
                        lambda self: opened.append(1))
    monkeypatch.setattr(llama, "LlamaConfig", parent)
    with pytest.raises(SystemExit) as refusal:
        drive(root, 0, capsys)
    assert "conv_L_cache" in str(refusal.value) and not opened
    assert "route_norm_eps" in str(refusal.value)
    assert "nothing was run" in str(refusal.value)
    assert capsys.readouterr().out == ""

    def knows_no_conv(**kw):
        raise ValueError(f"unknown layer type in {kw['layer_types']}")

    monkeypatch.undo()
    knows_no_conv.__dataclass_fields__ = llama.LlamaConfig.__dataclass_fields__
    monkeypatch.setattr("perfbench.harness.Run.open_device",
                        lambda self: opened.append(1))
    monkeypatch.setattr(llama, "LlamaConfig", knows_no_conv)
    with pytest.raises(SystemExit, match="refuses the model"):
        drive(root, 0, capsys)
    assert not opened


def test_the_runner_states_the_model_from_the_files_keys():
    cfg = serve_lfm2.model_config(config())
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.expert_layers,
            cfg.cache_layers, cfg.conv_layers) == (9, 1, 8, 2, 7)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size) == (
        2048, 32, 8, 64, 11776, 1536)
    assert (cfg.num_experts, cfg.held, cfg.moe_top_k, cfg.num_shared_experts,
            cfg.moe_capacity_factor) == (64, (0, 64), 4, 0, None)
    assert (cfg.router_score, cfg.route_norm, cfg.route_scale,
            cfg.route_norm_eps, cfg.router_bias) == ("sigmoid", True, 1.0,
                                                     1e-6, True)
    assert cfg.layer_types == ("conv",) + tuple(PERIOD) * 2
    assert (cfg.conv_L_cache, cfg.conv_bias, cfg.qk_norm, cfg.tie_embeddings,
            cfg.rope_theta, cfg.rms_eps) == (3, False, True, True, 1e6, 1e-5)
    assert (cfg.sandwich_norm, cfg.attn_output_gate, cfg.embed_scale,
            cfg.rope_full_attention) == (False, False, 1.0, True)
    assert cfg.dropless and cfg.hybrid and not cfg.windowed
    assert cfg.vocab_size == 65536 and str(cfg.dtype) == "bfloat16"


def test_the_weights_and_the_tokens_are_the_seeds(root):
    """Every expert is held, so no draw of the weights decides how much of
    the routing lands here: the weights follow `--seed` like the token ids."""
    import jax
    from perfbench import harness

    leaves = []
    for seed in (SEED, SEED + 1):
        run = harness.Run(CELL, seed, 1.5, 0, root=root, require_tpu=False)
        server = serve_lfm2.Lfm2Server(run)
        server.start(seed)
        assert server.engine.scheduler.cache.conv_state.shape == (7, 2, 4, 64)
        leaves.append(jax.tree_util.tree_leaves(server.params))
    assert not any(np.array_equal(a, b) for a, b in zip(*leaves)
                   if a.size > 64)
    assert "weights_draw" not in config()


def test_the_cell_is_appended_and_the_contract_takes_it():
    """What this cell brought to BENCHMARK.json lies at the end of every list
    it entered (its configuration, the cell, its six metrics, its name in the
    accepted metrics' `workloads`), and the file is a benchmark the
    contract's own tests accept."""
    import test_perfbench_contract as contract

    bench = tiny._load("BENCHMARK.json")
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in bench["per_layer"][-len(HYBRID):]} == {
        n + ".hybrid_backlog" for n in HYBRID}
    assert all(m["workloads"] == [CELL]
               for m in bench["per_layer"][-len(HYBRID):])
    gained = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert len(gained) == 1 + len(BACKLOG) + len(MOE) + len(HYBRID)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert CELL not in metric.get("workloads", [])[:-1]
    assert (bench["command"], bench["paths"], bench["run_seconds"]) == (
        ["python3", "perfbench/run.py"], ["perfbench", "tests/perfbench"], 45)
    for check in (contract.test_configs, contract.test_workloads,
                  contract.test_end_to_end, contract.test_per_layer):
        check(bench)
    assert len(json.dumps(bench, indent=1)) <= 64 * 1024


def test_the_configuration_is_the_catalogs_but_for_the_three_reduced_keys():
    bench = tiny._load("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    body, row = config(), catalog_row()
    assert entry["source"] == body["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "layer_types"]
    assert sorted(entry["reduced"]) == sorted(body["published"])
    differs = {k for k, v in row["config"].items() if body.get(k) != v}
    assert differs == set(entry["reduced"])
    assert body["published"] == {k: row["config"][k]
                                 for k in entry["reduced"]}
    assert (body["published"]["num_hidden_layers"],
            body["published"]["num_dense_layers"]) == (40, 2)
    assert body["published"]["layer_types"] == ["conv", "conv"] + PERIOD * 9 \
        + ["full_attention", "conv"]
    # one leading dense conv layer and two whole periods: published layers
    # 1 to 9, a contiguous slice of the stack
    assert body["layer_types"] == ["conv"] + PERIOD * 2 == \
        body["published"]["layer_types"][1:10]
    assert (body["num_hidden_layers"], body["num_dense_layers"]) == (9, 1)
    assert body["torch_dtype"] == "bfloat16" and body["tie_word_embeddings"]
    assert "5 pipeline stages" in body["deployment"]
    assert "layers 1 to 9" in body["deployment"]
    assert {"head", "final_norm", "head_dim", "conv", "attention", "router",
            "torch_dtype", "weights", "eos", "not_built"} <= set(
        body["assumed"])
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, MIX, 1)
    # the contract's own limits: 24 cells, a quarter of them on four chips
    assert len(bench["workloads"]) == 7 <= 24
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert moved["workloads"][-1] == CELL and moved["bound"] == 0.02


def test_the_mix_is_the_issues():
    mix = tiny._load("perfbench", "traffic", MIX + ".json")
    # of the issue's two levers for the spread only the order is used (the
    # mix's `order_note`); a run takes under a fifth of the queue
    assert mix["arrivals"] == {"process": "backlog", "requests": 4096}
    assert mix["order_seed"] != 0
    assert mix["prompt_len"] == {
        "dist": "uniform", "range": [1024, 4096],
        "snap": [1000, 1500, 2000, 2500, 3000, 3500, 4000]}
    assert mix["output_len"] == {"dist": "uniform", "range": [32, 128]}
    assert (mix["lead_in_s"], mix["trace_s"]) == (12.0, 6.0)
    assert mix["engine"] == {"max_batch": 32, "page_size": 128,
                             "max_prompt_len": 4096, "max_new_cap": 128,
                             "num_pages": 1056}
    assert {"sample_tokens", "logit_gap_limit", "logit_gap_mean_limit",
            "logit_gap_first_steps_limit", "limit_note"} <= set(mix["check"])
    # 32 rows x 33 pages of the two attention layers; with the trash page, in
    # GiB, what `engine_note` says (the issue's "1 MiB" a page doubles it)
    cfg = config()
    page = 128 * ob.attention_layers(cfg) * ob.kv_bytes_per_position(cfg)
    assert page == 2 ** 19 and 32 * 33 == 1056
    assert round(1057 * page / 2 ** 30, 2) == 0.52
    assert 32 * ob.state_bytes_per_row(cfg) == 1.75 * 2 ** 20
    plan = traffic.serve_schedule(mix, SEED, 45.0, cfg["vocab_size"])
    assert len(plan) == 4096
    lengths = sorted({len(p.prompt) for p in plan})
    assert lengths == [1000, 1500, 2000, 2500, 3000, 3500, 4000]
    assert traffic.prompt_buckets(plan, 128) == [
        1024, 1536, 2048, 2560, 3072, 3584, 4096]
    # every prompt ends 24 to 96 positions before its bucket does
    short = {-(-n // 128) * 128 - n for n in lengths}
    assert short == {24, 36, 48, 60, 72, 84, 96}
    outs = [p.max_new_tokens for p in plan]
    assert (min(outs), max(outs)) == (32, 128) and np.mean(outs) == 80
    assert max(int(p.prompt.max()) for p in plan) < 65536
    # long in, short out: thirty prompt tokens to an output token
    assert 28 < sum(len(p.prompt) for p in plan) / sum(outs) < 34


def test_the_files_parameter_count():
    cfg = config()
    assert ob.conv_weights(cfg) + ob.conv_taps(cfg) == 16_783_360
    assert ob.attention_weights(cfg) == 10_485_760
    assert ob.expert_weights(cfg) == 9_437_184
    assert ob.dense_ffn_weights(cfg) == 72_351_744
    assert 64 * ob.expert_weights(cfg) + ob.router_weights(cfg) == 604_110_848
    assert ob.parameter_count(cfg) == 5_177_950_464
    assert round(ob.parameter_count(cfg) / 1e9, 2) == 5.18
    assert ob.router_bias_count(cfg) == 8 * 64
    # what `init` makes, leaf by leaf: the count and the biases
    total = sum(
        n * sum(int(np.prod(s)) for s in ref.parameter_shapes(
            cfg, operator, routed).values())
        for operator, routed, n in ref.runs(cfg))
    total += 8 * sum(int(np.prod(s)) for s in ref.expert_shapes(cfg).values())
    assert total + 2048 * 65536 + 2048 == 5_177_950_464 + 8 * 64
    # 10.36 GB at 2 bytes a parameter, 9.64 GiB
    assert round(2 * ob.parameter_count(cfg) / 1e9, 2) == 10.36
    assert round(2 * ob.parameter_count(cfg) / 2 ** 30, 2) == 9.64
    # uncut: the published 24B
    whole = {**cfg, **cfg["published"]}
    assert round(ob.parameter_count(whole) / 1e9, 1) == 23.8


def test_ops_and_bytes_follow_the_tokens_the_routing_and_the_state():
    cfg = config()
    unrouted = ob.unrouted_layer_weights(cfg)
    assert unrouted == (7 * 4 * 2048 ** 2 + 2 * 10_485_760 + 72_351_744
                        + 8 * 2048 * 64)
    # 1.03 GFLOP a token: experts 59%, conv operators 23%, dense FFN 14%
    per_token = ob.token_flops(cfg)
    assert round(per_token / 1e9, 2) == 1.03
    assert round(2 * 8 * 4 * ob.expert_weights(cfg) / per_token, 2) == 0.59
    assert round(2 * 7 * (ob.conv_weights(cfg) + ob.conv_taps(cfg))
                 / per_token, 2) == 0.23
    assert round(2 * ob.dense_ffn_weights(cfg) / per_token, 2) == 0.14
    head = 2 * 2048 * 65536
    # a prefill counts its true tokens: causal attention on 2 layers
    assert ob.prefill_flops(cfg, 1) == per_token + 2 * 4 * 32 * 64 + head
    attn = 2 * 4 * 32 * 64 * 2500 * 2501 / 2
    assert ob.prefill_flops(cfg, 2500) == per_token * 2500 + attn + head
    assert round(ob.prefill_flops(cfg, 2500) / 1e12, 1) == 2.6
    assert ob.prefill_flops(cfg, 2500) < ob.prefill_flops(cfg, 2560)
    # a step: K and V on the two attention layers, the state read and written
    kv = ob.kv_bytes_per_position(cfg)
    assert kv == 2048 and ob.state_bytes_per_row(cfg) == 7 * 2 * 2048 * 2
    base = ob.decode_bytes(cfg, 0, 0, 0)
    assert base == 2 * (unrouted + 7 * 2048 * 3 + 2048 * 65536)
    assert ob.decode_bytes(cfg, 32, 1000, 5) - base == (
        2 * 1000 * kv + 5 * 2 * 9_437_184 + 2 * 32 * 7 * 2 * 2048 * 2)
    # touched experts, never all 512: a step of 32 rows can touch 128 a layer
    assert ob.decode_bytes(cfg, 32, 0, 400) < ob.decode_bytes(cfg, 32, 0, 512)
    assert ob.decode_flops(cfg, 1, 0, 0) == 2 * (
        unrouted + 7 * 2048 * 3 + 2048 * 65536)
    assert ob.decode_flops(cfg, 32, 1000, 1024) == (
        32 * ob.decode_flops(cfg, 1, 0, 0) + 1024 * 2 * 9_437_184
        + 1000 * 2 * 4 * 32 * 64)


class Ring:
    def __init__(self, spans):
        self.spans = spans

    def completed(self, since=0):
        return list(self.spans)

    def dropped(self, since=0):
        return 0


def span(name, seq, end_s, ms=10.0, **args):
    return Span(name, 1, int((end_s - ms / 1e3) * 1e9), int(end_s * 1e9), 0,
                seq, seq + 1, None, None, args, False)


DECODE = {"rows": 32, "pages_live": 700, "positions": 80_000,
          "expert_tokens": 1024, "experts_hit": 440, "expert_layers": 8,
          "experts_held": 64, "conv_layers": 7, "state_bytes": 1_835_008}


@pytest.mark.parametrize("fields,present", [
    (DECODE, True),
    ({"rows": 8, "pages_live": 10, "pages_gathered": 40}, False),  # no model
])
def test_the_readers_read_the_spans_fields(fields, present):
    cfg = config()
    programs = [("jit__decode_step", i * 100_000_000, d)
                for i, d in enumerate((19_000_000, 20_000_000, 21_000_000))]
    programs += [("jit__serving_prefill_s2560", 400_000_000, 60_000_000),
                 ("jit__serving_prefill_s1024", 500_000_000, 30_000_000),
                 ("jit__serving_prefill_s4096", 600_000_000, 90_000_000)]
    trace = tr.Trace(window=(0, 6_000_000_000), modules={0: programs})
    run = types.SimpleNamespace(
        trace=trace, peak=PEAK, config=cfg, window_s=6.0,
        counters={"trace_t0": 1000.0, "trace_t1": 1006.0, "t_open": 1000.0,
                  "t_close": 1006.0})
    spans = [span("serving/decode", i, 1001.0 + i, **fields)
             for i in range(3)]
    if present:     # the bucket of 4,096 ran in the trace; no admission of
        spans += [  # it ended there: that program is left out, both ways
            span("serving/admit", 10, 1004.0, prompt_tokens=2500, bucket=2560),
            span("serving/admit", 11, 1004.5, prompt_tokens=1000, bucket=1024),
            span("serving/write_state", 12, 1004.6, ms=0.5, conv_layers=7),
            span("serving/write_state", 13, 1004.7, ms=1.5, conv_layers=7)]
    previous = set_tracer(Ring(spans))
    try:
        got = {what: hybrid.read(run, what) for what in (
            "prefill_mfu", "prefill_ms_per_ktok", "prefill_device_share",
            "decode_mfu", "decode_hbm_share", "state_write_ms")}
    finally:
        set_tracer(previous)
    assert got["prefill_device_share"] == pytest.approx(100 * 180 / 240)
    if not present:
        assert {v for k, v in got.items()
                if k != "prefill_device_share"} == {None}
        return
    assert got["prefill_ms_per_ktok"] == pytest.approx(90.0 / 3.5)
    assert got["prefill_mfu"] == pytest.approx(100 * (
        ob.prefill_flops(cfg, 2500) + ob.prefill_flops(cfg, 1000))
        / (0.090 * 197e12))
    assert got["decode_mfu"] == pytest.approx(
        100 * 3 * ob.decode_flops(cfg, 32, 80_000, 1024) / (6.0 * 197e12))
    assert got["decode_hbm_share"] == pytest.approx(
        100 * ob.decode_bytes(cfg, 32, 80_000, 440) / 819e9 / 0.020)
    assert got["state_write_ms"] == pytest.approx(1.0)
    assert max(got["prefill_mfu"], got["decode_hbm_share"]) < 100
    run.trace = None
    previous = set_tracer(Ring(spans))
    try:
        assert {hybrid.read(run, what) for what in (
            "prefill_mfu", "prefill_device_share",
            "decode_hbm_share")} == {None}
    finally:
        set_tracer(previous)
