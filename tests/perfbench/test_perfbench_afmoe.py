"""The `afmoe` configuration's part of the benchmark (ISSUE 33): the cell
`trinity_large_ep8_d5.longctx_backlog` end to end at a size the CPU holds,
traced and untraced; the configuration file against the published numbers and
its parameter count; the ops-and-bytes count of a step that depends on its
routing and on the window; the readers on made-up spans; the mix's lengths;
and a checkout whose program cannot state the model refused before anything
runs."""

import dataclasses
import json
import types

import numpy as np
import pytest

import perfbench_tiny as tiny
from apex_tpu.observability import set_tracer
from apex_tpu.observability.profiling import Span
from perfbench import opsbytes_afmoe as ob, trace_reduce as tr, traffic
from perfbench import run as run_module
from perfbench.readers import decode_afmoe
from perfbench.references import afmoe as ref
from perfbench.runners import serve_afmoe

CELL = "trinity_large_ep8_d5.longctx_backlog"
SEED = 2 ** 31 + 3333
BACKLOG = {"batch_occupancy_mean", "kv_page_utilization",
           "decode_step_ms_p50", "decode_program_ms_p50", "device_idle_share",
           "idle_ms_per_decode_step", "decode_gather_useful_share",
           "span_clock_slack_us", "peak_hbm_gib", "compiles_in_window"}
MOE = {"decode_mfu", "decode_hbm_share", "experts_hit_share",
       "expert_tokens_per_layer_step", "window_pages_skipped_share"}
REDUCED = {"num_hidden_layers": (60, 5), "num_dense_layers": (6, 1),
           "num_experts": (256, 32), "vocab_size": (200192, 25024)}


def config():
    return tiny._load("perfbench", "configs", "trinity_large_ep8_d5.json")


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("the catalog of architectures is not on this machine")
    return next(r for r in rows if r["name"] == "Trinity-Large-Preview")


def build(dst):
    """`perfbench_tiny.build` plus this configuration and its mix, cut the
    same way: hidden 64, 4 heads of 32, 4 of 16 experts held, a window of 24
    under contexts of 16 to 48, float32."""
    root = tiny.build(dst)
    cfg = config()
    cfg.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=48,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               num_experts=4, experts_held={"first": 4, "count": 4, "of": 16},
               num_experts_per_tok=2, sliding_window=24, vocab_size=256,
               max_position_embeddings=256, torch_dtype="float32")
    tiny._dump(cfg, root, "perfbench", "configs", "trinity_large_ep8_d5.json")
    mix = tiny._load("perfbench", "traffic", "longctx_backlog.json")
    mix["arrivals"]["requests"] = 600
    mix["prompt_len"] = {"dist": "uniform", "range": [8, 32],
                         "snap": [8, 16, 24, 32]}
    mix["output_len"] = {"dist": "uniform", "range": [8, 16]}
    mix.update(lead_in_s=0.3, trace_s=0.5)
    mix["engine"] = dict(max_batch=4, page_size=8, max_prompt_len=32,
                         max_new_cap=16, num_pages=24)
    mix["check"] = {"sample_tokens": 20, "logit_gap_limit": 0.01,
                    "logit_gap_mean_limit": 0.001}
    tiny._dump(mix, root, "perfbench", "traffic", "longctx_backlog.json")
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
    device trace and of the peaks return nothing; the counts of the routing
    and of the window come from the program's spans."""
    line, _ = drive(root, 1, capsys)
    assert line["correct"] is True
    listed = {m["name"] for m in tiny._load("BENCHMARK.json")["per_layer"]
              if CELL in m["workloads"]}
    assert listed == {n + ".backlog" for n in BACKLOG} | {
        n + ".moe_backlog" for n in MOE}
    got = line["metrics"]
    assert {"batch_occupancy_mean.backlog", "compiles_in_window.backlog",
            "decode_gather_useful_share.backlog",
            "experts_hit_share.moe_backlog",
            "expert_tokens_per_layer_step.moe_backlog",
            "window_pages_skipped_share.moe_backlog"} <= set(got) <= listed
    assert got["compiles_in_window.backlog"]["value"] == 0
    assert "decode_mfu.moe_backlog" not in got          # no peak on the CPU
    assert 0 < got["experts_hit_share.moe_backlog"]["value"] <= 100
    # 4 rows x 2 assignments over 16 experts, 4 held: 2 a layer and step
    assert 0.5 < got["expert_tokens_per_layer_step.moe_backlog"]["value"] < 4
    assert 0 < got["window_pages_skipped_share.moe_backlog"]["value"] < 100


def test_a_program_that_cannot_state_the_model_is_refused_before_anything_runs(
        root, monkeypatch, capsys):
    """What the parent commit does with this cell: its `LlamaConfig` knows
    the loop and nothing of experts held or windows; the runner says so and
    exits before the device opens."""
    from apex_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class ParentConfig:
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
        num_experts: int = 0
        moe_top_k: int = 2
        moe_capacity_factor: float = 1.25
        num_passes: int = 1
        sandwich_norm: bool = False

    monkeypatch.setattr(llama, "LlamaConfig", ParentConfig)
    opened = []
    monkeypatch.setattr("perfbench.harness.Run.open_device",
                        lambda self: opened.append(1))
    with pytest.raises(SystemExit) as refusal:
        drive(root, 0, capsys)
    assert "experts_held" in str(refusal.value) and not opened
    assert "sliding_window" in str(refusal.value)
    assert capsys.readouterr().out == ""


def test_the_runner_states_the_model_from_the_files_keys():
    cfg = serve_afmoe.model_config(config())
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.expert_layers,
            cfg.cache_layers) == (5, 1, 4, 5)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size) == (
        3072, 48, 8, 128, 12288, 3072)
    assert (cfg.num_experts, cfg.held, cfg.moe_top_k, cfg.num_shared_experts,
            cfg.moe_capacity_factor) == (256, (0, 32), 4, 1, None)
    assert (cfg.router_score, cfg.route_norm, cfg.route_scale,
            cfg.router_bias) == ("sigmoid", True, 2.448, True)
    assert cfg.layer_types == ("sliding_attention",) * 4 + ("full_attention",)
    assert (cfg.sliding_window, cfg.rope_full_attention, cfg.qk_norm,
            cfg.attn_output_gate, cfg.sandwich_norm) == (4096, False, True,
                                                         True, True)
    assert cfg.embed_scale == 3072 ** 0.5 and cfg.vocab_size == 25024
    assert cfg.dropless and cfg.windowed
    with pytest.raises(SystemExit, match="experts_held.count"):
        serve_afmoe.model_config({**config(), "num_experts": 16})


def test_the_weights_are_the_configurations_draw_and_the_tokens_the_seeds(
        root):
    """Which experts a draw's router favours decides how much of the routing
    falls on the experts held, so the draw is the configuration's
    (`weights_draw`), as a mix's order is the mix's; `--seed` draws the
    token ids and where the cycle of lengths is entered."""
    import jax
    from perfbench import harness

    assert config()["weights_draw"]["seed"] == 2147484017
    leaves = []
    for seed in (SEED, SEED + 1):
        run = harness.Run(CELL, seed, 1.5, 0, root=root, require_tpu=False)
        server = serve_afmoe.AfmoeServer(run)
        server.start(seed)
        leaves.append(jax.tree_util.tree_leaves(server.params))
    assert all(np.array_equal(a, b) for a, b in zip(*leaves))
    mix = tiny._load("perfbench", "traffic", "longctx_backlog.json")
    plans = [traffic.serve_schedule(mix, seed, 45.0, 25024)
             for seed in (SEED, SEED + 1)]
    assert not np.array_equal(plans[0][0].prompt[:2048],
                              plans[1][0].prompt[:2048])


def test_the_configuration_is_the_catalogs_but_for_the_five_reduced_keys():
    bench = tiny._load("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "trinity_large_ep8_d5")
    body, row = config(), catalog_row()
    assert entry["source"] == body["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(
        list(REDUCED) + ["layer_types"]) == sorted(body["published"])
    differs = {k for k, v in row["config"].items() if body.get(k) != v}
    assert differs == set(entry["reduced"])
    for key, (published, held) in REDUCED.items():
        assert (body["published"][key], body[key]) == (published, held)
        assert row["config"][key] == published
    assert body["published"]["layer_types"] == row["config"]["layer_types"]
    # one leading dense layer and one whole period: three sliding to one full
    assert body["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]
    assert body["experts_held"] == {"first": 0, "count": 32, "of": 256}
    assert body["torch_dtype"] == "bfloat16" and "8 chips" in body["deployment"]
    assert {"embedding_multiplier", "sandwich_norm", "attention", "rope",
            "window", "router", "experts", "not_built", "weights",
            "eos"} <= set(body["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity_large_ep8_d5", "longctx_backlog", 1)
    assert len(bench["workloads"]) <= 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_mix_is_the_issues():
    mix = tiny._load("perfbench", "traffic", "longctx_backlog.json")
    assert mix["arrivals"] == {"process": "backlog", "requests": 512}
    assert mix["prompt_len"] == {"dist": "uniform", "range": [2048, 4096],
                                 "snap": [2048, 2560, 3072, 3584, 4096]}
    assert mix["output_len"] == {"dist": "uniform", "range": [512, 3072]}
    assert (mix["lead_in_s"], mix["trace_s"]) == (12.0, 6.0)
    assert mix["engine"] == {"max_batch": 32, "page_size": 128,
                             "max_prompt_len": 4096, "max_new_cap": 3072,
                             "num_pages": 1792}
    assert mix["check"]["sample_tokens"] == 3000
    # 32 rows x 56 pages; with the trash page, in GiB: what `engine_note` says
    cfg = config()
    page = 128 * cfg["num_hidden_layers"] * ob.kv_bytes_per_position(cfg)
    assert page == 2.5 * 2 ** 20
    assert round(1793 * page / 2 ** 30, 2) == 4.38
    plan = traffic.serve_schedule(mix, SEED, 45.0, cfg["vocab_size"])
    assert len(plan) == 512
    lengths = sorted({len(p.prompt) for p in plan})
    assert lengths == [2048, 2560, 3072, 3584, 4096]
    outs = [p.max_new_tokens for p in plan]
    assert min(outs) >= 512 and max(outs) <= 3072
    assert max(int(p.prompt.max()) for p in plan) < 25024
    # about half of all row-steps decode past the window's edge
    past = sum(max(0, len(p.prompt) + p.max_new_tokens - 4096) for p in plan)
    assert 0.4 < past / sum(outs) < 0.6


def test_the_files_parameter_count():
    cfg = config()
    assert ob.attention_parameters(cfg) == 62_914_816
    assert ob.dense_layer_parameters(cfg) == 176_173_312
    assert ob.expert_weights(cfg) == 28_311_552
    assert ob.expert_layer_parameters(cfg) == 997_994_752
    assert ob.parameter_count(cfg) == 4_321_902_848
    assert ob.router_bias_count(cfg) == 4 * 256
    # what `init` makes, leaf by leaf: the count and the biases
    shapes = ref.parameter_shapes(cfg)
    per_layer = {k: sum(int(np.prod(s)) for s in v.values())
                 for k, v in shapes.items()}
    total = (per_layer["dense_layers"] + 4 * per_layer["layers"]
             + 2 * 3072 * 25024 + 3072)
    assert total == 4_321_902_848 + 4 * 256
    # uncut: the published 400B
    whole = {**cfg, **cfg["published"], "experts_held": None}
    assert round(ob.parameter_count(whole) / 1e9, 1) == 398.6


def test_ops_and_bytes_follow_the_routing_and_the_window():
    cfg = config()
    unrouted = ob.unrouted_weight_reads(cfg)
    assert unrouted == (5 * 62_914_560 + 3 * 3072 * 12288
                        + 4 * (28_311_552 + 786_432) + 3072 * 25024)
    # a context inside the window: every layer reads all of it
    assert ob.attended(cfg, 3000) == 5 * 3000
    # past it: four sliding layers read 4,096, the full one everything
    assert ob.attended(cfg, 7000) == 4 * 4096 + 7000
    kv = ob.kv_bytes_per_position(cfg)
    assert kv == 4096                        # 2 x 8 x 128 x 2 B a layer
    assert ob.decode_bytes(cfg, 0, 0) == 2 * unrouted
    assert ob.decode_bytes(cfg, 100, 3) - ob.decode_bytes(cfg, 0, 0) == \
        100 * kv + 3 * 56_623_104
    # touched experts, never all 32: a step that hit 12 reads 12
    assert ob.decode_bytes(cfg, 0, 12) < ob.decode_bytes(cfg, 0, 4 * 32)
    assert ob.decode_flops(cfg, 1, 0, 0) == 2 * unrouted
    assert ob.decode_flops(cfg, 32, 1000, 64) == (
        32 * 2 * unrouted + 64 * 2 * 28_311_552 + 1000 * 4 * 48 * 128)


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


FIELDS = {"rows": 32, "pages_live": 1000, "pages_live_window": 800,
          "positions": 120_000, "positions_window": 100_000,
          "expert_tokens": 60, "experts_hit": 48, "expert_layers": 4,
          "experts_held": 32}


@pytest.mark.parametrize("args,present", [
    (FIELDS, True),
    ({"rows": 8, "pages_live": 10, "pages_gathered": 40}, False),  # the parent
])
def test_the_readers_read_the_spans_fields(args, present):
    cfg = config()
    trace = tr.Trace(window=(0, 6_000_000_000), modules={0: [
        ("jit__decode_step", i * 100_000_000, d)
        for i, d in enumerate((19_000_000, 20_000_000, 21_000_000))]})
    run = types.SimpleNamespace(
        trace=trace, peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        config=cfg, window_s=6.0,
        counters={"trace_t0": 1000.0, "trace_t1": 1006.0, "t_open": 1000.0,
                  "t_close": 1006.0})
    previous = set_tracer(Ring([decode_span(i, 1001.0 + i, **args)
                                for i in range(3)]))
    try:
        got = {what: decode_afmoe.read(run, what) for what in (
            "mfu", "hbm_share", "experts_hit_share",
            "expert_tokens_per_layer_step", "window_pages_skipped_share")}
    finally:
        set_tracer(previous)
    if not present:
        assert set(got.values()) == {None}
        return
    attended = 4 * 100_000 + 120_000
    assert got["mfu"] == pytest.approx(
        100 * 3 * ob.decode_flops(cfg, 32, attended, 60) / (6.0 * 197e12))
    assert got["hbm_share"] == pytest.approx(
        100 * ob.decode_bytes(cfg, attended, 48) / 819e9 / 0.020)
    assert got["hbm_share"] < 100
    assert got["experts_hit_share"] == pytest.approx(100 * 48 / 128)
    assert got["expert_tokens_per_layer_step"] == pytest.approx(15.0)
    assert got["window_pages_skipped_share"] == pytest.approx(20.0)
    run.trace = None
    previous = set_tracer(Ring([decode_span(0, 1001.0, **args)]))
    try:
        assert decode_afmoe.read(run, "hbm_share") is None
    finally:
        set_tracer(previous)
