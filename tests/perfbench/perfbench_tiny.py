"""A checkout in miniature for the benchmark's tests: BENCHMARK.json's own
cells and metrics over configurations and mixes cut to a size the CPU holds.
Only data files differ; the harness, runners, readers and references under
test are the repo's."""

import copy
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
C1, C4 = "gpt2_345m.pretrain_s1024", "gpt2_345m.pretrain_s1024_ddp4"
C2 = "mistral7b_v03_d16.longprompt_poisson"
C3 = "mistral7b_v03_d16.longgen_backlog"
TRAIN_LIMITS = {"grad_norm_gap_worst_leaf": 0.01,
                "delta_norm_gap_worst_leaf": 0.02,
                "delta_norm_gap_mean_leaf": 0.008}


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    os.makedirs(os.path.dirname(os.path.join(*parts)), exist_ok=True)
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f)


def benchmark_with_all_cells():
    """BENCHMARK.json with the four-chip training cell put back where a PR had
    to leave it out: the tests cover its path either way."""
    bench = _load("BENCHMARK.json")
    names = {w["name"] for w in bench["workloads"]}
    if C4 not in names and C1 in names:
        cell = copy.deepcopy(next(w for w in bench["workloads"]
                                  if w["name"] == C1))
        cell.update(name=C4, traffic="pretrain_s1024_ddp4", chips=4)
        bench["workloads"].append(cell)
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if C1 in metric.get("workloads", []):
                metric["workloads"].append(C4)
    return bench


def build(dst):
    dst = str(dst)
    shutil.copytree(os.path.join(REPO, "perfbench", "metrics"),
                    os.path.join(dst, "perfbench", "metrics"))
    _dump(benchmark_with_all_cells(), dst, "BENCHMARK.json")

    gpt2 = _load("perfbench", "configs", "gpt2_345m.json")
    gpt2.update(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=64)
    gpt2["train"]["micro_batch"] = 4
    _dump(gpt2, dst, "perfbench", "configs", "gpt2_345m.json")

    mistral = _load("perfbench", "configs", "mistral7b_v03_d16.json")
    mistral.update(hidden_size=64, intermediate_size=128,
                   num_attention_heads=4, num_key_value_heads=2,
                   num_hidden_layers=2, vocab_size=256,
                   max_position_embeddings=256, torch_dtype="float32")
    _dump(mistral, dst, "perfbench", "configs", "mistral7b_v03_d16.json")

    for name in ("pretrain_s1024", "pretrain_s1024_ddp4"):
        mix = _load("perfbench", "traffic", name + ".json")
        mix.update(seq_len=64, trace_s=0.5)
        mix["check"]["limits"] = dict(TRAIN_LIMITS)
        _dump(mix, dst, "perfbench", "traffic", name + ".json")

    mix = _load("perfbench", "traffic", "longprompt_poisson.json")
    mix["arrivals"]["rate_per_s"] = 20.0
    mix["prompt_len"] = {"dist": "lognormal", "median": 24, "sigma": 0.5,
                         "clip": [8, 64], "snap": [8, 16, 24, 32, 48, 64]}
    mix["output_len"] = {"dist": "uniform", "range": [4, 8]}
    mix.update(lead_in_s=0.3, lead_out_s=0.3, drain_limit_s=20.0, trace_s=0.5)
    mix["engine"] = dict(max_batch=4, page_size=8, max_prompt_len=64,
                         max_new_cap=8, num_pages=64)
    mix["check"] = {"sample_tokens": 20, "logit_gap_limit": 0.01}
    _dump(mix, dst, "perfbench", "traffic", "longprompt_poisson.json")

    mix = _load("perfbench", "traffic", "longgen_backlog.json")
    mix["arrivals"]["requests"] = 600
    mix["prompt_len"] = {"dist": "uniform", "range": [8, 32],
                         "snap": [8, 16, 24, 32]}
    mix["output_len"] = {"dist": "uniform", "range": [8, 16]}
    mix.update(lead_in_s=0.3, trace_s=0.5)
    mix["engine"] = dict(max_batch=4, page_size=8, max_prompt_len=32,
                         max_new_cap=16, num_pages=64)
    mix["check"] = {"sample_tokens": 20, "logit_gap_limit": 0.01}
    _dump(mix, dst, "perfbench", "traffic", "longgen_backlog.json")
    return dst
