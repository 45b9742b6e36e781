"""BENCHMARK.json against the contract it is written to, and against the
files it names: every cell's configuration, mix, metrics and readers exist."""

import importlib
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden_size|intermediate_size|latent|state_size"
                    r"|head_dim|_dim$|_rank$|expansion|experts_per_tok)")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["command"]) <= 32
    assert all(line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for path in bench["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        assert os.path.isdir(os.path.join(REPO, path))
    assert isinstance(bench["run_seconds"], int)
    # 2 + 14 x 24 runs of run_seconds + 60 s, 180 s a cell, 1200 s spare
    assert 1 <= bench["run_seconds"] <= 51
    assert 338 * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for config in bench["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(config["name"]) and config["name"] in used
        assert line(config["source"]) and line(config["why"])
        assert any(config["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(REPO, config["file"])) as f:
            body = json.load(f)
        assert len(config["reduced"]) <= 16
        for key in config["reduced"]:
            assert NAME.match(key) and key in body
            assert not WIDTHS.search(key), f"{key} is a width"
        for module in ("runners." + body["runner"],
                       "references." + body["reference"]):
            importlib.import_module("perfbench." + module)


def test_mistral_config_keeps_the_published_numbers(bench):
    """Mistral-7B-v0.3's config.json, every number, with the depth cut and
    named; no width differs."""
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "max_position_embeddings": 32768, "num_attention_heads": 32,
                 "num_hidden_layers": 32, "num_key_value_heads": 8,
                 "rms_norm_eps": 1e-05, "rope_theta": 1000000.0,
                 "vocab_size": 32768, "attention_dropout": 0.0,
                 "initializer_range": 0.02, "bos_token_id": 1,
                 "eos_token_id": 2}
    entry = next(c for c in bench["configs"]
                 if c["name"] == "mistral7b_v03_d16")
    with open(os.path.join(REPO, entry["file"])) as f:
        body = json.load(f)
    differing = [k for k, v in published.items() if body.get(k) != v]
    assert differing == entry["reduced"] == ["num_hidden_layers"]
    assert body["num_hidden_layers"] == 16
    assert body["published"]["num_hidden_layers"] == 32
    assert body["sliding_window"] is None and not body["tie_word_embeddings"]


def test_workloads(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    for cell in cells:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["config"] in configs and cell["chips"] in (1, 4)
        assert line(cell["why"])
        assert os.path.exists(os.path.join(
            REPO, "perfbench", "traffic", cell["traffic"] + ".json"))
    four = sum(c["chips"] == 4 for c in cells)
    assert four <= max(1, len(cells) // 4)


def test_end_to_end(bench):
    metrics = bench["end_to_end"]
    assert 1 <= len(metrics) <= 16
    names = [m["name"] for m in metrics]
    assert "setup_s" in names
    cells = {c["name"] for c in bench["workloads"]}
    for metric in metrics:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "bound", "source"}
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        assert set(metric.get("workloads", cells)) <= cells
    setup = metrics[names.index("setup_s")]
    assert "workloads" not in setup and setup["bound"] <= 0.1
    for cell in cells:                  # set-up and at least one other
        reported = [m["name"] for m in metrics
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2


def test_per_layer(bench):
    metrics = bench["per_layer"]
    assert 1 <= len(metrics) <= 128
    every = [m["name"] for m in metrics + bench["end_to_end"]]
    assert len(set(every)) == len(every)
    cells = {c["name"] for c in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    for metric in metrics:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "source", "layer", "moves"}
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES and line(metric["layer"])
        assert metric["moves"] in e2e and metric["moves"] != "setup_s"
        # every cell that reports it reports the metric it should move
        assert set(metric["workloads"]) <= e2e[metric["moves"]]
        with open(os.path.join(REPO, "perfbench", "metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module("perfbench.readers."
                                         + spec["reader"])
        assert callable(reader.read)
        if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
            assert metric["unit"] == "%"
    for cell in cells:
        assert any(cell in m["workloads"] for m in metrics)
    # a kernel's roofline stands beside the whole step's share of the peak
    for metric in metrics:
        if metric["name"].endswith("_roofline"):
            assert any("mfu" in other["name"]
                       and other["moves"] == metric["moves"]
                       and set(metric["workloads"]) <= set(other["workloads"])
                       for other in metrics), metric["name"]


def test_files_under_paths_have_plain_names(bench):
    for path in bench["paths"]:
        for folder, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                assert NAME.match(name), os.path.join(folder, name)
