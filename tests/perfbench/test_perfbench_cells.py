"""Every cell end to end at a size the CPU holds: the harness's look for a
chip is skipped, everything else is the run the driver makes. Also: a run
without a TPU fails, a checkout without the program fails, and a
configuration, a mix, a metric and a reader added as new files are found."""

import json
import os
import subprocess
import sys

import pytest

import perfbench_tiny as tiny
from perfbench import harness, run as run_module

SEED = 2 ** 31 + 4242


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.build(tmp_path_factory.mktemp("checkout"))


def drive(root, cell, trace, capsys, seconds=1.5):
    line = run_module.main(
        ["--workload", cell, "--seed", str(SEED), "--seconds", str(seconds),
         "--trace", str(trace)], require_tpu=False, root=root)
    printed = capsys.readouterr()
    return line, printed


@pytest.mark.parametrize("cell,metrics", [
    (tiny.C1, {"train_tokens_per_s_per_chip", "setup_s"}),
    (tiny.C2, {"tpot_p90_ms", "setup_s"}),
    (tiny.C3, {"serve_tokens_per_s", "setup_s"}),
    (tiny.C4, {"train_tokens_per_s_per_chip", "setup_s"}),
])
def test_cell_runs_and_prints_the_result_line(root, cell, metrics, capsys):
    line, printed = drive(root, cell, 0, capsys)
    last = json.loads(printed.out.strip().splitlines()[-1])
    assert last == json.loads(json.dumps(line))
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(last)[-2:] == ["setup_split_s", "compared"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == metrics
    for value in last["metrics"].values():
        assert value["value"] > 0 and isinstance(value["unit"], str)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(last["device"])
    # each number compared, beside its limit, ends standard error
    tail = printed.err.strip().splitlines()
    assert tail[-1] == "correct: True"
    for name, pair in last["compared"].items():
        assert any(row.startswith(f"compared {name}: ") for row in tail)
        assert pair["value"] <= pair["limit"]


@pytest.mark.parametrize("cell,expect", [
    (tiny.C1, {"step_ms_p50.train", "compiles_in_window.train"}),
    (tiny.C2, {"generator_lateness_ms_p90", "requests_in_window.open_loop",
               "ttft_p90_ms", "ttft_mean_ms",
               "batch_occupancy_mean.open_loop",
               "kv_page_utilization.open_loop",
               "decode_step_ms_p50.open_loop",
               "compiles_in_window.open_loop"}),
    (tiny.C3, {"batch_occupancy_mean.backlog", "decode_step_ms_p50.backlog",
               "compiles_in_window.backlog"}),
])
def test_traced_run_reports_what_the_host_can_read(root, cell, expect, capsys):
    """On the CPU there is no device plane: the readers of the device trace
    return nothing and are left out; the host's counters and spans are read.
    `correct` means the same as in the untraced run."""
    line, _ = drive(root, cell, 1, capsys)
    assert line["correct"] is True
    assert expect <= set(line["metrics"])
    assert "apex_flash_fwd_roofline" not in line["metrics"]
    assert line["metrics"][sorted(expect)[-1]]["value"] >= 0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    if cell == tiny.C2:
        assert line["metrics"]["requests_in_window.open_loop"]["value"] == 30
        assert line["metrics"]["compiles_in_window.open_loop"]["value"] == 0


def test_without_a_tpu_the_run_fails_and_prints_no_result(root, capsys):
    with pytest.raises(SystemExit) as refusal:
        run_module.main(["--workload", tiny.C1, "--seed", "1", "--seconds",
                         "1", "--trace", "0"], require_tpu=True, root=root)
    assert "no TPU" in str(refusal.value)
    assert capsys.readouterr().out == ""


def test_a_cell_that_wants_more_chips_than_there_are_fails(root, capsys):
    bench = harness.load_json(root, "BENCHMARK.json")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        cell = next(w for w in bench["workloads"] if w["name"] == tiny.C4)
        cell["chips"] = 64
        json.dump(bench, f)
    try:
        with pytest.raises(SystemExit) as refusal:
            run_module.main(["--workload", tiny.C4, "--seed", "1",
                             "--seconds", "1", "--trace", "0"],
                            require_tpu=False, root=root)
        assert "64 chip(s)" in str(refusal.value)
        assert capsys.readouterr().out == ""
    finally:
        cell["chips"] = 4
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)


def test_alone_with_its_paths_the_command_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths` has no program to measure: no result, another exit code than 0."""
    import shutil

    bench = harness.load_json(tiny.REPO, "BENCHMARK.json")
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(tiny.REPO, path), tmp_path / path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable] + bench["command"][1:]
        + ["--workload", bench["workloads"][0]["name"], "--seed", "1",
           "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_unknown_workload_is_an_error(root):
    with pytest.raises(SystemExit):
        run_module.main(["--workload", "no.such_cell", "--seed", "1",
                         "--seconds", "1", "--trace", "0"],
                        require_tpu=False, root=root)


def test_new_files_alone_add_a_configuration_a_mix_a_metric_and_a_reader(
        root, tmp_path, capsys):
    import perfbench.readers

    here = lambda *parts: os.path.join(root, "perfbench", *parts)
    config = harness.load_json(here("configs", "gpt2_345m.json"))
    config["num_layers"] = 1
    mix = harness.load_json(here("traffic", "pretrain_s1024.json"))
    mix["seq_len"] = 32
    for obj, path in ((config, here("configs", "gpt2_one_layer.json")),
                      (mix, here("traffic", "pretrain_s32.json")),
                      ({"reader": "steps_taken", "args": {"scale": 2.0}},
                       here("metrics", "steps_doubled.json"))):
        with open(path, "w") as f:
            json.dump(obj, f)
    (tmp_path / "steps_taken.py").write_text(
        "def read(run, scale):\n    return run.counters['steps'] * scale\n")
    perfbench.readers.__path__.append(str(tmp_path))
    bench = harness.load_json(root, "BENCHMARK.json")
    before = json.dumps(bench)
    bench["configs"].append({"name": "gpt2_one_layer", "source": "test",
                             "file": "perfbench/configs/gpt2_one_layer.json",
                             "reduced": ["num_layers"], "why": "test"})
    bench["workloads"].append({"name": "gpt2_one_layer.pretrain_s32",
                               "config": "gpt2_one_layer",
                               "traffic": "pretrain_s32", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "steps_doubled", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "train_tokens_per_s_per_chip",
        "workloads": ["gpt2_one_layer.pretrain_s32"]})
    for metric in bench["end_to_end"]:
        if metric["name"] == "train_tokens_per_s_per_chip":
            metric["workloads"].append("gpt2_one_layer.pretrain_s32")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    try:
        line, _ = drive(root, "gpt2_one_layer.pretrain_s32", 1, capsys, 1.0)
        assert line["correct"] is True
        assert line["metrics"]["steps_doubled"]["value"] == \
            2.0 * line["attempted"]
        assert "step_ms_p50.train" not in line["metrics"]   # not its cell
        plain, _ = drive(root, "gpt2_one_layer.pretrain_s32", 0, capsys, 1.0)
        assert set(plain["metrics"]) == {"train_tokens_per_s_per_chip",
                                         "setup_s"}
    finally:
        perfbench.readers.__path__.remove(str(tmp_path))
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            f.write(before)
