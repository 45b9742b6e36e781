"""What decides `correct`, at a size a test run can hold: the sound program
passes; the control (the reference one precision below the configuration's:
fp8 for bfloat16) is not correct; and a run whose timed path is broken
underneath comes out with `correct` false, once for each fault a cell can
have. The harness's look for a chip is skipped; the rest is a run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perfbench_tiny as tiny
from perfbench import harness, run as run_module
from perfbench.runners import serve, train

SEEDS = [11, 12, 2 ** 31 + 13]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.build(tmp_path_factory.mktemp("checkout"))


def drive(root, cell, seed=SEEDS[0]):
    return run_module.main(["--workload", cell, "--seed", str(seed),
                            "--seconds", "1", "--trace", "0"],
                           require_tpu=False, root=root)


def over(compared):
    return sorted(k for k, v in compared.items() if v["value"] > v["limit"])


# ------------------------------------------------------------------ training

@pytest.fixture(scope="module")
def trainer(root):
    run = harness.Run(tiny.C1, SEEDS[0], 1, 0, root=root, require_tpu=False)
    return run, train.Trainer(run, run.open_device())


@pytest.mark.parametrize("seed", SEEDS)
def test_training_control_at_fp8_is_not_correct(trainer, seed):
    run, tr = trainer
    limits = run.mix["check"]["limits"]
    want = train.reference_readings(run, tr, seed)
    tr.start(seed)
    sound = train.compare(tr.first_steps(seed), want, limits)
    tr.free()
    assert all(v <= limit for v, limit in sound.values()), sound
    control = train.compare(
        train.reference_readings(run, tr, seed, precision="fp8"), want,
        limits)
    failed = [k for k, (v, limit) in control.items() if v > limit]
    assert failed, control


def test_compare_leaves_split_packed_qkv_and_skip_what_has_no_gradient():
    want = {"losses": [1.0], "grad_norms": {"a": 1.0, "b": 2.0, "k": 1e-9},
            "delta_norms": {"a": 1.0, "b": 2.0, "k": 0.0}}
    got = {"losses": [1.0], "grad_norms": {"a": 1.0, "b": 2.0, "k": 1e-9},
           "delta_norms": {"a": 1.0, "b": 2.0, "k": 5.0}}
    limits = dict.fromkeys(tiny.TRAIN_LIMITS, 0)
    worst = lambda: train.compare(got, want, limits)[
        "delta_norm_gap_worst_leaf"][0]
    # `k` moved by round-off alone in the program: left out by the rule on
    # the reference's gradient (under a thousandth of the median leaf's)
    assert worst() == 0.0
    got["delta_norms"]["a"] = 2.0           # moved double
    assert worst() == 1.0
    got["delta_norms"]["a"] = 0.0           # has not moved
    assert worst() == 1.0
    assert train.compare(got, want, limits)[
        "delta_norm_gap_mean_leaf"][0] == 0.5
    # a tiny leaf is measured against the median leaf, not against itself
    assert train.leaf_gaps({"a": 1.0, "b": 2.0, "c": 0.002},
                           {"a": 1.0, "b": 2.0, "c": 0.001})["c"] == 0.001
    got["delta_norms"]["a"] = float("nan")
    assert worst() != worst()               # a NaN stays a NaN, never a pass
    from perfbench.references import gpt2 as ref
    tree = {"layers": {"wqkv": jnp.zeros((2, 8, 3, 8)),
                       "bqkv": jnp.arange(48.0).reshape(2, 3, 8)}}
    split = ref.compare_leaves(tree)
    assert set(split) == {"layers.wqkv.q", "layers.wqkv.k", "layers.wqkv.v",
                          "layers.bqkv.q", "layers.bqkv.k", "layers.bqkv.v"}
    assert split["layers.bqkv.k"].shape == (2, 8)
    assert float(split["layers.bqkv.k"][0, 0]) == 8.0


def test_fault_step_returns_its_state_unchanged(root, monkeypatch):
    def call(self, batch):
        copy = jax.tree_util.tree_map(jnp.copy, self.state)
        *_, loss = self.step(*copy, batch)
        return loss

    monkeypatch.setattr(train.Trainer, "call", call)
    line = drive(root, tiny.C1)
    assert line["correct"] is False
    for number in tiny.TRAIN_LIMITS:
        reads = line["compared"][number]["value"]
        assert reads == pytest.approx(1.0) if "worst" in number else reads > 0.9


def test_fault_half_of_the_batch_left_out(root, monkeypatch):
    put = train.Trainer.put

    def half(self, batch):
        keep = lambda a: np.concatenate([a[:len(a) // 2]] * 2)
        return put(self, tuple(keep(a) for a in batch))

    monkeypatch.setattr(train.Trainer, "put", half)
    line = drive(root, tiny.C1)
    assert line["correct"] is False
    assert "grad_norm_gap_worst_leaf" in over(line["compared"])


def test_fault_exchange_between_chips_left_out(root, monkeypatch):
    """Without the gradient exchange every chip's update comes from its own
    rows: what the step then computes is one chip's rows alone."""
    put = train.Trainer.put

    def own_rows(self, batch):
        keep = lambda a: np.concatenate([a[:len(a) // self.dp]] * self.dp)
        return put(self, tuple(keep(a) for a in batch))

    monkeypatch.setattr(train.Trainer, "put", own_rows)
    line = drive(root, tiny.C4)
    assert line["correct"] is False
    assert "grad_norm_gap_worst_leaf" in over(line["compared"])


def test_sound_ddp_run_is_correct_and_counts_every_chip(root):
    line = drive(root, tiny.C4, SEEDS[2])
    assert line["correct"] is True
    assert line["device"]["count"] >= 4


# ------------------------------------------------------------------- serving

@pytest.mark.parametrize("seed", SEEDS)
def test_serving_control_at_fp8_is_not_correct(root, seed):
    run = harness.Run(tiny.C2, seed, 1, 0, root=root, require_tpu=False)
    run.open_device()
    server = serve.Server(run)
    attempted, failed, t_close = serve.drive(run, server)
    picked = serve.sample_served(run, server, t_close)
    assert attempted == 20 and failed == 0 and picked
    longest = max(len(p.prompt) + len(r.tokens) for p, r in server.requests
                  if p.in_window)
    assert len(picked[0][0]) + len(picked[0][1]) == longest
    assert sum(len(s) for _, s in picked) >= run.mix["check"]["sample_tokens"]
    server.free()
    gap, control = serve.check_served(run, server, picked, precision="fp8")
    limit = run.mix["check"]["logit_gap_limit"]
    assert gap <= limit < control


def test_fault_a_token_altered_where_it_is_produced(root, monkeypatch):
    from apex_tpu.serving.scheduler import ContinuousBatchScheduler

    step_decode = ContinuousBatchScheduler.step_decode

    def altered(self):
        live = [r for r in self.slots if r is not None]
        finished = step_decode(self)
        for req in live:
            if len(req.tokens) == 3:        # the reply's third token
                req.tokens[-1] = (req.tokens[-1] + 1) % self.cfg.vocab_size
        return finished

    monkeypatch.setattr(ContinuousBatchScheduler, "step_decode", altered)
    line = drive(root, tiny.C2)
    assert line["correct"] is False
    assert over(line["compared"]) == ["served_logit_gap"]


def test_a_request_that_never_comes_back_is_not_correct(root, monkeypatch):
    mix = harness.load_json(root, "perfbench", "traffic",
                            "longprompt_poisson.json")
    run = harness.Run(tiny.C2, 1, 1, 0, root=root, require_tpu=False)
    run.device = {}
    line = run.result(20, 1, {"served_logit_gap": (0.0, mix["check"][
        "logit_gap_limit"])})
    assert line["correct"] is False
    assert run.result(20, 0, {"served_logit_gap": (0.0, 1.0)})["correct"]
    assert not run.result(20, 0, {"served_logit_gap": (float("nan"),
                                                       1.0)})["correct"]
    assert not run.result(20, 0, {})["correct"]
