"""The traffic generator: the same seed gives the same schedule, every seed
gets the same amount of work, lengths stay inside their clips, the offered
rate is the mix's, and latency counts from the due time."""

import json
import os

import numpy as np
import pytest

from perfbench import stats, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG_SEED = 2 ** 31 + 12345


def mix(name):
    return traffic.load_mix(REPO, name)


def test_same_seed_same_schedule_other_seed_same_cycle_cut_elsewhere():
    m = mix("longprompt_poisson")
    a = traffic.serve_schedule(m, BIG_SEED, 30, 32768)
    b = traffic.serve_schedule(m, BIG_SEED, 30, 32768)
    c = traffic.serve_schedule(m, BIG_SEED + 1, 30, 32768)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    # another seed: other token ids, and the same cycle of sizes and gaps
    # entered at another place, so the same amount of work in the window
    sizes = lambda plan: [(len(p.prompt), p.max_new_tokens)
                          for p in plan if p.in_window]
    assert sizes(a) != sizes(c)
    assert any(sizes(a)[k:] + sizes(a)[:k] == sizes(c)
               for k in range(len(sizes(a))))
    gaps = lambda plan: set(np.round(np.diff(
        [p.due_s for p in plan if p.in_window]), 6))
    assert len(gaps(a) ^ gaps(c)) <= 2     # all but the gap the cut falls in
    first = lambda plan: next(p for p in plan if p.in_window)
    assert not (first(a).prompt[:16] == first(c).prompt[:16]).all()
    # lead-in and lead-out replay the cycle's end and start
    tail = [len(p.prompt) for p in a if p.in_window][-3:]
    assert [len(p.prompt) for p in a if p.due_s < 0][-3:] == tail


def test_lengths_stay_inside_their_clips_and_on_their_buckets():
    m = mix("longprompt_poisson")
    plan = traffic.serve_schedule(m, 7, 45, 32768)
    lengths = {len(p.prompt) for p in plan}
    assert lengths <= set(m["prompt_len"]["snap"])
    assert min(lengths) >= 512 and max(lengths) <= 4096
    assert all(16 <= p.max_new_tokens <= 64 for p in plan)
    assert all(0 <= p.prompt.min() and p.prompt.max() < 32768 for p in plan)
    page = m["engine"]["page_size"]
    assert traffic.prompt_buckets(plan, page) == sorted(lengths)
    window = [len(p.prompt) for p in plan if p.in_window]
    assert 1100 <= np.median(window) <= 1300       # the mix's median, 1,200


def test_poisson_rate_and_window_membership():
    m = mix("longprompt_poisson")
    rate = m["arrivals"]["rate_per_s"]
    plan = traffic.serve_schedule(m, 3, 40, 32768)
    window = [p for p in plan if p.in_window]
    assert len(window) == round(rate * 40)
    assert all(0 <= p.due_s < 40 for p in window)
    lead_in = [p for p in plan if p.due_s < 0]
    assert lead_in and not any(p.in_window for p in lead_in)
    assert min(p.due_s for p in lead_in) >= -m["lead_in_s"]
    assert abs(len(lead_in) - rate * m["lead_in_s"]) < 0.5 * rate * \
        m["lead_in_s"]
    lead_out = [p for p in plan if p.due_s >= 40]
    assert lead_out and max(p.due_s for p in lead_out) < 40 + m["lead_out_s"]
    assert [p.due_s for p in plan] == sorted(p.due_s for p in plan)
    gaps = np.diff([p.due_s for p in window])
    # exponential gaps: the coefficient of variation is about 1
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_backlog_queues_everything_before_the_window():
    m = mix("longgen_backlog")
    plan = traffic.serve_schedule(m, BIG_SEED, 45, 32768)
    assert len(plan) == m["arrivals"]["requests"]
    assert {p.due_s for p in plan} == {-m["lead_in_s"]}
    assert all(64 <= len(p.prompt) <= 512 for p in plan)
    assert all(256 <= p.max_new_tokens <= 768 for p in plan)


def test_gamma_arrivals_and_classes_and_shared_prefix_need_no_new_code():
    m = {"arrivals": {"process": "gamma", "cv": 2.5, "rate_per_s": 10.0},
         "classes": [
             {"weight": 0.9, "prompt_len": {"dist": "uniform",
                                            "range": [64, 512]},
              "output_len": {"dist": "uniform", "range": [64, 256]}},
             {"weight": 0.1, "prompt_len": {"dist": "constant",
                                            "value": 8192},
              "output_len": {"dist": "constant", "value": 32}}],
         "shared_prefix": {"len": 48, "pool": 1, "share": 1.0}}
    plan = traffic.serve_schedule(m, 5, 20, 1000)
    assert len(plan) == 200
    assert sum(len(p.prompt) == 8192 for p in plan) == 20
    gaps = np.diff([p.due_s for p in plan])
    assert gaps.std() / gaps.mean() > 1.5           # burstier than Poisson
    assert all((p.prompt[:48] == plan[0].prompt[:48]).all() for p in plan)
    with pytest.raises(ValueError):
        traffic.arrival_gaps({"process": "nonsense"}, 3, 1.0)


def test_train_batches_differ_by_row_step_and_seed():
    x, y = traffic.train_batch(BIG_SEED, 0, 16, 1024, 50304)
    assert x.shape == y.shape == (16, 1024)
    assert (x[:, 1:] == y[:, :-1]).all()            # targets are inputs shifted
    assert len({row.tobytes() for row in x}) == 16
    x1, _ = traffic.train_batch(BIG_SEED, 1, 16, 1024, 50304)
    x2, _ = traffic.train_batch(BIG_SEED + 1, 0, 16, 1024, 50304)
    assert (x != x1).any() and (x != x2).any()
    again, _ = traffic.train_batch(BIG_SEED, 0, 16, 1024, 50304)
    assert (x == again).all()


def test_latency_counts_from_the_due_time_not_from_the_submit():
    # a request due at 1.0 s, submitted late at 1.4 s, first token at 1.5 s
    due, submit, first = 1.0, 1.4, 1.5
    lateness_ms = (submit - due) * 1e3
    ttft_ms = (first - due) * 1e3
    assert lateness_ms == pytest.approx(400.0)
    assert ttft_ms == pytest.approx(500.0)          # not 100 ms
    assert stats.percentile([ttft_ms] + [50.0] * 8, 90) == pytest.approx(500.0)


def test_every_mix_of_the_benchmark_loads():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        assert mix(cell["traffic"])["kind"] in ("train", "serve")
