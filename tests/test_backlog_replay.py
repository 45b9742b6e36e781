"""`tools/backlog_replay.py`: the replay of a backlog's schedule on the host
that chose the expert cell's `order_seed` (PERF.md, PR 33): against a plain
loop over one entry, and what it says of the mix as committed."""

import importlib.util
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "backlog_replay", os.path.join(ROOT, "tools", "backlog_replay.py"))
tool = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tool)

MIX = {"lead_in_s": 0.5, "engine": {"max_batch": 3, "page_size": 8}}
PRICES = dict(step_ms=10.0, page_ms=0.5, prompt_ms_per_ktok=2048.0,
              admit_ms=1.0)
LAYERS = dict(sliding=2, full=1, window=24)


def plain(prompts, outputs, start, seconds):
    """One entry, a request and a row at a time."""
    rows, page, lead = 3, 8, 0.5
    slots, queue = [None] * rows, [(start + i) % len(prompts)
                                   for i in range(len(prompts))]
    t, tokens, opened, closed = 0.0, 0, None, None
    while closed is None:
        if opened is None and t >= lead:
            opened = (t, tokens)
        if t >= lead + seconds:
            closed = (t, tokens)
            break
        while queue and None in slots:
            req = queue.pop(0)
            t += (2048.0 * prompts[req] / 1024 + 1.0) / 1e3
            slots[slots.index(None)] = [prompts[req], outputs[req] - 1]
            tokens += 1
        pages = 0
        for row in filter(None, slots):
            first = max(row[0] - 24 + 1, 0)
            pages += row[0] // page + 1 + 2 * (row[0] // page - first // page
                                               + 1)
        t += (10.0 + 0.5 * pages) / 1e3
        for i, row in enumerate(slots):
            if row is not None:
                tokens += 1
                row[0] += 1
                row[1] -= 1
                if row[1] <= 0:
                    slots[i] = None
    return (closed[1] - opened[1]) / (closed[0] - opened[0])


@pytest.mark.parametrize("start", [0, 5, 17])
def test_the_replay_is_the_plain_loop(start):
    rng = np.random.default_rng(3)
    prompts, outputs = rng.integers(4, 40, 24), rng.integers(2, 30, 24)
    got = tool.replay(prompts, outputs, [start], MIX, 3.0, layers=LAYERS,
                      **PRICES)
    assert got[0] == pytest.approx(plain(prompts, outputs, start, 3.0),
                                   rel=1e-12)


def test_the_expert_cells_order_is_the_steadier_one():
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "longctx_backlog.json")) as f:
        mix = json.load(f)
    own = tool.of_order(mix, mix["order_seed"], 45.0)
    zero = tool.of_order(mix, 0, 45.0)
    # quartile distance and deviation, as shares: the mix's note and PERF.md
    assert own[0] < 0.007 and own[1] < 0.0045
    assert zero[0] > 0.011 and zero[1] > 0.008


def test_the_hybrid_cells_order_is_the_steadier_one():
    """`lfm2_24b_a2b_d9.docextract_backlog` (PR 35): the mix's own order of
    its 4,096 requests against the order of seed 0, at that cell's prices and
    with pages on its two attention layers alone."""
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "docextract_backlog.json")) as f:
        mix = json.load(f)
    name = "docextract_backlog"
    assert tool.CELLS[name][1] == dict(sliding=0, full=2, window=1 << 30)
    own = tool.of_order(mix, mix["order_seed"], 45.0, name)
    zero = tool.of_order(mix, 0, 45.0, name)
    assert own[0] < 0.0065 and own[1] < 0.005
    assert zero[0] > 0.009 and zero[1] > 0.006
