#!/usr/bin/env python3
"""python tools/backlog_replay.py [--orders N] [--mix NAME]

How far the place where `--seed` enters the cycle of lengths of an expert
cell's backlog (`trinity_large_ep8_d5.longctx_backlog`, or with `--mix
docextract_backlog` the hybrid cell's, `lfm2_24b_a2b_d9.docextract_backlog`)
moves `serve_tokens_per_s`, without a chip: the engine's schedule replayed on the
host for every entry at once. Rows are filled first come first served, every
admission costs its prompt, every decode step a constant and a price for each
page its rows attend to (a windowed model's sliding layers count the pages of
the window), and the window opens `lead_in_s` after the first admission, as
`perfbench/runners/serve.py` drives it. The prices are that cell's own, read
from the per-step records of chip runs (`serving/decode` and
`serving/admit` spans): they set the level, which is no measurement, and the
spread over entries, which is what this is for. Prints, for the mix's own
`order_seed` and for the steadiest of `--orders` other orders, the quartile
distance, the standard deviation and the range of the replayed rate over all
entries, as shares of its median or mean. PERF.md (PR 33) says what it found.
"""

import argparse
import os
import statistics
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the expert cell's prices (least squares over the 31,931 decode steps and 528
# admissions of twelve chip runs, PR 33): ms a step beside the pages (5.23,
# 48.4 experts touched at 0.091 each, 0.19 between steps), ms a page and
# layer, ms a thousand prompt tokens and ms an admission
PRICES = dict(step_ms=9.83, page_ms=0.00119, prompt_ms_per_ktok=19.46,
              admit_ms=9.34)
LAYERS = dict(sliding=4, full=1, window=4096)     # its `layer_types`
MIX = "longctx_backlog"
# the hybrid cell's (PR 35, PERF.md §6): two full attention layers hold
# pages, seven conv layers none; prices from the spans of its chip runs
CELLS = {MIX: (PRICES, LAYERS),
         "docextract_backlog": (
             dict(step_ms=15.6, page_ms=0.0006, prompt_ms_per_ktok=8.2,
                  admit_ms=39.0),
             dict(sliding=0, full=2, window=1 << 30))}


def replay(prompts, outputs, starts, mix, seconds, step_ms, page_ms,
           prompt_ms_per_ktok, admit_ms, layers=LAYERS):
    """The replayed tokens per second of the window, for each entry of
    `starts` into the cycle (`prompts[i]`, `outputs[i]`)."""
    n, count = len(prompts), len(starts)
    rows, page = mix["engine"]["max_batch"], mix["engine"]["page_size"]
    lead = float(mix.get("lead_in_s", 0.0))
    starts, each = np.asarray(starts), np.arange(count)
    taken = np.zeros(count, np.int64)
    pos = np.zeros((count, rows), np.int64)
    left = np.zeros((count, rows), np.int64)
    active = np.zeros((count, rows), bool)
    t, tokens = np.zeros(count), np.zeros(count, np.int64)
    t_open, t_close = np.full(count, np.nan), np.full(count, np.nan)
    at_open, at_close = np.zeros(count, np.int64), np.zeros(count, np.int64)
    while np.isnan(t_close).any():
        opens = np.isnan(t_open) & (t >= lead)
        t_open[opens], at_open[opens] = t[opens], tokens[opens]
        closes = np.isnan(t_close) & (t >= lead + seconds)
        t_close[closes], at_close[closes] = t[closes], tokens[closes]
        while True:
            free = np.isnan(t_close) & ~active.all(axis=1) & (taken < n)
            if not free.any():
                break
            who = each[free]
            slot = np.argmin(active[who], axis=1)
            req = (starts[who] + taken[who]) % n
            taken[who] += 1
            t[who] += (prompt_ms_per_ktok * prompts[req] / 1024
                       + admit_ms) / 1e3
            pos[who, slot], left[who, slot] = prompts[req], outputs[req] - 1
            active[who, slot] = True
            tokens[who] += 1
        first = np.maximum(pos - layers["window"] + 1, 0)
        pages = np.where(active, layers["full"] * (pos // page + 1)
                         + layers["sliding"] * (pos // page - first // page
                                                + 1), 0).sum(axis=1)
        t += (step_ms + page_ms * pages) / 1e3
        tokens += active.sum(axis=1)
        pos += active
        left -= active
        active &= left > 0
    return (at_close - at_open) / (t_close - t_open)


def spread(rates):
    """(quartile distance over median, deviation over mean, range over
    mean) of the replayed rates."""
    q1, _, q3 = statistics.quantiles(rates, n=4)
    return ((q3 - q1) / statistics.median(rates),
            float(np.std(rates) / np.mean(rates)),
            float((rates.max() - rates.min()) / np.mean(rates)))


def of_order(mix, order_seed, seconds, name=MIX):
    """`spread` over every entry of the mix's cycle laid out by
    `order_seed`, at the prices of the cell of mix `name`."""
    from perfbench import traffic

    n = int(mix["arrivals"]["requests"])
    prompts, outputs = traffic._lengths(
        mix, n, np.random.default_rng(int(order_seed)))
    prices, layers = CELLS[name]
    return spread(replay(prompts, outputs, np.arange(n), mix, seconds,
                         layers=layers, **prices))


def main(argv=None):
    from perfbench import harness, traffic

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--orders", type=int, default=1,
                        help="also replay the orders of seeds 0..N-1")
    parser.add_argument("--mix", default=MIX, choices=sorted(CELLS))
    args = parser.parse_args(argv)
    mix = traffic.load_mix(harness.ROOT, args.mix)
    seconds = harness.load_json(harness.ROOT, "BENCHMARK.json")["run_seconds"]
    own = int(mix["order_seed"])
    found = {seed: of_order(mix, seed, seconds, args.mix)
             for seed in {own, *range(args.orders)}}
    for seed, (iqr, dev, span) in sorted(found.items(),
                                         key=lambda kv: kv[1][1])[:10]:
        print(f"order_seed {seed}{' (the mix)' if seed == own else ''}: "
              f"quartile distance {100 * iqr:.2f}%, deviation "
              f"{100 * dev:.2f}%, range {100 * span:.2f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
