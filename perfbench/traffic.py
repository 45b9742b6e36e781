"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix is `perfbench/traffic/<name>.json`. Serving mixes give an arrival
process, length distributions (one class, or several with weights), and an
optional shared prefix; training mixes give a batch and a sequence length. A
later PR adds a mix by adding a file, never by touching this one.

Every seed gets the same work. The set of lengths and the set of gaps between
arrivals are the quantiles of their distributions, fixed by the mix and the
window alone, and laid out once, in an order drawn from the mix's own
`order_seed`, as one cycle as long as the window. `--seed` chooses where in the
cycle the window starts and draws the token ids; the lead-in replays the end
of the cycle and the lead-out its start. So two seeds differ in content and in
where the cycle is cut, never in amount or in which request follows which: on
the chip a free shuffle of the same set moved the 90th percentile of the time
to first token from 269 to 510 ms (PERF.md), and a tail that the dice move
cannot hold a bound. A run's numbers now spread by what the system does.
"""

import json
import math
import os
import statistics
from dataclasses import dataclass

import numpy as np

_NORMAL = statistics.NormalDist()


def load_mix(root, name):
    with open(os.path.join(root, "perfbench", "traffic", name + ".json")) as f:
        return json.load(f)


def rng_for(seed, *tags):
    """A generator keyed by the run's seed (any whole number up to a little
    over 2**31, and beyond) and a tag for what it draws."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *[int(t) for t in tags]])


def quantiles(dist, n):
    """`n` values that stand for `dist`: its inverse CDF at (i + 0.5) / n,
    clipped and snapped as the mix says. Deterministic: no seed enters."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = dist["range"]
        x = lo + u * (hi - lo)
    elif kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(v)) for v in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "constant":
        x = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    if "clip" in dist:
        x = np.clip(x, dist["clip"][0], dist["clip"][1])
    if "snap" in dist:
        grid = np.asarray(sorted(dist["snap"]), float)
        x = grid[np.abs(np.log(grid)[None, :]
                        - np.log(x)[:, None]).argmin(axis=1)]
    return np.rint(x).astype(np.int64)


def arrival_gaps(arrivals, n, span_s):
    """`n` gaps that sum to `span_s`. Poisson: the exponential's quantiles.
    Gamma with a coefficient of variation: a fixed sample from the mix's own
    `base_seed`, the same for every run. Both are scaled to the span, so the
    offered rate is exactly n / span."""
    process = arrivals["process"]
    u = (np.arange(n) + 0.5) / n
    if process == "poisson":
        gaps = -np.log1p(-u)
    elif process == "gamma":
        shape = 1.0 / arrivals["cv"] ** 2
        base = np.random.default_rng(int(arrivals.get("base_seed", 0)))
        gaps = np.sort(base.gamma(shape, 1.0 / shape, size=n))
    elif process == "uniform":
        gaps = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return gaps * (span_s / gaps.sum())


@dataclass
class Planned:
    """One request of a schedule. `due_s` is relative to the window's start:
    negative in the lead-in, at or past the window's length in the lead-out."""
    index: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int
    in_window: bool


def _class_counts(classes, n):
    weights = np.array([c.get("weight", 1.0) for c in classes], float)
    counts = np.floor(weights / weights.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    return counts


def _lengths(mix, n, rng):
    """(prompt_len, output_len) pairs for `n` requests, shuffled."""
    classes = mix.get("classes") or [mix]
    prompts, outputs = [], []
    for cls, count in zip(classes, _class_counts(classes, n)):
        p = quantiles(cls["prompt_len"], count)
        o = quantiles(cls["output_len"], count)
        prompts.append(rng.permutation(p))      # decouple the two orders
        outputs.append(o)
    order = rng.permutation(n)
    return np.concatenate(prompts)[order], np.concatenate(outputs)[order]


def _tokens(mix, lengths, vocab, rng):
    prefix = mix.get("shared_prefix")
    pool = None
    if prefix:
        pool = rng.integers(0, vocab, (prefix.get("pool", 1), prefix["len"]),
                            dtype=np.int32)
    out = []
    for length in lengths:
        toks = rng.integers(0, vocab, int(length), dtype=np.int32)
        if pool is not None and rng.random() < prefix.get("share", 1.0):
            head = pool[rng.integers(len(pool))][:int(length)]
            toks[:len(head)] = head
        out.append(toks)
    return out


def serve_schedule(mix, seed, seconds, vocab):
    """Lead-in, window and lead-out of one run, in order of due time. The
    lead-in brings the engine to steady state before the window opens and the
    lead-out keeps the load on while the window's last requests finish; only
    the window's requests are measured. A backlog has no arrivals: its whole
    cycle is queued before the lead-in, starting where the seed says."""
    arrivals = mix["arrivals"]
    backlog = arrivals["process"] == "backlog"
    lead_in = float(mix.get("lead_in_s", 0.0))
    lead_out = float(mix.get("lead_out_s", 0.0))
    n = int(arrivals["requests"]) if backlog else \
        int(round(float(arrivals["rate_per_s"]) * seconds))
    cycle = np.random.default_rng(int(mix.get("order_seed", 0)))
    prompts, outputs = _lengths(mix, n, cycle)
    rng = rng_for(seed, 1)
    start = int(rng.integers(n))
    if backlog:
        order = np.roll(np.arange(n), -start)
        spots = [(i, -lead_in, True) for i in order]
    else:
        gaps = cycle.permutation(arrival_gaps(arrivals, n, float(seconds)))
        place = np.cumsum(gaps) - gaps          # on a circle of `seconds`
        due = (place - place[start]) % seconds
        spots = [(i, due[i] - seconds, False) for i in range(n)
                 if due[i] >= seconds - lead_in]
        spots += [(i, due[i], True) for i in range(n)]
        spots += [(i, due[i] + seconds, False) for i in range(n)
                  if due[i] < lead_out]
        spots.sort(key=lambda spot: spot[1])
    tokens = _tokens(mix, [prompts[i] for i, _, _ in spots], vocab, rng)
    return [Planned(index, float(at), tokens[index], int(outputs[i]), inside)
            for index, (i, at, inside) in enumerate(spots)]


def train_batch(seed, step, rows, seq_len, vocab):
    """The batch of optimizer step `step` (from 0): `rows` rows of
    `seq_len + 1` uniform token ids, every row different; inputs are all but
    the last column, targets all but the first."""
    toks = rng_for(seed, 7, step).integers(0, vocab, (rows, seq_len + 1),
                                           dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def prompt_buckets(plan, page_size):
    """The padded prompt lengths a schedule can make the engine compile."""
    return sorted({math.ceil(len(p.prompt) / page_size) * page_size
                   for p in plan})
