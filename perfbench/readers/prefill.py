"""Prefill programs (`_serving_prefill_s<padded length>`) in the trace: their
device milliseconds per thousand prompt tokens, or the model FLOPs of their
prompts over their device time and the chip's peak. The tokens are those of
the padded length in the program's name; the benchmark's mixes snap prompt
lengths to those lengths, so padded and true tokens are the same there."""

import re

from perfbench import opsbytes, trace_reduce

PROGRAM = "_serving_prefill_s"


def _events(run):
    if run.trace is None:
        return []
    out = []
    for name, seconds in trace_reduce.module_events(run.trace, PROGRAM):
        out.append((int(re.search(PROGRAM + r"(\d+)", name).group(1)),
                    seconds))
    return out


def read(run, what):
    events = _events(run)
    if not events:
        return None
    seconds = sum(s for _, s in events)
    if what == "ms_per_ktok":
        return seconds * 1e3 / (sum(n for n, _ in events) / 1e3)
    if what == "mfu":
        flops = sum(opsbytes.llama_prefill_flops(run.config, n)
                    for n, _ in events)
        return 100.0 * flops / (seconds * run.peak["flops_per_s"])
    raise ValueError(what)
