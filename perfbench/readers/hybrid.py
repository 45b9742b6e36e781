"""A model with conv layers beside attention layers and dropless experts all
held, against the chip's peaks and its own counts, with `opsbytes_lfm2`'s
work. From the program's `serving/*` spans (`serving/admit`: `prompt_tokens`,
`bucket`; `serving/decode`: `rows`, `positions`, `expert_tokens`,
`experts_hit`, `conv_layers`; `serving/write_state`) and the programs of the
device trace; nothing where a span lacks a field, as on a program that has no
such model, or where the trace has no such program.

- `prefill_mfu`, `prefill_ms_per_ktok`: the `_serving_prefill_s<S>` programs
  of the trace, each with the true prompt tokens of the admissions of its
  bucket (a prompt ends before its bucket does; the padding counts nothing):
  the model's FLOPs over the programs' device seconds and the peak, and their
  device milliseconds a thousand true tokens.
- `prefill_device_share`: the prefill programs' device seconds over theirs
  plus the `_decode_step` programs': which regime the cell is in.
- `decode_mfu`: model FLOPs of every decode step of the window over the
  window's seconds and the peak.
- `decode_hbm_share`: the least bytes of the traced steps (unrouted weights
  once, the experts touched, K and V of the positions attended on the
  attention layers, the conv state read and written) at the peak bandwidth,
  over the device time of `_decode_step` in the trace.
- `state_write_ms`: wall milliseconds of a `serving/write_state` span, the
  mean over the window's admissions.
"""

import collections
import re

from perfbench import opsbytes_lfm2, trace_reduce
from perfbench.readers import program_ring

PREFILL, DECODE = "_serving_prefill_s", "_decode_step"


def _spans(run, lo, hi, name, *fields):
    """The spans of `name` that ended between two stamps, if there are any
    and each carries every field; None otherwise."""
    spans = program_ring.named(program_ring.ended_between(run, lo, hi), name)
    if not spans or any(f not in s.args for s in spans for f in fields):
        return None
    return spans


def _prefills(run):
    """(true tokens, device seconds) of each traced prefill program whose
    bucket some admission of the traced window names."""
    if run.trace is None:
        return []
    admits = _spans(run, "trace_t0", "trace_t1", "serving/admit",
                    "prompt_tokens", "bucket")
    true = collections.defaultdict(list)
    for s in admits or ():
        true[s.args["bucket"]].append(s.args["prompt_tokens"])
    out = []
    for name, seconds in trace_reduce.module_events(run.trace, PREFILL):
        bucket = int(re.search(PREFILL + r"(\d+)", name).group(1))
        if true[bucket]:
            out.append((sum(true[bucket]) / len(true[bucket]), seconds))
    return out


def read(run, what):
    cfg = run.config
    if what in ("prefill_mfu", "prefill_ms_per_ktok"):
        events = _prefills(run)
        if not events or run.peak is None:
            return None
        seconds = sum(s for _, s in events)
        if what == "prefill_ms_per_ktok":
            return seconds * 1e3 / (sum(n for n, _ in events) / 1e3)
        flops = sum(opsbytes_lfm2.prefill_flops(cfg, n) for n, _ in events)
        return 100.0 * flops / (seconds * run.peak["flops_per_s"])
    if what == "prefill_device_share":
        if run.trace is None:
            return None
        prefill = sum(s for _, s in trace_reduce.module_events(
            run.trace, PREFILL))
        decode = sum(s for _, s in trace_reduce.module_events(
            run.trace, DECODE))
        return 100.0 * prefill / (prefill + decode) if prefill + decode \
            else None
    if what == "decode_mfu":
        spans = _spans(run, "t_open", "t_close", "serving/decode", "rows",
                       "positions", "expert_tokens", "conv_layers")
        if spans is None or run.peak is None or not run.window_s:
            return None
        flops = sum(opsbytes_lfm2.decode_flops(
            cfg, s.args["rows"], s.args["positions"],
            s.args["expert_tokens"]) for s in spans)
        return 100.0 * flops / (run.window_s * run.peak["flops_per_s"])
    if what == "decode_hbm_share":
        spans = _spans(run, "trace_t0", "trace_t1", "serving/decode", "rows",
                       "positions", "experts_hit", "conv_layers")
        if spans is None or run.peak is None or run.trace is None:
            return None
        programs = trace_reduce.module_events(run.trace, DECODE)
        if not programs:
            return None
        nbytes = sum(opsbytes_lfm2.decode_bytes(
            cfg, s.args["rows"], s.args["positions"], s.args["experts_hit"])
            for s in spans) / len(spans)
        seconds = sum(s for _, s in programs) / len(programs)
        return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / seconds
    if what == "state_write_ms":
        spans = _spans(run, "t_open", "t_close", "serving/write_state",
                       "conv_layers")
        return None if spans is None else sum(
            s.duration_ns for s in spans) / 1e6 / len(spans)
    raise ValueError(what)
