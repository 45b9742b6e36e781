"""The decode step against the chip's peaks. `mfu`: model FLOPs of every
token decoded in the window (weights at 2 FLOPs each, attention over the
row's live context) over the window's seconds and the peak. `hbm_share`: the
bytes a step needs (every weight once, each active row's K and V once) at the
peak bandwidth, over the device time of `_decode_step` in the trace."""

from perfbench import opsbytes, trace_reduce

PROGRAM = "_decode_step"


def read(run, what):
    steps = run.series.get("steps", [])
    if not steps or run.peak is None:
        return None
    cfg = run.config
    if what == "mfu":
        per_token = opsbytes.llama_decode_flops(cfg, 0)
        per_context = opsbytes.llama_decode_flops(cfg, 1) - per_token
        flops = sum(s["rows"] * per_token + s["context"] * per_context
                    for s in steps)
        return 100.0 * flops / (run.window_s * run.peak["flops_per_s"])
    if what == "hbm_share":
        if run.trace is None:
            return None
        traced = [s for s in steps if run.in_trace(s["t"]) and s["rows"]]
        programs = trace_reduce.module_events(run.trace, PROGRAM)
        if not traced or not programs:
            return None
        nbytes = sum(opsbytes.llama_decode_bytes(cfg, [s["context"]])
                     for s in traced) / len(traced)
        seconds = sum(s for _, s in programs) / len(programs)
        return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / seconds
    raise ValueError(what)
