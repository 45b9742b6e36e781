"""The decode step of a looped model against the chip's peaks, with
`opsbytes_looped`'s count (every layer weight once a pass), and its device
time per layer pass.

- `mfu`: model FLOPs of every token decoded in the window over the window's
  seconds and the peak.
- `hbm_share`: the bytes a step needs at the peak bandwidth, over the device
  time of `_decode_step` in the trace.
- `ms_per_layer_pass`: the median device time of `_decode_step` over the
  `layer_passes` that the step's `serving/decode` span records (the stack's
  layers times its passes): what one layer costs a step, so that models of
  different depth and loop count stand side by side. Nothing where the
  program's spans carry no such field.
"""

from perfbench import opsbytes_looped, stats, trace_reduce
from perfbench.readers import program_ring

PROGRAM = "_decode_step"


def read(run, what):
    steps = run.series.get("steps", [])
    if not steps or run.peak is None:
        return None
    cfg = run.config
    if what == "mfu":
        per_token = opsbytes_looped.decode_flops(cfg, 0)
        per_context = opsbytes_looped.decode_flops(cfg, 1) - per_token
        flops = sum(s["rows"] * per_token + s["context"] * per_context
                    for s in steps)
        return 100.0 * flops / (run.window_s * run.peak["flops_per_s"])
    if run.trace is None:
        return None
    programs = trace_reduce.module_events(run.trace, PROGRAM)
    if not programs:
        return None
    if what == "hbm_share":
        traced = [s for s in steps if run.in_trace(s["t"]) and s["rows"]]
        if not traced:
            return None
        nbytes = sum(opsbytes_looped.decode_bytes(cfg, [s["context"]])
                     for s in traced) / len(traced)
        seconds = sum(s for _, s in programs) / len(programs)
        return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / seconds
    if what == "ms_per_layer_pass":
        decodes = program_ring.named(
            program_ring.ended_between(run, "trace_t0", "trace_t1"),
            "serving/decode")
        counts = {s.args.get("layer_passes") for s in decodes}
        if len(counts) != 1 or not all(counts):
            return None
        return stats.percentile([s * 1e3 for _, s in programs], 50) \
            / counts.pop()
    raise ValueError(what)
