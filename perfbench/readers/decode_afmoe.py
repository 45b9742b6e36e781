"""The decode step of a model with dropless experts and sliding-window layers
against the chip's peaks and against its own counts, with `opsbytes_afmoe`'s
work. All of it from the fields of the program's `serving/decode` spans
(`rows`, `positions`, `positions_window`, `pages_live`, `pages_live_window`,
`expert_tokens`, `experts_hit`, `expert_layers`, `experts_held`); nothing
where a span lacks the field, as on a program that has no such model.

- `mfu`: model FLOPs of every decode step of the window (the unrouted weights
  a row, the held experts' a routed assignment, attention over the window on a
  sliding layer and over the context on a full one) over the window's seconds
  and the peak.
- `hbm_share`: the least bytes of the traced steps (unrouted weights once, the
  experts that were touched, K and V of the positions attended) at the peak
  bandwidth, over the device time of `_decode_step` in the trace.
- `experts_hit_share`: held experts that got a token, over held experts, a
  layer and step.
- `expert_tokens_per_layer_step`: assignments on held experts a layer, step.
- `window_pages_skipped_share`: of the pages a full layer reads, the share a
  sliding layer does not.
"""

from perfbench import opsbytes_afmoe, trace_reduce
from perfbench.readers import program_ring

PROGRAM = "_decode_step"


def _decodes(run, lo, hi, *fields):
    """The `serving/decode` spans that ended between two stamps, if each
    carries every field; None otherwise."""
    spans = program_ring.named(program_ring.ended_between(run, lo, hi),
                               "serving/decode")
    if not spans or any(f not in s.args for s in spans for f in fields):
        return None
    return spans


def _attended(cfg, s):
    """Positions the step of span `s` attends to, over all layers."""
    n_sliding = opsbytes_afmoe.sliding_layers(cfg)
    return n_sliding * s.args["positions_window"] + (
        cfg["num_hidden_layers"] - n_sliding) * s.args["positions"]


def read(run, what):
    cfg = run.config
    if what == "mfu":
        spans = _decodes(run, "t_open", "t_close", "rows", "positions",
                         "positions_window", "expert_tokens")
        if spans is None or run.peak is None or not run.window_s:
            return None
        flops = sum(opsbytes_afmoe.decode_flops(
            cfg, s.args["rows"], _attended(cfg, s), s.args["expert_tokens"])
            for s in spans)
        return 100.0 * flops / (run.window_s * run.peak["flops_per_s"])
    if what == "hbm_share":
        spans = _decodes(run, "trace_t0", "trace_t1", "positions",
                         "positions_window", "experts_hit")
        if spans is None or run.peak is None or run.trace is None:
            return None
        programs = trace_reduce.module_events(run.trace, PROGRAM)
        if not programs:
            return None
        nbytes = sum(opsbytes_afmoe.decode_bytes(
            cfg, _attended(cfg, s), s.args["experts_hit"])
            for s in spans) / len(spans)
        seconds = sum(s for _, s in programs) / len(programs)
        return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / seconds
    if what == "experts_hit_share":
        spans = _decodes(run, "t_open", "t_close", "experts_hit",
                         "expert_layers", "experts_held")
        return None if spans is None else 100.0 * sum(
            s.args["experts_hit"] for s in spans) / sum(
            s.args["expert_layers"] * s.args["experts_held"] for s in spans)
    if what == "expert_tokens_per_layer_step":
        spans = _decodes(run, "t_open", "t_close", "expert_tokens",
                         "expert_layers")
        return None if spans is None else sum(
            s.args["expert_tokens"] for s in spans) / sum(
            s.args["expert_layers"] for s in spans)
    if what == "window_pages_skipped_share":
        spans = _decodes(run, "t_open", "t_close", "pages_live",
                         "pages_live_window")
        return None if spans is None else 100.0 * (1.0 - sum(
            s.args["pages_live_window"] for s in spans) / sum(
            s.args["pages_live"] for s in spans))
    raise ValueError(what)
