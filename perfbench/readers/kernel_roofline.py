"""A named kernel's share of its roofline: the least time the chip could take
for each call's work (operations over peak FLOP/s, or bytes over peak bytes/s,
whichever is larger) over the device time of the calls in the trace. The work
is what the algorithm needs at the call's shapes, from `perfbench/opsbytes.py`:

- `flash_prefill`: the kernel inside `_serving_prefill_s<S>`: one prompt of S
  tokens, the configuration's heads, the causal half;
- `ln_bwd_train`: LayerNorm backward over micro_batch x seq_len rows;
- `causal_softmax_train`: the causal half of micro_batch x heads score rows.
"""

import re

from perfbench import opsbytes, trace_reduce


def _flash_prefill(run, kernel):
    cfg = run.config
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    least = spent = 0.0
    for name, calls in trace_reduce.kernel_events_by_module(
            run.trace, kernel, "_serving_prefill_s"):
        s = int(re.search(r"_serving_prefill_s(\d+)", name).group(1))
        t, _ = opsbytes.roofline_seconds(
            opsbytes.flash_causal_flops(1, s, nq, d),
            opsbytes.flash_causal_bytes(1, s, nq, nkv, d), run.peak)
        least += t * len(calls)
        spent += sum(calls)
    return least, spent


def _train_kernel(run, kernel, nbytes):
    calls = trace_reduce.kernel_events(run.trace, kernel)
    t, _ = opsbytes.roofline_seconds(0.0, nbytes, run.peak)
    return t * len(calls), sum(calls)


def read(run, kernel, work):
    if run.trace is None or run.peak is None:
        return None
    if work == "flash_prefill":
        least, spent = _flash_prefill(run, kernel)
    else:
        rows = run.config["train"]["micro_batch"]
        seq = run.mix["seq_len"]
        if work == "ln_bwd_train":
            nbytes = opsbytes.layer_norm_bwd_bytes(
                rows * seq, run.config["hidden_size"])
        elif work == "causal_softmax_train":
            nbytes = opsbytes.causal_softmax_bytes(
                rows * run.config["num_heads"], seq)
        else:
            raise ValueError(work)
        least, spent = _train_kernel(run, kernel, nbytes)
    return 100.0 * least / spent if spent else None
