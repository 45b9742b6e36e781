"""The fused softmax's backward kernel's share of its roofline: the least time
the chip could take for each call's bytes over the device time of the calls of
stem `kernel` in the trace. The bytes are what the mathematics needs at the
cell's shapes, whatever the kernel moves: the causal half of the probabilities
and of their cotangent read, and of the scores' cotangent written, in bf16
(rows above the diagonal are zero in all three). Nothing where the kernel
never ran, which makes the metric the count of the mechanism engaging.
"""

from perfbench import opsbytes, trace_reduce


def causal_softmax_bwd_bytes(batch_heads, seq_len, itemsize=2):
    """y and g read and dx written, the causal half of each."""
    return 3 * batch_heads * seq_len * (seq_len + 1) / 2 * itemsize


def read(run, kernel):
    if run.trace is None or run.peak is None:
        return None
    calls = trace_reduce.kernel_events(run.trace, kernel)
    if not calls:
        return None
    nbytes = causal_softmax_bwd_bytes(
        run.config["train"]["micro_batch"] * run.config["num_heads"],
        run.mix["seq_len"])
    least, _ = opsbytes.roofline_seconds(0.0, nbytes, run.peak)
    return 100.0 * least * len(calls) / sum(calls)
