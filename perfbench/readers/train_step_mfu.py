"""The whole training step's share of the chip's peak: forward and backward
model FLOPs per token (recomputation not counted, attention counted causal)
times the tokens per second per chip of the measured window, over the peak."""

from perfbench import opsbytes


def read(run):
    rate = run.e2e.get("train_tokens_per_s_per_chip")
    if rate is None or run.peak is None:
        return None
    flops = opsbytes.gpt2_train_flops_per_token(run.config, run.mix["seq_len"])
    return 100.0 * flops * rate / run.peak["flops_per_s"]
