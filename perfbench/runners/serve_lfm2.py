"""Serving cells of an `lfm2_moe` model (LFM2-24B-A2B): gated short-convolution
layers beside grouped-query attention layers in one stack, a dense lead, then
dropless sigmoid-routed experts, all of them held on the chip.
`runners/serve.py`'s load loop and measures and `runners/serve_afmoe.py`'s
comparison (the gaps of the served tokens' float32 reference logits), driving
an engine whose `LlamaConfig` states the model from the configuration's own
keys. A checkout whose program cannot state it is refused before the device
is opened.
"""

import contextlib
import dataclasses
import gc
import time

import numpy as np

from perfbench.references import lfm2_moe
from perfbench.runners import serve
from perfbench.runners.serve import drive, sample_served
from perfbench.runners.serve_afmoe import served_gaps, summary

# what `LlamaConfig` has to be able to say of this model
STATES = ("layer_types", "conv_L_cache", "conv_bias", "route_norm_eps",
          "attn_head_dim", "qk_norm", "num_dense_layers",
          "moe_intermediate_size", "router_score", "route_norm",
          "route_scale", "router_bias", "moe_capacity_factor")


def model_config(cfg):
    """The program's `LlamaConfig` of the model, each field from the
    configuration file's key of the same meaning."""
    import jax.numpy as jnp
    from apex_tpu.models import llama

    missing = set(STATES) - {f.name for f in
                             dataclasses.fields(llama.LlamaConfig)}
    if missing:
        raise SystemExit(
            f"perfbench: this checkout's LlamaConfig has no {sorted(missing)}"
            f": the program cannot state a model with conv layers beside "
            f"attention layers; nothing was run")
    _, nq, nkv, d = lfm2_moe.dims(cfg)
    try:
        return llama.LlamaConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"], num_heads=nq,
            num_kv_heads=nkv, attn_head_dim=d,
            max_seq_len=cfg["max_position_embeddings"],
            rope_theta=lfm2_moe.rope_theta(cfg), rms_eps=cfg["norm_eps"],
            dtype=jnp.dtype(cfg["torch_dtype"]),
            tie_embeddings=cfg["tie_word_embeddings"],
            layer_types=tuple(cfg["layer_types"]),
            conv_L_cache=cfg["conv_L_cache"], conv_bias=cfg["conv_bias"],
            qk_norm=True, num_dense_layers=cfg["num_dense_layers"],
            num_experts=cfg["num_experts"],
            moe_top_k=cfg["num_experts_per_tok"], moe_capacity_factor=None,
            moe_intermediate_size=cfg["moe_intermediate_size"],
            router_score="sigmoid", route_norm=cfg["norm_topk_prob"],
            route_scale=float(cfg["routed_scaling_factor"]),
            route_norm_eps=lfm2_moe.ROUTE_NORM_EPS,
            router_bias=cfg["use_expert_bias"])
    except ValueError as e:          # a LlamaConfig that knows no conv layer
        raise SystemExit(f"perfbench: this checkout's LlamaConfig refuses "
                         f"the model ({e}); nothing was run")


class Lfm2Server(serve.Server):
    def start(self, seed):
        from apex_tpu.serving import ServingEngine

        self.params = self.init(*serve.seed_words(seed))
        self.engine = ServingEngine(self.params, model_config(self.cfg),
                                    **self.run.mix["engine"])
        self.requests = []


def first_steps(run, picked, gaps):
    """The mean of `gaps` over the sample's decode steps whose convolution
    still reads what the prefill handed on: each request's served tokens 1 to
    `conv_L_cache - 1` (token 0 is the prefill's own). A state taken at the
    bucket's end, not at the prompt's, is wrong in these and in no other, and
    they are a fortieth of the sample: the mean over all of it hardly moves."""
    starts = np.cumsum([0] + [len(served) for _, served in picked[:-1]])
    steps = 1 + np.arange(run.config["conv_L_cache"] - 1)
    return float(gaps[(starts[:, None] + steps).ravel()].mean())


def run(run):
    model_config(run.config)         # refuses here, before any device work
    run.open_device()
    server = Lfm2Server(run)
    attempted, failed, t_close = drive(run, server)
    run.read_memory_peak()
    picked = sample_served(run, server, t_close)
    server.free()
    check = run.mix["check"]
    compared = {"requests_to_check_missing": (float(not picked), 0.0)}
    if picked:
        gaps = served_gaps(run, server, picked)
        got = summary(gaps)
        compared["served_logit_gap"] = (got["widest"],
                                        check["logit_gap_limit"])
        compared["served_logit_gap_mean"] = (got["mean"],
                                             check["logit_gap_mean_limit"])
        compared["served_logit_gap_first_steps"] = (
            first_steps(run, picked, gaps),
            check["logit_gap_first_steps_limit"])
    return run.result(attempted, failed, compared)


@contextlib.contextmanager
def state_at_the_buckets_end():
    """The fault the padding invites, planted in the program for one window
    of `calibrate`: every prefill hands on the conv state of its bucket's
    last positions, not of the prompt's."""
    from apex_tpu.models import generate

    true = generate._prefill_attend
    generate._prefill_attend = lambda lp, cfg, length=None: true(lp, cfg)
    try:
        yield
    finally:
        generate._prefill_attend = true


def calibrate(workload, seeds, control_seeds, seconds, emit, **run_args):
    """`serve_afmoe.calibrate` over `Lfm2Server`: for every seed a short
    window at the cell's own load and the `summary` of its sample's gaps; for
    the control seeds also that of the token that the reference at fp8 puts
    first, and of a token drawn at random, at the same positions, and a
    second window with `state_at_the_buckets_end` planted (`planted: true`).
    Every record carries `first_steps` beside the summary."""
    from perfbench import harness

    for seed, fault in [(s, f) for s in seeds for f in (
            (False, True) if s in control_seeds else (False,))]:
        t0 = time.monotonic()
        run = harness.Run(workload, seed, seconds, 0, **run_args)
        model_config(run.config)
        run.open_device()
        server = Lfm2Server(run)
        with state_at_the_buckets_end() if fault else \
                contextlib.nullcontext():
            attempted, failed, t_close = drive(run, server)
        picked = sample_served(run, server, t_close)
        server.free()
        record = {"workload": workload, "seed": seed, "planted": fault,
                  "attempted": attempted, "failed": failed,
                  "sampled_requests": len(picked),
                  "sampled_tokens": sum(len(s) for _, s in picked),
                  "metrics": dict(run.e2e)}
        if seed in control_seeds and not fault:
            sound, control, chance = served_gaps(run, server, picked, "fp8")
            record["control_fp8_gap"] = summary(control)
            record["random_token_gap"] = summary(chance)
        else:
            sound = served_gaps(run, server, picked)
        record["served_logit_gap"] = {
            **summary(sound), "first_steps": first_steps(run, picked, sound)}
        server.params = None
        gc.collect()
        record["seconds"] = time.monotonic() - t0
        emit(record)
