"""Serving cells of an `afmoe` model (Trinity): a dense lead, then dropless
sigmoid-routed expert layers beside a shared expert, sliding-window and full
attention layers in one stack, as one chip of an expert-parallel deployment
holds it. `runners/serve.py`'s load loop, measures and comparison, driving an
engine whose `LlamaConfig` states the model from the configuration's own keys.
A checkout whose program cannot state it is refused before the device is
opened.
"""

import dataclasses
import gc
import math
import time

import numpy as np

from perfbench import traffic
from perfbench.references.common import seed_words
from perfbench.runners import serve
from perfbench.runners.serve import drive, sample_served

# what `LlamaConfig` has to be able to say of this model
STATES = ("attn_head_dim", "layer_types", "sliding_window", "rope_full_attention", "qk_norm",
          "attn_output_gate", "embed_scale", "num_dense_layers",
          "moe_intermediate_size", "num_shared_experts", "router_score",
          "route_norm", "route_scale", "router_bias", "experts_held",
          "sandwich_norm")


def model_config(cfg):
    """`serve.model_config` plus what makes the model an `afmoe` one, each
    from the configuration file's key of the same meaning."""
    base = serve.model_config(cfg)
    missing = set(STATES) - {f.name for f in dataclasses.fields(base)}
    if missing:
        raise SystemExit(
            f"perfbench: this checkout's LlamaConfig has no {sorted(missing)}"
            f": the program cannot state a model with dropless experts and "
            f"sliding-window layers; nothing was run")
    share = cfg["experts_held"]
    if share["count"] != cfg["num_experts"]:
        raise SystemExit("perfbench: num_experts counts the experts held; "
                         "experts_held.count disagrees")
    return dataclasses.replace(
        base, attn_head_dim=cfg["head_dim"], sandwich_norm=True,
        layer_types=tuple(cfg["layer_types"]),
        sliding_window=cfg["sliding_window"], rope_full_attention=False,
        qk_norm=True, attn_output_gate=True,
        embed_scale=cfg["hidden_size"] ** 0.5 if cfg["mup_enabled"] else 1.0,
        num_dense_layers=cfg["num_dense_layers"], num_experts=share["of"],
        moe_top_k=cfg["num_experts_per_tok"], moe_capacity_factor=None,
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["num_shared_experts"],
        router_score=cfg["score_func"], route_norm=cfg["route_norm"],
        route_scale=cfg["route_scale"], router_bias=True,
        experts_held=(share["first"], share["count"]))


class AfmoeServer(serve.Server):
    def start(self, seed):
        """The weights are the configuration's own draw (`weights_draw.seed`),
        as the order of a mix's lengths is the mix's own: which experts a
        draw's router favours decides how much of the routing falls on the
        experts held, so the draw is part of the work (61 to 71 of a step's
        128 assignments over four draws on the chip, 3.9% of the tokens per
        second: PERF.md). `--seed` draws the token ids and where the cycle of
        lengths is entered."""
        from apex_tpu.serving import ServingEngine

        self.params = self.init(*seed_words(self.cfg["weights_draw"]["seed"]))
        self.engine = ServingEngine(self.params, model_config(self.cfg),
                                    **self.run.mix["engine"])
        self.requests = []


def served_gaps(run, server, picked, precision=None):
    """`serve.check_served`, token by token: for every served token of the
    sample, how far its logit lies below the reference's best (one array);
    with `precision`, also the same for the token that the reference at that
    lower precision puts first, at the same positions (the control), and for
    a token drawn at random from the run's seed (what a wrong token reads)."""
    import jax
    import jax.numpy as jnp

    ref, cfg = server.ref, server.cfg
    cap = run.mix["engine"]["max_new_cap"]

    @jax.jit
    def gaps(params, tokens, rows, served):
        logits = ref.row_logits(params, tokens, rows, cfg)
        return ref.served_gaps(logits, served)

    @jax.jit
    def control_tokens(params, tokens, rows):
        # a program of its own: two forwards of 7,168 positions in one do
        # not fit beside the weights
        low = ref.row_logits(params, tokens, rows, cfg, precision)
        return jnp.argmax(low, axis=-1).astype(jnp.int32)

    sound, control, chance = [], [], []
    rng = traffic.rng_for(run.seed, 13)
    for prompt, served in picked:
        n, p = len(served), len(prompt)
        seq = np.concatenate([prompt, served[:-1]])
        padded = np.zeros(math.ceil(len(seq) / serve.PAD_TO) * serve.PAD_TO,
                          np.int32)
        padded[:len(seq)] = seq
        rows = np.minimum(p - 1 + np.arange(cap), len(seq) - 1)
        tokens = np.zeros(cap, np.int32)
        tokens[:n] = served
        sound.append(np.asarray(gaps(server.params, padded, rows, tokens))[:n])
        if precision:
            control.append(np.asarray(gaps(
                server.params, padded, rows,
                control_tokens(server.params, padded, rows)))[:n])
            chance.append(np.asarray(gaps(
                server.params, padded, rows, rng.integers(
                    0, cfg["vocab_size"], cap).astype(np.int32)))[:n])
    sound = np.concatenate(sound)
    return (sound, np.concatenate(control), np.concatenate(chance)) \
        if precision else sound


def summary(gaps):
    """The numbers of a sample's gaps that `correct` is decided on (the
    widest and the mean), and what else the calibration looks at."""
    return {"widest": float(gaps.max()), "mean": float(gaps.mean()),
            "p50": float(np.percentile(gaps, 50)),
            "p90": float(np.percentile(gaps, 90)),
            "p99": float(np.percentile(gaps, 99)),
            "over_half": float((gaps > 0.5).mean())}


def run(run):
    model_config(run.config)         # refuses here, before any device work
    run.open_device()
    server = AfmoeServer(run)
    attempted, failed, t_close = drive(run, server)
    run.read_memory_peak()
    picked = sample_served(run, server, t_close)
    server.free()
    check = run.mix["check"]
    compared = {"requests_to_check_missing": (float(not picked), 0.0)}
    if picked:
        got = summary(served_gaps(run, server, picked))
        compared["served_logit_gap"] = (got["widest"],
                                        check["logit_gap_limit"])
        compared["served_logit_gap_mean"] = (got["mean"],
                                             check["logit_gap_mean_limit"])
    return run.result(attempted, failed, compared)


def calibrate(workload, seeds, control_seeds, seconds, emit, **run_args):
    """`serve.calibrate` over `AfmoeServer`: for every seed a short window
    at the cell's own load and the `summary` of its sample's gaps; for the
    control seeds also that of the token that the reference at fp8 puts
    first, and of a token drawn at random, at the same positions."""
    from perfbench import harness

    for seed in seeds:
        t0 = time.monotonic()
        run = harness.Run(workload, seed, seconds, 0, **run_args)
        model_config(run.config)
        run.open_device()
        server = AfmoeServer(run)
        attempted, failed, t_close = drive(run, server)
        picked = sample_served(run, server, t_close)
        server.free()
        record = {"workload": workload, "seed": seed, "attempted": attempted,
                  "failed": failed, "sampled_requests": len(picked),
                  "sampled_tokens": sum(len(s) for _, s in picked),
                  "metrics": dict(run.e2e)}
        if seed in control_seeds:
            sound, control, chance = served_gaps(run, server, picked, "fp8")
            record["control_fp8_gap"] = summary(control)
            record["random_token_gap"] = summary(chance)
        else:
            sound = served_gaps(run, server, picked)
        record["served_logit_gap"] = summary(sound)
        server.params = None
        gc.collect()
        record["seconds"] = time.monotonic() - t0
        emit(record)
