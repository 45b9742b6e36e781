"""Serving cells of a looped model (a stack of layers run several times over
shared weights): `runners/serve.py`'s load loop, measures and comparison,
driving an engine whose `LlamaConfig` carries the loop. `serve.Server` builds
a plain config; `LoopedServer` builds the looped one from the configuration's
own keys, and `run` and `calibrate` are `serve`'s over that class. A checkout
whose program cannot express the loop is refused before the device is opened.
"""

import dataclasses
import gc
import time

from perfbench.references.common import seed_words
from perfbench.runners import serve
from perfbench.runners.serve import check_served, drive, sample_served


def model_config(cfg):
    """`serve.model_config` plus the loop: `total_ut_steps` passes over the
    stack, and a norm on each sub-layer's output as well as its input."""
    base = serve.model_config(cfg)
    looped = {"num_passes", "sandwich_norm"} - {
        f.name for f in dataclasses.fields(base)}
    if looped:
        raise SystemExit(
            f"perfbench: this checkout's LlamaConfig has no {sorted(looped)}: "
            f"the program cannot run a stack several times; nothing was run")
    if cfg.get("head_dim", base.head_dim) != base.head_dim:
        raise SystemExit(
            f"perfbench: head_dim {cfg['head_dim']} is not hidden_size / "
            f"num_attention_heads = {base.head_dim}")
    return dataclasses.replace(base, num_passes=int(cfg["total_ut_steps"]),
                               sandwich_norm=bool(cfg["sandwich_norm"]))


class LoopedServer(serve.Server):
    def start(self, seed):
        from apex_tpu.serving import ServingEngine

        self.params = self.init(*seed_words(seed))
        self.engine = ServingEngine(self.params, model_config(self.cfg),
                                    **self.run.mix["engine"])
        self.requests = []


def run(run):
    model_config(run.config)         # refuses here, before any device work
    run.open_device()
    server = LoopedServer(run)
    attempted, failed, t_close = drive(run, server)
    run.read_memory_peak()
    picked = sample_served(run, server, t_close)
    server.free()
    limit = run.mix["check"]["logit_gap_limit"]
    compared = {"requests_to_check_missing": (float(not picked), 0.0)}
    if picked:
        compared["served_logit_gap"] = (check_served(run, server, picked),
                                        limit)
    return run.result(attempted, failed, compared)


def calibrate(workload, seeds, control_seeds, seconds, emit, **run_args):
    """`serve.calibrate` over `LoopedServer`: for every seed a short window
    at the cell's own load and the widest gap of its sample; for the control
    seeds also the widest gap of the token that the reference at fp8 puts
    first, at the same positions."""
    from perfbench import harness

    for seed in seeds:
        t0 = time.monotonic()
        run = harness.Run(workload, seed, seconds, 0, **run_args)
        model_config(run.config)
        run.open_device()
        server = LoopedServer(run)
        attempted, failed, t_close = drive(run, server)
        picked = sample_served(run, server, t_close)
        server.free()
        record = {"workload": workload, "seed": seed, "attempted": attempted,
                  "failed": failed, "sampled_requests": len(picked),
                  "sampled_tokens": sum(len(s) for _, s in picked),
                  "metrics": dict(run.e2e)}
        if seed in control_seeds:
            record["served_logit_gap"], record["control_fp8_gap"] = \
                check_served(run, server, picked, precision="fp8")
        else:
            record["served_logit_gap"] = check_served(run, server, picked)
        server.params = None
        gc.collect()
        record["seconds"] = time.monotonic() - t0
        emit(record)
