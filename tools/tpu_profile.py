#!/usr/bin/env python
"""Capture a jax.profiler trace of the flagship Llama train step on the
live TPU (SURVEY §5 tracing subsystem, operationalized).

Companion to chip_smoke.py (correctness pre-flight) and bench.py
(numbers): this produces the xplane trace that says WHERE the step time
goes — MXU busy %, HBM stalls, collective time — for the
profile-and-iterate loop the scaling playbook prescribes.

    python tools/tpu_profile.py                 # ~5 traced steps
    python tools/tpu_profile.py --out /tmp/trace --steps 10 --batch 8

View with TensorBoard's profile plugin or xprof on the written logdir.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="/tmp/apex_tpu_trace")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--vocab-chunks", type=int, default=0,
                    help="stream the lm-head CE in N slices (0 = off)")
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "full"],
                    help="default 'none' matches bench.py's surviving "
                         "ladder rung (no-remat B=4 fits a v5e) so the "
                         "profile explains the bench number; pass 'dots' "
                         "to compare with r5's TPU_TRACE_r05 capture")
    ap.add_argument("--force", action="store_true",
                    help="profile even on a non-TPU backend")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from apex_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    dev = jax.devices()[0]
    print(f"platform={dev.platform} kind={dev.device_kind}", flush=True)
    if dev.platform != "tpu" and not args.force:
        print("not a TPU backend — pass --force to trace anyway")
        return 2

    from apex_tpu.models import llama
    from apex_tpu.optimizers import fused_adam

    cfg = llama.flagship_0p9b()
    remat = {"none": False, "dots": "dots", "full": True}[args.remat]
    chunks = args.vocab_chunks or None

    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, cfg.max_seq_len),
                                0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1)
    tx = fused_adam(lr=1e-4)
    opt_state = tx.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(llama.loss_fn)(
            params, batch, cfg, tp_axis=None, cp_axis=None, remat=remat,
            vocab_chunks=chunks)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state, loss

    batch = (tokens, targets)
    # compile + warm outside the trace
    from apex_tpu.runtime import timing

    params, opt_state, loss = train_step(params, opt_state, batch)
    print(f"warm step loss={float(loss):.4f}; tracing {args.steps} steps "
          f"to {args.out}", flush=True)

    t0 = time.perf_counter()
    with jax.profiler.trace(args.out):
        for i in range(args.steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                params, opt_state, loss = train_step(params, opt_state,
                                                     batch)
        timing.sync(loss)
    dt = (time.perf_counter() - t0) / args.steps
    print(f"traced: {dt * 1e3:.1f} ms/step  -> {args.out}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
