"""Llama training with composed TP x PP x DP (+ sequence parallelism) —
the 3D-parallel example the reference enables through apex.transformer
(ref apex/transformer/parallel_state.py + pipeline_parallel/schedules;
the reference itself ships no end-to-end transformer example — this is the
Megatron-LM composition its pieces exist for).

TPU-native shape: one ``shard_map`` over a (pp, dp, tp) mesh contains the
whole train step — collective-1F1B pipeline via scan+ppermute, tensor- and
sequence-parallel layers, vocab-parallel cross entropy, fused Adam, and the
cross-axis gradient reductions (dp mean everywhere; pp psum of the shared
embedding/head grads — the reference's embedding-group allreduce; tp psum
of sequence-parallel norm grads). XLA overlaps the collectives with
compute; there is no NCCL-style schedule code.

The step loop is driven by ``apex_tpu.resilience.ResilientTrainLoop``
(ISSUE 5): auto-resume from the newest *valid* checkpoint, periodic +
emergency saves, retry/rollback on transient failures, SIGTERM/env
preemption handling — and ``APEX_TPU_FAULT_PLAN=preempt@7,...`` turns
any invocation into a chaos run (docs/resilience.md).

    python examples/llama_train.py --pp 2 --dp 2 --tp 2 --steps 10
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    t_main0 = time.perf_counter()
    p = argparse.ArgumentParser()
    p.add_argument("--pp", type=int, default=2)
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--microbatch-size", type=int, default=2)
    p.add_argument("--seq", type=int, default=32)
    p.add_argument("--layers-per-stage", type=int, default=2)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--no-sequence-parallel", action="store_true")
    p.add_argument("--fixed-data", action="store_true",
                   help="overfit one fixed batch (deterministic decrease)")
    p.add_argument("--checkpoint-dir", default="",
                   help="save sharded train state here (orbax)")
    p.add_argument("--save-every", type=int, default=5)
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest step in --checkpoint-dir")
    p.add_argument("--auto-shard", action="store_true",
                   help="let the analysis planner pick pp/dp/tp and the "
                        "PartitionSpec layout for the device budget "
                        "(--pp*--dp*--tp devices) instead of the "
                        "hand-written tables (docs/planner.md)")
    p.add_argument("--opt-level", default="O0", choices=["O0", "O4"],
                   help="O4 (ISSUE 13): run the lm_head matmul in fp8 "
                        "(E4M3 fwd / E5M2 grad) under delayed per-tensor "
                        "scaling; the Fp8ScalingState rides the train "
                        "state through checkpoints, so scales resume "
                        "bit-identical (docs/amp.md). The O1-O3 amp "
                        "levels apply to the apex-shaped examples "
                        "(imagenet/main_amp style); this 3D-parallel "
                        "demo exposes the fp8 tier.")
    args = p.parse_args()

    n_dev = args.pp * args.dp * args.tp
    from examples._common import ensure_devices, opt_partition_specs

    ensure_devices(n_dev)

    plan = None
    if args.auto_shard:
        # the flag's CLI contract: --pp/--dp/--tp still size the DEVICE
        # budget (so invocations stay comparable), but the planner
        # decides how to factor it and which dims shard (ISSUE 8)
        from apex_tpu.parallel import auto_shard

        # min tp=2: this step's vocab-parallel CE / sequence-parallel
        # collectives assume a bound tp axis — the executability floor
        # rides the plan request so the search never emits a mesh this
        # example cannot execute.
        # The run-derived knobs that shape the cost model's comms and
        # bubble terms ride along (seq scales activation bytes,
        # microbatches the pipeline bubble; batch/layers anchored at
        # the device budget so every dp|pp factorization divides them).
        # hidden/heads/vocab stay the planner's defaults because this
        # demo scales those dims WITH the chosen tp below.
        plan = auto_shard.plan_for(
            "llama", devices=n_dev, min_mesh={"tp": 2},
            seq=args.seq, microbatches=args.microbatches,
            batch=args.microbatches * args.microbatch_size * n_dev,
            layers=args.layers_per_stage * n_dev)
        args.pp, args.dp, args.tp = (plan.mesh["pp"], plan.mesh["dp"],
                                     plan.mesh["tp"])
        print(f"auto-shard plan: pp={args.pp} dp={args.dp} tp={args.tp} "
              f"layout={plan.layout} "
              f"(predicted {plan.predicted['step_ms']:.3f} ms/step, "
              f"comms {plan.predicted['comms_bytes']} B/step, "
              f"verified {plan.predicted['findings']} findings)")

    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    from apex_tpu.models import llama
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        pipelined_forward,
    )
    from apex_tpu.transformer.tensor_parallel.cross_entropy import (
        vocab_parallel_cross_entropy,
    )
    from apex_tpu.transformer.tensor_parallel.mappings import _to_varying

    pp, dp, tp = args.pp, args.dp, args.tp
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(pp, dp, tp),
                ("pp", "dp", "tp"))
    sp = tp > 1 and not args.no_sequence_parallel

    cfg = llama.tiny(
        num_layers=args.layers_per_stage * pp, num_heads=2 * tp,
        num_kv_heads=tp, hidden_size=32 * tp, intermediate_size=64 * tp,
        vocab_size=128 * tp, max_seq_len=args.seq)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    stage_params = llama.split_stages(params, pp)
    io_params = {k: v for k, v in params.items() if k != "layers"}

    M, mb, s = args.microbatches, args.microbatch_size, args.seq
    tx = fused_adam(lr=args.lr)

    # O4 fp8 tier (ISSUE 13): one registered site — the lm_head
    # projection, the biggest single matmul in the step (hidden x
    # vocab). Decoder-layer matmuls live inside the lax.scan over
    # layers, where the delayed-scaling context deliberately falls back
    # to the fp32-accum path (a collected amax may not escape a
    # transform); registering only "lm_head" makes that explicit.
    fp8 = None
    if args.opt_level == "O4":
        from apex_tpu.amp import Fp8DelayedScaler

        fp8 = Fp8DelayedScaler(["lm_head"], history=16)
        print("opt-level O4: lm_head in fp8 (E4M3/E5M2, delayed "
              "scaling, history=16)")

    def psum(t, ax):
        return jax.lax.psum(_to_varying(t, ax), ax)

    def pmean(t, ax):
        return jax.lax.pmean(_to_varying(t, ax), ax)

    def train_step(stage_params, io_params, opt_state, tokens, targets,
                   fp8_state=None):
        pp_rank = jax.lax.axis_index("pp")
        pp_size = jax.lax.axis_size("pp")

        def vary_all(t):
            for ax in ("pp", "dp", "tp"):
                t = jax.tree_util.tree_map(
                    lambda a, ax=ax: _to_varying(a, ax), t)
            return t

        def total_loss(trees):
            stage, io = trees
            stage = jax.tree_util.tree_map(lambda a: a[0], stage)
            stage, io = vary_all(stage), vary_all(io)

            x_mb = vary_all(jax.vmap(
                lambda tok: llama.embed(io, tok, cfg, tp_axis="tp",
                                        sequence_parallel=sp))(tokens))
            positions = llama._positions(mb, s, None)

            def stage_fn(sp_params, x):
                return llama.stage_fn(sp_params, x, cfg, positions,
                                      tp_axis="tp", cp_axis=None,
                                      sequence_parallel=sp)

            outs = pipelined_forward(stage_fn, stage, x_mb, axis_name="pp",
                                     remat=True)

            if fp8 is not None:
                # O4: fold the microbatch dim into the batch and run ONE
                # lm_head call outside any vmap — the fp8 context's amax
                # collection cannot cross a transform boundary, and the
                # folded gemm is the same math (equal-sized microbatches
                # mean mean-of-means == global mean)
                o2 = outs.reshape((M * mb,) + outs.shape[2:])
                t2 = targets.reshape((M * mb,) + targets.shape[2:])
                logits = llama.lm_head(io, o2, cfg, tp_axis="tp",
                                       sequence_parallel=sp)
                losses = jnp.mean(vocab_parallel_cross_entropy(
                    logits, t2, axis_name="tp"))
            else:
                def mb_loss(o, t):
                    logits = llama.lm_head(io, o, cfg, tp_axis="tp",
                                           sequence_parallel=sp)
                    return jnp.mean(vocab_parallel_cross_entropy(
                        logits, t, axis_name="tp"))

                losses = jnp.mean(jax.vmap(mb_loss)(outs, targets))
            local = jnp.where(pp_rank == pp_size - 1, losses, 0.0)
            return jax.lax.psum(local, "pp")

        if fp8 is not None:
            with fp8.step(fp8_state) as fp8_ctx:
                loss, (g_stage, g_io) = fp8_ctx.value_and_grad(
                    total_loss)((stage_params, io_params))
            # pmax the observations over EVERY mesh axis so all ranks
            # write identical ring columns and the delayed scales stay
            # replicated (non-last pp stages observe their bubble
            # activations too — a conservative over-estimate that only
            # lowers the scale)
            new_fp8 = fp8.update(fp8_state, fp8_ctx,
                                 reduce_axes=("pp", "dp", "tp"))
        else:
            loss, (g_stage, g_io) = jax.value_and_grad(total_loss)(
                (stage_params, io_params))
            new_fp8 = fp8_state

        g_stage = jax.tree_util.tree_map(lambda g: pmean(g, "dp"), g_stage)
        g_io = jax.tree_util.tree_map(
            lambda g: pmean(psum(g, "pp"), "dp"), g_io)
        if sp:  # sequence-parallel norm grads are tp-partial (Megatron SP)
            g_stage = {k: (psum(v, "tp") if k.endswith("norm") else v)
                       for k, v in g_stage.items()}
            g_io = {k: (psum(v, "tp") if k == "final_norm" else v)
                    for k, v in g_io.items()}

        grads = {"stage": g_stage, "io": g_io}
        updates, opt_state = tx.update(
            grads, opt_state, {"stage": stage_params, "io": io_params})
        new_stage = jax.tree_util.tree_map(
            jnp.add, stage_params, updates["stage"])
        new_io = jax.tree_util.tree_map(jnp.add, io_params, updates["io"])
        loss = jax.lax.pmean(jax.lax.pmean(loss, "dp"), "tp")
        if fp8 is not None:
            return new_stage, new_io, opt_state, new_fp8, loss
        return new_stage, new_io, opt_state, loss

    if plan is not None:
        # the plan's spec tables replace the hand-written layout: layer
        # specs gain the leading stage dim, io specs apply as-is (at
        # tp=1 the planner's entries degenerate to replicated, which is
        # exactly what a tp=1 mesh needs)
        from apex_tpu.parallel import auto_shard

        lp = auto_shard.spec_group(plan, "layers")
        io_specs = auto_shard.spec_group(plan, "io")
    else:
        lp = llama.param_specs(cfg)["layers"]
        io_specs = {"embed": P("tp", None), "final_norm": P(),
                    "lm_head": P(None, "tp")}
    stage_specs = {k: P("pp", *lp[k]) for k in lp}

    with mesh:
        opt_state = tx.init({"stage": stage_params, "io": io_params})
        opt_specs = opt_partition_specs(
            tx, {"stage": stage_params, "io": io_params},
            {"stage": stage_specs, "io": io_specs})

        if fp8 is not None:
            # the Fp8ScalingState is replicated (every leaf P()): the
            # pmax'd updates keep all ranks' rings bit-identical, and a
            # replicated spec is what lets the restored state resume
            # bit-identical after preempt/crash-restart
            fp8_state0 = fp8.init()
            fp8_specs = jax.tree_util.tree_map(lambda _: P(), fp8_state0)
            step = jax.jit(shard_map(
                train_step, mesh=mesh,
                in_specs=(stage_specs, io_specs, opt_specs,
                          P(None, "dp", None), P(None, "dp", None),
                          fp8_specs),
                out_specs=(stage_specs, io_specs, opt_specs, fp8_specs,
                           P()),
            ))
        else:
            step = jax.jit(shard_map(
                train_step, mesh=mesh,
                in_specs=(stage_specs, io_specs, opt_specs,
                          P(None, "dp", None), P(None, "dp", None)),
                out_specs=(stage_specs, io_specs, opt_specs, P()),
            ))

        # per-step telemetry through the shared layer: structured step
        # records (step time, tokens/s, loss) land in the process
        # registry; APEX_TPU_METRICS=<path> dumps the run as JSONL for
        # `python -m apex_tpu.observability report`
        from apex_tpu import observability as obs
        from apex_tpu import resilience

        reporter = obs.StepReporter("llama_train",
                                    tokens_per_step=M * mb * dp * s)
        # per-step phase attribution (ISSUE 7): every step runs inside a
        # span window; the data/compute/comms/host fractions land on the
        # StepReporter record, so the step log says WHERE the time went
        phases = obs.StepPhases(name="llama_train/step")
        # numerics tier (ISSUE 9): a decimated fused stats pass over the
        # param tree (amax/l2/underflow/finite, ONE host fetch every 8
        # steps) rides the step record's numerics block, and the health
        # monitor turns loss trajectories into numerics/* events before
        # the resilience ladder has to act
        collector = obs.StatsCollector("llama_train", every=8)
        health = obs.HealthMonitor("llama_train")
        # memory tier (ISSUE 15): a decimated live-HBM snapshot (one
        # host-side walk of the live buffers every 8 steps) rides the
        # step record's memory block; the monitor's watermark + top-k
        # buffers feed the OOM forensics verdict the resilience loop
        # attaches when a step dies RESOURCE_EXHAUSTED
        memmon = obs.MemoryMonitor("llama_train", every=8)
        key = jax.random.PRNGKey(1)
        stats = {"first": None, "last": None}

        def make_batch(it):
            # the data stream is a pure function of the step index
            # (fold_in) — the property the loop's bit-identical
            # resume-replay guarantee rests on
            sub = jax.random.fold_in(key, 0 if args.fixed_data else it)
            tokens = jax.random.randint(sub, (M, mb * dp, s), 0,
                                        cfg.vocab_size)
            return tokens, jnp.roll(tokens, -1, axis=-1)

        def train_step_fn(state, it):
            with phases.step():
                # t0 before make_batch: step_time_ms must cover the same
                # window as the phase fractions, or step_time × phases
                # misattributes the excluded data time
                t0 = time.perf_counter()
                with obs.span("data/batch"):
                    tokens, targets = make_batch(it)
                if fp8 is not None:
                    new_stage, new_io, new_opt, new_fp8, loss = step(
                        state["stage"], state["io"], state["opt"],
                        tokens, targets, state["fp8"])
                else:
                    new_stage, new_io, new_opt, loss = step(
                        state["stage"], state["io"], state["opt"],
                        tokens, targets)
                loss = float(loss)  # host pull: syncs the step chain
                dt = time.perf_counter() - t0
            collector.observe({"stage": new_stage, "io": new_io}, it)
            health.observe(it, loss=loss)
            memmon.observe(it)
            rec = reporter.step(dt, loss=loss, numerics=collector.last,
                                memory=memmon.last,
                                **phases.last_fields())
            if stats["first"] is None:
                stats["first"] = loss
            stats["last"] = loss
            print(f"step {it:3d}  loss {loss:.4f}  "
                  f"({rec['step_time_ms']:.0f} ms  "
                  f"{rec['tokens_per_sec']:.0f} tok/s)")
            new_state = {"stage": new_stage, "io": new_io,
                         "opt": new_opt}
            if fp8 is not None:
                new_state["fp8"] = new_fp8
            return new_state, {"loss": loss}

        # resilient driver (ISSUE 5): the ref-style epoch checkpointing
        # of main_amp.py upgraded to the production contract — sharded
        # train state round-trips through orbax with commit markers,
        # SIGTERM/APEX_TPU_PREEMPT forces an emergency save + exit 75,
        # checkpoint I/O is retried, APEX_TPU_FAULT_PLAN injects chaos
        fault_spec = os.environ.get("APEX_TPU_FAULT_PLAN")
        # stall flight recorder (ISSUE 7): a step that runs past 3x the
        # trailing median (or APEX_TPU_STALL_DEADLINE seconds) dumps the
        # span ring, all thread stacks and the last registry events to a
        # flightrec_*.json post-mortem; its sensor feeds the preemption
        # watcher so a hung fleet ALSO takes the emergency-checkpoint +
        # exit-75 path instead of burning its allocation
        deadline = os.environ.get("APEX_TPU_STALL_DEADLINE")
        try:
            deadline_s = float(deadline) if deadline else None
        except ValueError:
            raise SystemExit(
                f"APEX_TPU_STALL_DEADLINE={deadline!r} is not a number "
                f"(wall-deadline seconds, e.g. 120)")
        recorder = obs.FlightRecorder(
            directory=args.checkpoint_dir or None,
            # 10x median, not the default 3x: a contended CI host can
            # jitter a CPU step 3x without anything being wedged, and a
            # false stall here escalates to exit 75 via the sensor
            stall_factor=10.0,
            deadline_s=deadline_s).install()
        watcher = resilience.PreemptionWatcher(
            sensors=[resilience.env_sensor(), recorder.sensor()]).install()
        loop = resilience.ResilientTrainLoop(
            train_step_fn,
            flight_recorder=recorder,
            directory=args.checkpoint_dir or None,
            save_every=args.save_every, max_to_keep=2,
            retry_policy=resilience.Policy(max_attempts=3, name="llama"),
            fault_plan=(resilience.FaultPlan.parse(fault_spec)
                        if fault_spec else None),
            watcher=watcher, auto_resume=args.resume,
            memory_monitor=memmon,  # OOM forensics read its watermark
            check_state_every=0,  # loss is the health signal; skip the
            # per-step full-state device fetch on the 3D-sharded tree
            exit_on_preempt=True,  # the scheduler-facing contract:
            # emergency checkpoint, then exit 75 (EX_TEMPFAIL) = rerun me
            on_resume=lambda it: print(f"=> resumed from step {it}"))
        init_state = {"stage": stage_params, "io": io_params,
                      "opt": opt_state}
        if fp8 is not None:
            # the fp8 scaling state checkpoints/restores with the rest
            # of the train state — delayed scales are replay-stable
            init_state["fp8"] = fp8_state0
        try:
            loop.run(init_state, args.steps)
        finally:
            watcher.uninstall()
            recorder.uninstall()

    if stats["first"] is None:
        print(f"nothing to do: resumed step + 1 "
              f"({(loop.resumed_from or 0) + 1}) >= --steps {args.steps}")
    else:
        print(f"mesh pp={pp} dp={dp} tp={tp} sp={sp}: "
              f"loss {stats['first']:.4f} -> {stats['last']:.4f} "
              f"({'decreased' if stats['last'] < stats['first'] else 'NOT decreased'})")

    if os.environ.get("APEX_TPU_METRICS"):
        reg = obs.get_registry()
        # goodput accounting (ISSUE 17): publish the goodput/* gauge
        # family before the dump so the run's JSONL carries its own
        # accounting (re-derivable offline:
        # `python -m apex_tpu.observability goodput <dump>`)
        try:
            ledger = obs.ledger_from_records(reg.to_records())
            acc = obs.account_goodput(
                ledger, wall_s=time.perf_counter() - t_main0)
            obs.goodput.publish(acc, reg)
            print(f"goodput {acc['goodput_ratio']:.4f} "
                  f"(productive {acc['productive_s']:.2f}s of "
                  f"{acc['wall_s']:.2f}s wall)")
        except Exception as e:  # telemetry must not cost the run
            print(f"goodput accounting failed: {e!r}")
        reg.dump(os.environ["APEX_TPU_METRICS"])
        print(f"metrics -> {os.environ['APEX_TPU_METRICS']}")


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
