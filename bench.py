"""Headline benchmark: FusedAdam step time vs "eager" per-tensor Adam,
plus model-level step benches (Llama train step MFU, ResNet-50 images/s).

The reference's primary perf claim (BASELINE.json north star) is fused
multi-tensor optimizer steps >=3x an eager per-tensor Adam loop (one kernel
dispatch per tensor, ref csrc/multi_tensor_adam.cu vs torch.optim.Adam).
On TPU the analog of the eager loop is one jit call PER TENSOR (dispatch
bound, like torch eager); apex_tpu's fused_adam updates the whole tree in
ONE jitted program.

One process, one chip: ``python bench.py`` runs every section in this
process on the backend JAX selects, and that backend must be the TPU — a
run that finds none exits non-zero and prints no metric, and a section
that raises ends the run with its traceback. (``BENCH_FORCE_CPU=1`` is the
CPU-sized mode the slow tier-1 test drives; nothing sets it on a run that
was asked for the chip.)

Prints JSON lines, the last one complete:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}
vs_baseline > 1.0 means beating the reference's 3x target.
"""

import functools
import gc
import json
import os
import sys
import time

TARGET_SPEEDUP = 3.0  # reference north star: fused >= 3x eager


def _peak_flops(device_kind: str):
    """Peak bf16 FLOP/s by device generation — the table lives in
    apex_tpu.observability.step_report (single source of truth for
    bench, StepReporter MFU, and the examples)."""
    from apex_tpu.observability.step_report import peak_flops
    return peak_flops(device_kind)


def _metrics_path() -> str:
    """Where this run's metrics JSONL lands (APEX_TPU_METRICS overrides;
    default: BENCH_METRICS.jsonl next to bench.py). Summarize with
    ``python -m apex_tpu.observability report <path>``."""
    return os.environ.get(
        "APEX_TPU_METRICS",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_METRICS.jsonl"))


def make_params(key, n_layers=24, hidden=1024, vocab=50304):
    """A GPT-2-345M-shaped tree (~150 tensors, ~350M params at defaults).

    CPU mode shrinks ``hidden``/``vocab`` so the workload stays
    dispatch-bound — the quantity this benchmark measures — instead of
    being swamped by CPU elementwise compute.
    """
    import jax
    import jax.numpy as jnp
    h = hidden
    sizes = []
    for _ in range(n_layers):  # n_layers x 6 tensors
        sizes += [(h, 3 * h), (3 * h,), (h, h), (h, 4 * h), (4 * h, h), (h,)]
    sizes += [(vocab, h), (h, h)]
    params = {}
    for i, s in enumerate(sizes):
        key, k = jax.random.split(key)
        params[f"p{i}"] = jax.random.normal(k, s, jnp.float32) * 0.02
    return params


# The timing machinery (timed regions that end in a blocking sync,
# on-device scan loops) lives in apex_tpu/runtime/timing.py so tools/ and
# examples/ share one audited implementation. These delegates keep
# bench.py's public names (tests and older notes reference bench.time_fn
# etc.) and import lazily, so importing this file touches no backend.

def _sync(out):
    """Blocking sync — see apex_tpu.runtime.timing.sync."""
    from apex_tpu.runtime import timing
    return timing.sync(out)


def time_fn(fn, *args, **kw):
    """Independent-call timing — see timing.time_fn."""
    from apex_tpu.runtime import timing
    return timing.time_fn(fn, *args, **kw)


def time_train_step(step, state, batch, iters=10):
    """Chained train-step timing — see timing.time_train_step."""
    from apex_tpu.runtime import timing
    return timing.time_train_step(step, state, batch, iters=iters)


def time_chained(step, grads, state, params, iters=100):
    """Output-feeds-input timing — see timing.time_chained."""
    from apex_tpu.runtime import timing
    return timing.time_chained(step, grads, state, params, iters=iters)


def time_scanned(make_step, carry, chain, k=32, reps=3):
    """On-device scan-slope timing — see timing.time_scanned."""
    from apex_tpu.runtime import timing
    return timing.time_scanned(make_step, carry, chain, k=k, reps=reps)


def bench_fused_adam(cpu_mode, extras):
    import jax
    import jax.numpy as jnp
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.optimizers._math import adam_step

    if cpu_mode:
        # dispatch-bound sizing: CPU elementwise compute on a 350M tree
        # would swamp the dispatch overhead this benchmark measures
        shape_kw = dict(n_layers=24, hidden=64, vocab=5030)
        chained_iters, eager_iters = 50, 3
    else:
        shape_kw = dict(n_layers=24)
        chained_iters, eager_iters = 100, 3

    key = jax.random.PRNGKey(0)
    params = make_params(key, **shape_kw)
    grads = jax.tree_util.tree_map(lambda p: jnp.full_like(p, 1e-3), params)

    # fused: whole tree in ONE jitted update, opt state donated the way a
    # real train step would. Two variants of the one-dispatch design:
    # tree (per-leaf fused chains) and flat (per-dtype packed buffer — the
    # multi_tensor_apply end state, SURVEY.md §2 #10). The headline takes
    # the faster; both are reported.
    def time_fused(flat):
        tx = fused_adam(lr=1e-3, weight_decay=0.01, flat=flat)
        state = tx.init(params)

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def fused_step(grads, state, params):
            updates, state = tx.update(grads, state, params)
            return jax.tree_util.tree_map(jnp.add, params, updates), state

        # donation consumes the argument buffers — hand each run its own
        # copies so the eager baselines below still own live params
        t = time_chained(
            fused_step, grads, state,
            jax.tree_util.tree_map(jnp.copy, params), iters=chained_iters)
        # the executable pins the donated state/params copies; drop it
        gc.collect()
        jax.clear_caches()
        gc.collect()
        return t

    tree_t = time_fused(flat=False)
    flat_t = time_fused(flat=True)
    fused_t = min(tree_t, flat_t)
    extras["tree_fused_step_ms"] = round(tree_t * 1e3, 3)
    extras["flat_fused_step_ms"] = round(flat_t * 1e3, 3)
    print(f"fused: tree {tree_t * 1e3:.3f} / flat {flat_t * 1e3:.3f} ms/step",
          file=sys.stderr)

    # the race verdict as telemetry: which one-dispatch design won, and
    # (via fused_adam's trace-time dispatch counter, already in the
    # registry) whether flat took the Pallas kernel or the XLA chain —
    # the acceptance criterion's "kernel-dispatch choice" record
    from apex_tpu import observability as obs

    choice = "flat" if flat_t < tree_t else "tree"
    extras["fused_adam_dispatch_choice"] = choice
    reg = obs.get_registry()
    reg.gauge("optimizer/fused_adam/choice").set(choice)
    reg.event("kernel_dispatch", component="fused_adam", choice=choice,
              tree_ms=round(tree_t * 1e3, 3),
              flat_ms=round(flat_t * 1e3, 3))

    # per-step phase attribution (ISSUE 7): one fresh instrumented step
    # through the span layer — tracing runs inside the step window, so
    # the fused_adam/* hot-path spans plus an explicit data span
    # decompose the step wall into data/compute/host fractions; the
    # fractions ride the StepReporter record and the JSON line (device-
    # side fractions come from an xplane capture via
    # `python -m apex_tpu.observability trace`)
    phase_fields = {}
    try:
        phases = obs.StepPhases(name="bench/fused_adam_step")
        tx_p = fused_adam(lr=1e-3, weight_decay=0.01)
        # init outside the phases window: state allocation is setup,
        # not step work, and would skew the fractions
        state_p = tx_p.init(params)
        t0 = time.perf_counter()
        with phases.step():
            with obs.span("data/batch"):
                g_p = jax.tree_util.tree_map(jnp.copy, grads)
            u_p, _ = tx_p.update(g_p, state_p, params)
            _sync(u_p)
        window_ms = (time.perf_counter() - t0) * 1e3
        phase_fields = phases.last_fields()
        # the fractions decompose THIS instrumented window (first
        # instrumented call: spans fire during trace/eager execution),
        # not the warm-median fused_t — carry its wall explicitly so
        # step_time_ms x phases is never the implied (wrong) product
        phase_fields["phase_window_ms"] = round(window_ms, 3)
        extras["phase_breakdown"] = phase_fields
        del g_p, state_p, u_p
        gc.collect()
    except Exception as e:  # telemetry must not cost the headline
        extras["phase_breakdown_error"] = repr(e)[:120]

    # numerics stats-pass overhead (ISSUE 9): one fused on-device
    # amax/l2/underflow/finite pass over the 150-tensor param tree,
    # measured warm, then the decimation interval is CHOSEN so the
    # amortized cost stays under 2% of the fused step time — the
    # budget is derived from measurements, not asserted by hope. The
    # numerics/* gauge family lands in BENCH_METRICS.jsonl and the
    # JSON line carries the numerics object.
    numerics_block = None
    try:
        import math

        coll = obs.StatsCollector("bench/fused_adam", every=1,
                                  registry=reg)
        coll.observe(params, 0)           # compile + first pull
        summary = coll.observe(params, 0)  # warm: the steady-state cost
        stats_ms = summary["stats_pass_ms"]
        step_ms = fused_t * 1e3
        budget_frac = 0.02
        interval = max(1, math.ceil(stats_ms / (budget_frac * step_ms)))
        overhead_pct = 100.0 * stats_ms / (interval * step_ms)
        numerics_block = {
            "tensors": summary["tensors"],
            "finite": summary["finite"],
            "amax_max": round(summary["amax_max"], 6),
            "stats_pass_ms": stats_ms,
            "step_ms": round(step_ms, 3),
            "interval": interval,
            "overhead_pct": round(overhead_pct, 4),
            "budget_pct": budget_frac * 100,
        }
        extras["numerics"] = numerics_block
        reg.gauge("numerics/stats_pass_ms",
                  source="bench/fused_adam").set(stats_ms)
        reg.gauge("numerics/stats_interval",
                  source="bench/fused_adam").set(interval)
        reg.gauge("numerics/overhead_pct",
                  source="bench/fused_adam").set(round(overhead_pct, 4))
    except Exception as e:  # telemetry must not cost the headline
        extras["numerics_error"] = repr(e)[:120]

    # memory snapshot overhead (ISSUE 15): one warm live-bytes walk
    # over the bench's buffers, then — exactly like the numerics pass —
    # the decimation interval is CHOSEN so the amortized cost stays
    # under 2% of the fused step time. The memory/* gauge family lands
    # in BENCH_METRICS.jsonl and the JSON line carries the memory
    # object (live bytes, watermark, top buffers, derived cadence).
    memory_block = None
    try:
        import math

        mon = obs.MemoryMonitor("bench/fused_adam", every=1,
                                registry=reg, top_k=3)
        mon.observe(0)          # cold: first walk
        snap = mon.observe(0)   # warm: the steady-state cost
        snap_ms = snap["snapshot_ms"]
        step_ms = fused_t * 1e3
        budget_frac = 0.02
        interval = max(1, math.ceil(snap_ms / (budget_frac * step_ms)))
        overhead_pct = 100.0 * snap_ms / (interval * step_ms)
        memory_block = {
            "live_bytes": snap["live_bytes"],
            "live_buffers": snap["live_buffers"],
            "watermark_bytes": snap["watermark_bytes"],
            "top": snap["top"],
            "memory_stats": snap.get("memory_stats"),
            "snapshot_ms": snap_ms,
            "step_ms": round(step_ms, 3),
            "interval": interval,
            "overhead_pct": round(overhead_pct, 4),
            "budget_pct": budget_frac * 100,
        }
        extras["memory"] = memory_block
        reg.gauge("memory/snapshot_ms",
                  source="bench/fused_adam").set(snap_ms)
        reg.gauge("memory/snapshot_interval",
                  source="bench/fused_adam").set(interval)
        reg.gauge("memory/overhead_pct",
                  source="bench/fused_adam").set(round(overhead_pct, 4))
    except Exception as e:  # telemetry must not cost the headline
        extras["memory_error"] = repr(e)[:120]
    obs.StepReporter("fused_adam", registry=reg).step(
        fused_t, choice=choice, numerics=numerics_block,
        memory=memory_block, **phase_fields)

    # eager analog of the reference's baseline (unfused torch.optim.Adam:
    # one kernel per OP per tensor): op-by-op jax dispatch, no jit
    mu = {k: jnp.zeros_like(p) for k, p in params.items()}
    nu = {k: jnp.zeros_like(p) for k, p in params.items()}
    kw = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01,
              adam_w_mode=True, step=1.0, bias_correction=True)

    def eager_step():
        out = {}
        with jax.disable_jit():
            for k, p in params.items():
                d, m, v = adam_step(grads[k], p, mu[k], nu[k], **kw)
                out[k] = (p + d, m, v)
        return out

    eager_t = time_fn(eager_step, iters=eager_iters, warmup=1,
                      max_time_s=60.0)
    print(f"eager (op-by-op): {eager_t * 1e3:.3f} ms/step", file=sys.stderr)

    # the eager bench's moments (2.8 GB at TPU sizing) are dead from here
    # on — drop them before the per-tensor states allocate their own, or
    # the two together tip a 16 GB chip over
    del eager_step, mu, nu
    gc.collect()

    # secondary, stricter baseline: one jitted dispatch per tensor (each
    # tensor's op chain fused, launches not amortized)
    per_tensor_tx = fused_adam(lr=1e-3, weight_decay=0.01)
    single_states = {k: per_tensor_tx.init({"x": v})
                     for k, v in params.items()}

    @jax.jit
    def one_tensor(g, s, p):
        u, s = per_tensor_tx.update({"x": g}, s, {"x": p})
        return p + u["x"], s

    def per_tensor_step():
        return {k: one_tensor(grads[k], single_states[k], p)
                for k, p in params.items()}

    pt_t = time_fn(per_tensor_step, iters=eager_iters, warmup=1,
                   max_time_s=60.0)
    print(f"per-tensor-jit: {pt_t * 1e3:.3f} ms/step", file=sys.stderr)
    extras["eager_step_ms"] = round(eager_t * 1e3, 3)
    extras["per_tensor_jit_step_ms"] = round(pt_t * 1e3, 3)
    extras["speedup_vs_per_tensor_jit"] = round(pt_t / fused_t, 2)
    return eager_t / fused_t, fused_t


def _is_oom(e) -> bool:
    """True only for genuine resource exhaustion — the one failure a
    cheaper ladder rung can dodge. Everything else (shape bugs, Mosaic
    lowering/runtime bugs, TypeErrors) must fail fast instead of walking
    the ladder and landing a smaller-batch number that hides the bug."""
    s = repr(e)
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s or "OOM" in s)


def bench_llama(extras):
    """Single-chip Llama train step (fwd+bwd+FusedAdam), ms/step + MFU.

    Fallback ladder (VERDICT r2 weak #4): the no-remat full-batch config is
    fastest when activations fit HBM, but HBM size varies by device
    generation — on OOM, step down to remat and then smaller batches so an
    MFU number ALWAYS lands instead of silently vanishing.
    """
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import llama
    from apex_tpu.optimizers import fused_adam

    cfg = llama.flagship_0p9b()
    S = cfg.max_seq_len

    def attempt(remat, B, vocab_chunks=None):
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab_size)
        targets = jnp.roll(tokens, -1, axis=-1)
        tx = fused_adam(lr=1e-4)
        opt_state = tx.init(params)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(llama.loss_fn)(
                params, batch, cfg, tp_axis=None, cp_axis=None, remat=remat,
                vocab_chunks=vocab_chunks)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(jnp.add, params, updates)
            return params, opt_state, loss

        batch = (tokens, targets)
        return (time_train_step(train_step, (params, opt_state), (batch,)),
                n_params, B)

    from apex_tpu.ops import pallas_config

    # top rung: chunked lm-head CE (the fp32 [B·S, 32k] logits never
    # materialize) buys room for batch 8 without remat; then "dots"
    # (keep matmul outputs, recompute VPU chains) between no-remat and
    # full remat — docs/kernel_cost_study.md method note
    def record_failure(e, remat, B, chunks, tag=""):
        # record every rung's failure so a fully failed ladder still
        # carries its causes into the JSON. Only resource exhaustion
        # moves down a rung; anything else (shape bug, TypeError, a
        # kernel Mosaic refuses) must FAIL FAST — a smaller batch
        # landing a number would hide the bug
        extras.setdefault("llama_ladder_errors", []).append(
            f"{tag}remat={remat},B={B},chunks={chunks}: {repr(e)[:120]}")
        print(f"llama {tag}remat={remat} B={B} chunks={chunks} failed: "
              f"{repr(e)[:200]}", file=sys.stderr)
        if not _is_oom(e):
            raise e
        gc.collect()
        jax.clear_caches()

    def timed_config(remat, B, chunks):
        """(best_t, n_params, B, race) — race the kernel paths on TPU:
        Pallas flash attention (auto) vs the jnp/XLA fallback; both are
        first-class paths, report both, headline the faster (a kernel
        that loses to XLA must not tax the flagship number). Off-TPU the
        'auto' mode already IS the fallback, so there is no race."""
        t, n_params, B_used = attempt(remat, B, chunks)
        race = {}
        if jax.default_backend() == "tpu":
            race["pallas_ms"] = round(t * 1e3, 2)
            with pallas_config.force("off"):
                xla_t, _, _ = attempt(remat, B, chunks)
            race["xla_ms"] = round(xla_t * 1e3, 2)
            race["fastest"] = "xla" if xla_t < t else "pallas"
            t = min(t, xla_t)
        return t, n_params, B_used, race

    def publish(remat, B, chunks, race):
        extras["llama_config"] = (
            f"remat={remat} batch={B} vocab_chunks={chunks}")
        if "pallas_ms" in race:
            extras["llama_step_ms_pallas"] = race["pallas_ms"]
        if "xla_ms" in race:
            extras["llama_step_ms_xla"] = race["xla_ms"]
        if "fastest" in race:
            extras["llama_fastest_path"] = race["fastest"]

    # baseline rungs first, the bigger config as an UPGRADE afterwards:
    # land the known-good number before spending minutes compiling one
    # that may not fit
    ladder = [(False, 4, None), ("dots", 4, None),
              (True, 4, None), (True, 2, None), (True, 1, None)]
    upgrades = [(False, 8, 8)]
    step_t = None
    for remat, B, chunks in ladder:
        try:
            step_t, n_params, B_used, race = timed_config(remat, B, chunks)
            publish(remat, B, chunks, race)
            break
        except Exception as e:  # noqa: BLE001
            record_failure(e, remat, B, chunks)

    if step_t is None:
        raise RuntimeError(
            "all llama ladder configs failed: "
            + "; ".join(extras.get("llama_ladder_errors", []))[:400])

    # upgrade attempts: a bigger batch (chunked CE keeps the logits out
    # of HBM) wins on tokens/step when it compiles and runs; a resource
    # failure costs nothing (the baseline is banked), a genuine bug still
    # fails fast via record_failure
    for remat, B, chunks in upgrades:
        if B_used >= B:
            continue
        try:
            up_t, _, up_B, up_race = timed_config(remat, B, chunks)
            if up_B / up_t > B_used / step_t:
                step_t, B_used = up_t, up_B
                publish(remat, B, chunks, up_race)
                extras["llama_upgrade"] = "took bigger-batch config"
        except Exception as e:  # noqa: BLE001
            record_failure(e, remat, B, chunks, tag="upgrade ")

    # throughput/MFU derivation via StepReporter: the PaLM-appendix
    # accounting and the MFU>1 sanity trap live in
    # apex_tpu.observability.step_report now; the extras keys keep their
    # names for the driver's JSON-line contract
    from apex_tpu import observability as obs

    flops = obs.transformer_step_flops(
        n_params, cfg.num_layers, cfg.hidden_size, S, B_used)
    kind = jax.devices()[0].device_kind
    rec = obs.StepReporter(
        "llama_0p9b", tokens_per_step=B_used * S,
        flops_per_step=flops).step(step_t)
    extras["llama_0p9b_step_ms"] = round(step_t * 1e3, 2)
    extras["llama_tokens_per_sec"] = round(rec["tokens_per_sec"])
    extras["llama_tflops_per_sec"] = round(rec["tflops_per_sec"], 1)
    if rec["mfu"] is not None:
        extras["llama_mfu"] = round(rec["mfu"], 3)
        if "mfu_suspect" in rec:
            extras["llama_mfu_suspect"] = rec["mfu_suspect"]
    extras["device_kind"] = kind
    print(f"llama: {step_t*1e3:.1f} ms/step  "
          f"{flops/step_t/1e12:.1f} TF/s on {kind}", file=sys.stderr)
    _plan_calibration(extras, cfg, B_used, step_t, kind)


def _plan_calibration(extras, cfg, B_used, step_t, kind):
    """Auto-shard planner hook (ISSUE 8): the JSON line carries the
    chosen plan for this machine's device count at the measured model
    shape, plus the modeled-vs-measured single-device step-time ratio —
    the cost model's drift signal, tracked per run in the metrics JSONL
    (``analysis/plan_time_ratio``)."""
    import jax

    from apex_tpu import observability as obs

    model_kw = dict(
        layers=cfg.num_layers, hidden=cfg.hidden_size,
        heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
        intermediate=cfg.intermediate_size, vocab=cfg.vocab_size,
        seq=cfg.max_seq_len, batch=B_used)
    try:
        from apex_tpu.analysis import planner

        chosen = planner.plan(
            model="llama", devices=jax.device_count(), device_kind=kind,
            registry=obs.get_registry(), **model_kw)
        extras["plan"] = {
            "candidate": chosen.chosen_key, "mesh": chosen.mesh,
            "layout": chosen.layout,
            "predicted_step_ms": chosen.predicted["step_ms"],
            "comms_bytes": chosen.predicted["comms_bytes"],
            "peak_hbm_bytes": chosen.predicted["peak_hbm_bytes"]}
    except Exception as e:  # the planner must not cost the JSON line
        extras["plan_error"] = repr(e)[:160]
    try:
        from apex_tpu.analysis import planner

        # calibration is about the cost model's TIME, not feasibility:
        # the measured config already ran here, so bypass the HBM gate
        # and price the unsharded single-device candidate it used
        single = planner.plan(
            model="llama", devices=1, device_kind=kind, registry=False,
            verify=False, hbm_budget_bytes=1 << 62, **model_kw)
        predicted_ms = single.predicted["step_ms"]
        ratio = predicted_ms / (step_t * 1e3) if step_t > 0 else None
        extras["llama_plan_predicted_ms"] = round(predicted_ms, 3)
        if ratio is not None:
            extras["llama_plan_time_ratio"] = round(ratio, 4)
            reg = obs.get_registry()
            reg.gauge("analysis/plan_time_ratio", model="llama").set(
                round(ratio, 4))
            reg.event("plan_calibration", model="llama",
                      predicted_ms=round(predicted_ms, 3),
                      measured_ms=round(step_t * 1e3, 3),
                      ratio=round(ratio, 4))
        print(f"llama plan calibration: modeled "
              f"{predicted_ms:.2f} ms vs measured {step_t*1e3:.2f} ms "
              f"(ratio {ratio:.3f})" if ratio is not None else
              "llama plan calibration: no measured step",
              file=sys.stderr)
    except Exception as e:
        extras["plan_calibration_error"] = repr(e)[:160]


def bench_resnet(extras):
    """ResNet-50 bf16 train step (fwd+bwd+momentum SGD), images/s."""
    import jax
    import jax.numpy as jnp
    import optax
    from apex_tpu.models import resnet

    model = resnet.resnet50(sync_bn=False, axis_name=None)
    B = 64
    x = jnp.ones((B, 224, 224, 3), jnp.bfloat16)
    labels = jnp.zeros((B,), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state, x, labels):
        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels).mean()
            return loss, mut["batch_stats"]

        (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), bs, opt_state, loss

    # 30 iters: a ResNet step is ~10-20 ms, so at the default 10 the
    # host's jitter would dominate the per-step error
    step_t = time_train_step(
        train_step, (params, batch_stats, opt_state), (x, labels),
        iters=30)
    from apex_tpu import observability as obs

    rec = obs.StepReporter("resnet50", tokens_per_step=B).step(step_t)
    extras["resnet50_step_ms"] = round(step_t * 1e3, 2)
    extras["resnet50_images_per_sec"] = round(rec["tokens_per_sec"])
    print(f"resnet50: {step_t*1e3:.1f} ms/step  {B/step_t:.0f} im/s",
          file=sys.stderr)


def bench_bert(extras):
    """BERT-base MLM train step with FusedLAMB + FusedLayerNorm — the
    BASELINE.json "BERT-base FusedLAMB" config (ref csrc/multi_tensor_lamb
    path). Single chip, bf16, ms/step + sequences/s."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import bert
    from apex_tpu.optimizers import fused_lamb

    cfg = bert.bert_base(dtype=jnp.bfloat16)
    B, S = 8, min(512, cfg.max_seq_len)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 4,
                                cfg.vocab_size)
    mask = jax.random.bernoulli(jax.random.PRNGKey(2), 0.15, (B, S))
    inp = jnp.where(mask, 3, tokens)
    batch = (inp, tokens, mask.astype(jnp.float32))
    tx = fused_lamb(lr=1e-3)
    opt_state = tx.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(bert.loss_fn)(
            params, batch, cfg, tp_axis=None)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state, loss

    step_t = time_train_step(train_step, (params, opt_state), (batch,))
    from apex_tpu import observability as obs

    rec = obs.StepReporter("bert_base_lamb", tokens_per_step=B).step(step_t)
    extras["bert_base_lamb_step_ms"] = round(step_t * 1e3, 2)
    extras["bert_base_seq_per_sec"] = round(rec["tokens_per_sec"], 1)
    print(f"bert-base lamb: {step_t*1e3:.1f} ms/step  "
          f"{B/step_t:.1f} seq/s", file=sys.stderr)


def bench_gpt2(extras):
    """GPT-2 345M train step (fwd+bwd+FusedAdam) through the fused
    causal-softmax attention — the BASELINE.json 'GPT-2 345M TP + fused
    softmax' config on a single chip (tp collectives no-op at tp=1,
    same code path)."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import gpt2
    from apex_tpu.optimizers import fused_adam

    cfg = gpt2.gpt2_345m()  # 1024 hidden, 24 layers, vocab 50304
    B, S = 8, 1024
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1)
    tx = fused_adam(lr=1e-4)
    opt_state = tx.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(gpt2.loss_fn)(
            params, batch, cfg, tp_axis=None, vocab_chunks=8)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state, loss

    step_t = time_train_step(train_step, (params, opt_state),
                             ((tokens, targets),))
    from apex_tpu import observability as obs

    # same PaLM accounting as bench_llama: 6N + attention's 12·L·h·S
    flops = obs.transformer_step_flops(
        n_params, cfg.num_layers, cfg.hidden_size, S, B)
    rec = obs.StepReporter(
        "gpt2_345m", tokens_per_step=B * S,
        flops_per_step=flops).step(step_t)
    extras["gpt2_345m_step_ms"] = round(step_t * 1e3, 2)
    extras["gpt2_345m_tokens_per_sec"] = round(rec["tokens_per_sec"])
    if rec["mfu"] is not None:
        extras["gpt2_345m_mfu"] = round(rec["mfu"], 3)
    print(f"gpt2-345m: {step_t*1e3:.1f} ms/step  "
          f"{B*S/step_t:.0f} tok/s", file=sys.stderr)


def _ddp_comms_suite(payload_mb: float):
    """The DDP comms numbers over the CURRENT device mesh (needs >= 2
    devices): allreduce and reduce-scatter+all-gather bandwidth, plus
    the overlapped-bucket step's overlap_efficiency — how much of the
    comms time the barrier-chained schedule hides under compute
    ((t_compute + t_sync - t_overlapped) / min parts, clamped [0,1]).
    Publishes the ddp/* gauge family and returns the result dict."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from apex_tpu import observability as obs
    from apex_tpu.parallel import (
        grad_sync_comms_bytes,
        sync_gradients,
        sync_gradients_overlapped,
    )
    from jax import shard_map

    n = jax.device_count()
    mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
    nbytes = int(payload_mb * 2**20)
    # build pre-sharded: a plain jnp.ones would materialize all n shards
    # on device 0 first (16 GiB at n=64) before the jit reshards. One
    # hoisted HOST buffer -> each shard transfers host-to-device direct.
    ones = np.ones((1, nbytes // 4), np.float32)
    x = jax.make_array_from_callback(
        (n, nbytes // 4), NamedSharding(mesh, P("data")),
        lambda idx: ones)

    def allreduce(x):
        return sync_gradients({"g": x}, axis_name="data")["g"]

    def scatter_gather(x):
        # the ZeRO-1 comms layout: reduce to this rank's shard, gather
        # the (here: unchanged) shard back
        shard = jax.lax.psum_scatter(x.reshape(-1), "data",
                                     scatter_dimension=0, tiled=True)
        return jax.lax.all_gather(shard, "data", tiled=True)

    out = {"devices": n, "payload_mb": payload_mb}
    fn = jax.jit(shard_map(allreduce, mesh=mesh, in_specs=(P("data"),),
                           out_specs=P("data")))
    t = time_fn(fn, x, iters=10, warmup=2)
    bw = 2 * (n - 1) / n * nbytes / t  # ring allreduce bytes/device
    out["allreduce_ms"] = round(t * 1e3, 3)
    out["allreduce_algo_gbps"] = round(bw / 1e9, 2)

    fn_rs = jax.jit(shard_map(scatter_gather, mesh=mesh,
                              in_specs=(P("data"),),
                              out_specs=P("data"), check_vma=False))
    t_rs = time_fn(fn_rs, x, iters=10, warmup=2)
    out["reduce_scatter_gather_ms"] = round(t_rs * 1e3, 3)
    out["reduce_scatter_gather_algo_gbps"] = round(
        2 * (n - 1) / n * nbytes / t_rs / 1e9, 2)

    # overlapped-bucket step: a backward-ish compute chain whose grads
    # sync through the barrier-chained bucket schedule
    d = max(128, int(round((nbytes / 16 / 4) ** 0.5)) // 128 * 128)
    w = jnp.ones((d, d), jnp.float32)
    xb = jax.make_array_from_callback(
        (n * 8, d), NamedSharding(mesh, P("data")),
        lambda idx: np.ones((8, d), np.float32))
    grad_tree = {"w": w, "b": jnp.ones((d,), jnp.float32)}

    def compute_grads(w, xb):
        h = jnp.tanh(xb @ w)
        h = jnp.tanh(h @ w.T)
        return {"w": xb.T @ h, "b": jnp.sum(h, axis=0)}

    def step_compute(w, xb):
        return compute_grads(w, xb)

    def step_sync_only(w, xb):
        return sync_gradients_overlapped(
            {"w": w, "b": jnp.sum(xb, axis=0)}, axis_name="data",
            bucket_cap_mb=max(payload_mb / 4, 0.25))

    def step_overlapped(w, xb):
        return sync_gradients_overlapped(
            compute_grads(w, xb), axis_name="data",
            bucket_cap_mb=max(payload_mb / 4, 0.25))

    times = {}
    for name, f in (("compute", step_compute),
                    ("sync", step_sync_only),
                    ("overlapped", step_overlapped)):
        jf = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P(), P("data")),
            out_specs={"w": P(), "b": P()}, check_vma=False))
        times[name] = time_fn(jf, w, xb, iters=10, warmup=2)
    hidden = times["compute"] + times["sync"] - times["overlapped"]
    denom = max(min(times["compute"], times["sync"]), 1e-9)
    overlap_eff = max(0.0, min(1.0, hidden / denom))
    out["overlap_step_ms"] = round(times["overlapped"] * 1e3, 3)
    out["overlap_efficiency"] = round(overlap_eff, 3)

    comms = {mode: grad_sync_comms_bytes(grad_tree, n, mode)
             for mode in ("allreduce", "zero1")}
    out["comms_bytes"] = comms

    reg = obs.get_registry()
    reg.gauge("ddp/overlap_efficiency").set(out["overlap_efficiency"])
    for mode, b in comms.items():
        reg.gauge("ddp/comms_bytes", mode=mode).set(b)
    reg.gauge("ddp/allreduce_algo_gbps").set(out["allreduce_algo_gbps"])
    return out


def bench_allreduce(extras):
    """DDP comms over the device mesh (SURVEY §6 row 3: 'DDP allreduce
    bandwidth over ICI') — allreduce AND the ZeRO-1 reduce-scatter +
    all-gather layout, plus overlap_efficiency. With fewer than 2
    devices the section does not run: this process holds the chip, so a
    child could only be handed a CPU mesh, and a CPU time has no place
    under ``ddp/allreduce_algo_gbps``."""
    import jax

    n = jax.device_count()
    if n < 2:
        extras["ddp_skipped"] = f"{n} device(s): nothing to reduce across"
        print(f"ddp comms: skipped, {n} device(s)", file=sys.stderr)
        return
    ddp = _ddp_comms_suite(
        payload_mb=256.0 if jax.devices()[0].platform == "tpu" else 4.0)
    # host-platform virtual devices are a simulation; a real multi-chip
    # mesh is a measurement
    ddp["simulated"] = jax.devices()[0].platform == "cpu"
    extras["ddp"] = ddp
    print(f"ddp comms x{ddp['devices']}: allreduce "
          f"{ddp['allreduce_ms']} ms  rs+ag "
          f"{ddp['reduce_scatter_gather_ms']} ms  overlap_eff "
          f"{ddp['overlap_efficiency']}", file=sys.stderr)


def bench_serving(extras):
    """Continuous-batching inference closed loop (ISSUE 20): a seeded
    Poisson trace through apex_tpu.serving.ServingEngine on the tiny
    llama, against the one-request-at-a-time ``generate()`` baseline
    on the SAME trace. Emits the ``serving`` JSON object (p50/p99
    request latency, ttft, tokens/s, mean batch occupancy, retrace
    count) and mirrors it as ``serving/*`` gauges, so
    tools/metrics_report.py renders the family and the --compare gate
    watches p99-latency growth and tokens/s drops between runs."""
    import jax

    from apex_tpu.models import llama
    from apex_tpu.serving import (
        ServingEngine,
        make_trace,
        run_closed_loop,
        run_sequential,
    )

    cfg = llama.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    trace = make_trace(seed=0, num_requests=8, arrival_rate_hz=200.0,
                       prompt_lens=(4, 8, 12), output_lens=(4, 8, 16),
                       vocab_size=cfg.vocab_size)
    engine = ServingEngine(params, cfg, page_size=8, max_batch=4,
                           num_pages=64, max_prompt_len=16,
                           max_new_cap=16)
    report = run_closed_loop(engine, trace)
    if report["decode_retraces"]:
        # steady-state decode retracing means the static-shape contract
        # broke — surfaced loudly, never silently averaged into tok/s
        report["retrace_warning"] = (
            f"{report['decode_retraces']} decode retrace(s) — the "
            f"decode step should compile exactly once")
    seq = run_sequential(params, cfg, trace)
    report["sequential_tokens_per_s"] = seq["tokens_per_s"]
    if seq["tokens_per_s"]:
        report["speedup_vs_sequential"] = round(
            report["tokens_per_s"] / seq["tokens_per_s"], 3)
    extras["serving"] = report
    print(f"serving: {report['requests']} reqs "
          f"{report['tokens_per_s']} tok/s "
          f"(sequential {seq['tokens_per_s']} tok/s)  "
          f"p99 {report.get('latency_p99_ms', '-')} ms  "
          f"occ {report['mean_occupancy']}", file=sys.stderr)


def bench_fp8(cpu_mode, extras):
    """fp8-vs-bf16 llama matmul race (ISSUE 13): the lm_head-shaped
    gemm through ops.precision.matmul_fp8 (scale-in, E4M3 cast, fp32
    accumulate, scale-out) against the bf16 fp32-acc baseline, timed
    with the on-device scan slope. On CPU this is EMULATION via jax's
    float8 dtypes (numerics exact, perf meaningless-but-recorded: the
    JSON line + amp/fp8_* gauges are the schema a TPU run fills with
    real MXU numbers); the --compare gate
    in tools/metrics_report.py watches the speedup ratio once a TPU
    base exists."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import observability as obs
    from apex_tpu.ops import precision

    if cpu_mode:
        BS, H, V, k = 256, 256, 1024, 8
    else:
        BS, H, V, k = 8192, 4096, 32768, 8
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (BS, H), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (H, V),
                          jnp.bfloat16) * 0.05
    wt = w.T
    # delayed-style scales, computed once outside the timed region the
    # way the amp context serves them from the rings
    sa = jnp.float32(448.0) / jnp.maximum(precision.fp8_amax(a), 1e-6)
    sw = jnp.float32(448.0) / jnp.maximum(precision.fp8_amax(w), 1e-6)

    damp = jnp.bfloat16(1e-2)  # keeps the chained carry bounded

    def make_bf16():
        def step(x):
            z = precision.matmul_fp32acc(x, w)
            return precision.matmul_fp32acc(z, wt) * damp

        return step

    def make_fp8():
        def step(x):
            z = precision.matmul_fp8(x, w, sa, sw)
            return precision.matmul_fp8(z, wt, sa, sw) * damp

        return step

    chain = lambda c, step: step(c)  # noqa: E731
    bf16_t = time_scanned(make_bf16, a, chain, k=k)
    fp8_t = time_scanned(make_fp8, a, chain, k=k)
    # quantize-path cost on its own (the fused cast-and-scale pass the
    # fp8_cast tuner kernel owns the tiling of); dequantized carry +
    # sign(amax+1)==1 keep both outputs live against DCE
    def make_quant():
        def step(x):
            y, amax = precision.quantize_fp8_stats(x, sa)
            return y.astype(jnp.float32) * jnp.sign(amax + 1.0)

        return step

    quant_t = time_scanned(make_quant, a.astype(jnp.float32), chain, k=k)
    # numerics sanity rides the record: fp8 output vs the bf16 baseline
    y8 = precision.matmul_fp8(a, w, sa, sw).astype(jnp.float32)
    y16 = precision.matmul_fp32acc(a, w).astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(y8 - y16))
                / jnp.maximum(jnp.max(jnp.abs(y16)), 1e-6))

    speedup = bf16_t / fp8_t if fp8_t > 0 else 0.0
    line = {
        "matmul_fp8_ms": round(fp8_t * 1e3, 3),
        "matmul_bf16_ms": round(bf16_t * 1e3, 3),
        "speedup": round(speedup, 3),
        "quantize_ms": round(quant_t * 1e3, 3),
        "max_rel_err": round(rel, 4),
        "shape": [BS, H, V],
        "emulated": jax.default_backend() != "tpu",
    }
    extras["fp8"] = line
    reg = obs.get_registry()
    reg.gauge("amp/fp8_matmul_ms").set(line["matmul_fp8_ms"])
    reg.gauge("amp/fp8_bf16_matmul_ms").set(line["matmul_bf16_ms"])
    reg.gauge("amp/fp8_speedup").set(line["speedup"])
    reg.gauge("amp/fp8_quantize_ms").set(line["quantize_ms"])
    reg.gauge("amp/fp8_max_rel_err").set(line["max_rel_err"])
    reg.event("fp8_race", **line)
    print(f"fp8 matmul ({BS}x{H}x{V}): fp8 {line['matmul_fp8_ms']} ms "
          f"vs bf16 {line['matmul_bf16_ms']} ms -> {line['speedup']}x"
          f"{' [cpu emulation]' if line['emulated'] else ''}",
          file=sys.stderr)


def bench_kernels(extras):
    """Pallas vs XLA-fallback per-kernel timings at Llama-ish shapes
    (VERDICT r2 item 2: the kernels had never been Mosaic-compiled on
    hardware; a kernel slower than XLA is anti-perf and must lose its
    default). Times layer_norm, rms_norm, flash attention fwd and
    fwd+bwd, and causal fused softmax, each under pallas_config
    force('on') vs force('off'); also autotunes flash tile sizes over a
    small candidate set and records the winner."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.ops import pallas_config
    from apex_tpu.ops.layer_norm import layer_norm, rms_norm
    from apex_tpu.ops.flash_attention import flash_attention
    from apex_tpu.transformer.functional.fused_softmax import (
        scaled_upper_triang_masked_softmax,
    )

    kern = {}
    key = jax.random.PRNGKey(0)
    B, S, H, D = 4, 2048, 16, 128
    hidden = 4096

    def compare(name, make_fn, carry, chain=None, k=32):
        """Race compiled-Pallas vs XLA-fallback via on-device scan loops
        (time_scanned): most of these kernels are shorter than one
        host dispatch, so host-loop timing would measure the
        dispatch."""
        chain = chain or (lambda c, step: step(c))
        res = {}
        try:
            for mode, field in (("on", "pallas_ms"), ("off", "xla_ms")):
                with pallas_config.force(mode):
                    res[field] = time_scanned(make_fn, carry, chain, k=k)
            kern[name] = {
                "pallas_ms": round(res["pallas_ms"] * 1e3, 3),
                "xla_ms": round(res["xla_ms"] * 1e3, 3),
                "pallas_speedup": round(res["xla_ms"] / res["pallas_ms"],
                                        2)}
            print(f"kernel {name}: pallas {res['pallas_ms']*1e3:.3f} ms  "
                  f"xla {res['xla_ms']*1e3:.3f} ms  "
                  f"({res['xla_ms']/res['pallas_ms']:.2f}x)",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            kern[name] = {"error": repr(e)[:200]}
            print(f"kernel {name} FAILED: {repr(e)[:200]}", file=sys.stderr)

    # --- layer norm / rms norm (fwd, and fwd+bwd through custom_vjp)
    x = jax.random.normal(key, (B * S, hidden), jnp.bfloat16)
    w = jnp.ones((hidden,), jnp.float32)
    bb = jnp.zeros((hidden,), jnp.float32)

    compare("layer_norm_fwd", lambda: lambda x: layer_norm(
        x, w, bb, (hidden,)), x)
    compare("layer_norm_fwd_bwd", lambda: jax.grad(
        lambda x: jnp.sum(layer_norm(x, w, bb, (hidden,))
                          .astype(jnp.float32))), x)
    compare("rms_norm_fwd", lambda: lambda x: rms_norm(
        x, w, (hidden,)), x)
    compare("rms_norm_fwd_bwd", lambda: jax.grad(
        lambda x: jnp.sum(rms_norm(x, w, (hidden,))
                          .astype(jnp.float32))), x)

    # --- flash attention (causal self-attention at llama shapes)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, H, D), jnp.bfloat16)

    # carry (q,k,v); feed the output back as q so the scan isn't DCE'd
    flash_chain = lambda c, step: (step(*c), c[1], c[2])  # noqa: E731

    compare("flash_fwd", lambda: lambda q, k, v: flash_attention(
        q, k, v, causal=True), (q, k, v), flash_chain, k=8)

    def flash_loss():
        return jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32)),
            argnums=(0, 1, 2))

    # grads (dq,dk,dv) have q/k/v's exact structure: chain them straight
    compare("flash_fwd_bwd", flash_loss, (q, k, v),
            lambda c, step: step(*c), k=8)

    # --- causal fused softmax (GPT-2 345M attention shape)
    xs = jax.random.normal(key, (B * H, 1024, 1024), jnp.bfloat16)
    compare("causal_softmax", lambda: lambda x:
            scaled_upper_triang_masked_softmax(x, None, 1.0), xs)

    # --- flat-buffer fused adam: Pallas kernel vs the XLA-fused chain
    # (the multi_tensor_adam.cu race on the packed ~350M-element buffer).
    # use_kernel=None defers to the pallas gate, so compare()'s
    # force('on'/'off') toggles the path; trees ride as scan CARRY
    # (a closure would bake gigabytes in as constants). The carry applies
    # each step's updates so the state stays numerically steady.
    from apex_tpu.optimizers import fused_adam as _fa

    fa_params = make_params(jax.random.PRNGKey(2))
    fa_grads = jax.tree_util.tree_map(
        lambda p: jnp.full_like(p, 1e-3), fa_params)
    fa_tx = _fa(lr=1e-3, weight_decay=0.01, flat=True)
    fa_state = fa_tx.init(fa_params)

    def adam_chain(c, step):
        g, s, p = c
        updates, s2 = step(g, s, p)
        p2 = jax.tree_util.tree_map(jnp.add, p, updates)
        return g, s2, p2

    compare("flat_adam", lambda: lambda g, s, p: fa_tx.update(g, s, p),
            (fa_grads, fa_state, fa_params), adam_chain, k=8)

    # --- tile-sweep autotune (ISSUE 6): the tuning subsystem races the
    # full VMEM-bounded search space per kernel and persists winners +
    # dispatch verdicts in the per-device tuning cache — the evidence
    # artifact that flips _KERNEL_AUTO (tools/tune.sh sweeps ALL
    # registered kernels; the bench covers the ones it just raced).
    # Each kernel's sweep is gated on ITS OWN compile/race status: a
    # Mosaic-rejected flash kernel must not cost flat_adam (the headline
    # inversion kernel) its tune — they are independent kernels.
    from apex_tpu import tuning as tuning_mod

    tunable = {
        "flash_attention_fwd": kern.get("flash_fwd_bwd", {"error": 1}),
        "flash_attention_bwd": kern.get("flash_fwd_bwd", {"error": 1}),
        "flat_adam": kern.get("flat_adam", {"error": 1}),
    }
    for kname in ("flash_attention_fwd", "flash_attention_bwd",
                  "flat_adam"):
        if "error" in tunable[kname]:
            kern[f"tuned_{kname}"] = {
                "skipped": "base race failed; see its error"}
            continue
        try:
            r = tuning_mod.tune_kernel(kname)
            kern[f"tuned_{kname}"] = {
                "params": r["entry"]["params"],
                "pallas_ms": r["entry"]["pallas_ms"],
                "xla_ms": r["entry"]["xla_ms"],
                "use_pallas": r["entry"]["use_pallas"],
                "source": r["entry"]["source"],
                "bucket": r["bucket"]}
        except Exception as e:  # noqa: BLE001
            kern[f"tuned_{kname}"] = {"error": repr(e)[:200]}
            print(f"tune {kname} FAILED: {repr(e)[:200]}",
                  file=sys.stderr)
    pallas_config.refresh_tuning()  # new entries consult on next trace

    # --- the inversion gate (ISSUE 6 / ROADMAP 3): on TPU, the TUNED
    # flat path must not lose to the tree path. Both run in 'auto' mode
    # so flat takes whatever the tuned cache verdict dispatches; a loss
    # is reported loudly with the losing tile and its race numbers (the
    # JSON-line contract outlives a failed assert, so this records
    # rather than raises — CI reads flat_adam_vs_tree.flat_wins).
    if jax.default_backend() == "tpu":
        try:
            tree_tx = _fa(lr=1e-3, weight_decay=0.01, flat=False)
            tree_state = tree_tx.init(fa_params)
            tree_t = time_scanned(
                lambda: lambda g, s, p: tree_tx.update(g, s, p),
                (fa_grads, tree_state, fa_params), adam_chain, k=8)
            flat_t = time_scanned(
                lambda: lambda g, s, p: fa_tx.update(g, s, p),
                (fa_grads, fa_state, fa_params), adam_chain, k=8)
            tuned = kern.get("tuned_flat_adam", {})
            race = {
                "flat_ms": round(flat_t * 1e3, 3),
                "tree_ms": round(tree_t * 1e3, 3),
                "flat_wins": bool(flat_t <= tree_t),
                "tile": tuned.get("params"),
                "tile_race": {k2: tuned.get(k2) for k2 in
                              ("pallas_ms", "xla_ms", "use_pallas")},
            }
            extras["flat_adam_vs_tree"] = race
            if flat_t <= tree_t:
                print(f"flat-adam >= tree ASSERT OK: flat "
                      f"{flat_t*1e3:.3f} ms <= tree {tree_t*1e3:.3f} ms",
                      file=sys.stderr)
            else:
                print(f"flat-adam >= tree ASSERT FAILED: flat "
                      f"{flat_t*1e3:.3f} ms > tree {tree_t*1e3:.3f} ms "
                      f"with tile {race['tile']} "
                      f"(tile race: {race['tile_race']}) — the "
                      f"inversion survives this sweep; see "
                      f"docs/tuning.md", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            extras["flat_adam_vs_tree"] = {"error": repr(e)[:200]}
            print(f"flat-vs-tree race FAILED: {repr(e)[:200]}",
                  file=sys.stderr)

    extras["kernels"] = kern


def worker():
    t_worker = time.perf_counter()
    cpu_mode = os.environ.get("BENCH_FORCE_CPU") == "1"

    t_init = time.perf_counter()
    import jax
    import jax.numpy as jnp

    from apex_tpu.runtime.compile_cache import enable_compile_cache

    if cpu_mode:
        jax.config.update("jax_platforms", "cpu")
    cache_dir = enable_compile_cache()
    platform = jax.devices()[0].platform
    if not cpu_mode and platform != "tpu":
        # no fallback: a number taken on another backend is not this
        # benchmark's number
        sys.exit(f"bench.py: no TPU found — jax selected the {platform!r} "
                 f"backend ({jax.devices()[0].device_kind}); nothing "
                 f"measured")
    # warm the backend with a trivial compile before starting any clock
    _sync(jnp.ones((8, 8)) + 1)
    init_s = time.perf_counter() - t_init
    print(f"backend init + warm-up took {init_s:.1f}s", file=sys.stderr)
    print(f"platform: {platform} x{jax.device_count()} "
          f"({jax.devices()[0].device_kind}); compile cache {cache_dir}",
          file=sys.stderr)

    # runtime telemetry (ISSUE 2): every bench reports through the
    # process registry; compile/retrace counts come from the
    # jax.monitoring listener; the whole run dumps to a metrics JSONL
    # (summarize: python -m apex_tpu.observability report <path>)
    from apex_tpu import observability as obs

    listener = obs.install_recompile_listener()
    reg = obs.get_registry()
    # memory tier (ISSUE 15): capture every jitted-fn compile's XLA
    # memory_analysis off the listener — the per-executable static
    # memory view rides the metrics JSONL + memrec artifacts
    obs.install_compiled_capture(reg)
    reg.event("bench_start", platform=platform,
              device_count=jax.device_count(),
              device_kind=jax.devices()[0].device_kind,
              backend_init_s=round(init_s, 1))

    extras = {"platform": platform,
              "device_kind": jax.devices()[0].device_kind,
              "device_count": jax.device_count(),
              "backend_init_s": round(init_s, 1)}
    speedup, fused_ms = bench_fused_adam(cpu_mode, extras)
    extras["fused_adam_step_ms"] = round(fused_ms * 1e3, 3)

    # The analysis verdicts for this run (trace-only, any backend): each
    # lands as a counter family in the metrics JSONL and a count in the
    # JSON line, so a perf number ships with its lint status. An engine
    # that raises ends the run — a verdict that failed to compute is not
    # a clean verdict.
    from apex_tpu import analysis

    pfindings, perrors = analysis.run_precision_findings(registry=reg)
    extras["precision_findings"] = len(pfindings)
    if perrors:
        extras["precision_target_errors"] = dict(sorted(perrors.items()))

    # sharding-flow verdict + comms/HBM estimates (ISSUE 4)
    sfindings, serrors, sstats = analysis.run_sharding_findings(
        registry=reg)
    extras["sharding_findings"] = len(sfindings)
    extras["sharding_targets"] = {
        name: {"comms_bytes": int(s.get("comms_bytes", 0)),
               "peak_hbm_bytes": int(s.get("peak_hbm_bytes", 0))}
        for name, s in sorted(sstats.items())}
    if serrors:
        extras["sharding_target_errors"] = dict(sorted(serrors.items()))

    # measured-vs-modeled HBM calibration (ISSUE 15): re-compile the
    # calibration targets and ratio XLA's memory_analysis total against
    # the estimator's peak — the memory/hbm_calibration_ratio{target=}
    # gauges land in the metrics JSONL, where the --compare gate turns
    # cost-model drift into a failing diff
    cal = obs.calibrate_targets(registry=reg)
    extras["memory_calibration"] = {
        name: (row["ratio"] if "ratio" in row
               else f"skipped: {row['error'][:80]}")
        for name, row in sorted(cal.items())}

    # rank-consistency verdict (ISSUE 14)
    spfindings, sperrors, spstats = analysis.run_spmd_findings(registry=reg)
    extras["spmd_findings"] = len(spfindings)
    extras["spmd_targets"] = {
        name: {"collectives": int(s.get("collectives", 0)),
               "host_effects": int(s.get("host_effects", 0))}
        for name, s in sorted(spstats.items())}
    if sperrors:
        extras["spmd_target_errors"] = dict(sorted(sperrors.items()))

    # host-concurrency verdict (ISSUE 16)
    extras["concurrency_findings"] = len(
        analysis.run_concurrency_findings(registry=reg))

    # checkpoint/state-flow verdict (ISSUE 18): every check id explicit,
    # even at 0, so the report's binary --compare gate can tell "clean"
    # from "never ran"
    stfindings, sterrors, ststats = analysis.run_state_findings(
        registry=reg)
    extras["state_findings"] = len(stfindings)
    extras["state_targets"] = {
        name: {"carried": int(s.get("carried", 0)),
               "saved_leaves": int(s.get("saved_leaves", 0))}
        for name, s in sorted(ststats.items())}
    if sterrors:
        extras["state_target_errors"] = dict(sorted(sterrors.items()))

    # memory-liveness verdict (ISSUE 19)
    mfindings, merrors, mstats = analysis.run_memory_findings(registry=reg)
    extras["memory_findings"] = len(mfindings)
    extras["memory_targets"] = {
        name: {"peak_hbm_bytes": int(s.get("peak_hbm_bytes", 0)),
               "steady_bytes": int(s.get("steady_bytes", 0))}
        for name, s in sorted(mstats.items())}
    if merrors:
        extras["memory_target_errors"] = dict(sorted(merrors.items()))

    # fp8-vs-bf16 matmul race (ISSUE 13): the O4 tier's perf evidence
    # (float8 emulation in CPU mode)
    bench_fp8(cpu_mode, extras)

    # chaos mode (ISSUE 5): APEX_TPU_FAULT_PLAN=<spec> (e.g.
    # "seed=1,preempt@7,ckpt_torn@4,step_exc~0.05") runs the bench step
    # loop under the fault plan — a tiny deterministic train loop driven
    # through ResilientTrainLoop with scheduler-style restarts — so the
    # resilience/{retries,preemptions,rollbacks,resumes} counter family
    # lands in the metrics JSONL next to the perf numbers
    # (tools/metrics_report.py renders it as the resilience table)
    fault_spec = os.environ.get("APEX_TPU_FAULT_PLAN")
    if fault_spec:
        import tempfile

        from apex_tpu.resilience import chaos_probe

        with tempfile.TemporaryDirectory() as chaos_dir:
            extras["resilience"] = chaos_probe(
                fault_spec, chaos_dir, registry=reg)

    if cpu_mode:
        # CPU-sized by design (a few virtual devices, the tiny llama),
        # so both run before the (single) emit
        bench_allreduce(extras)
        bench_serving(extras)

    def finalize_metrics():
        """Fold recompile counts into extras and (re)write the metrics
        JSONL — called before EVERY emit so a run killed at its time
        limit still leaves a readable dump on disk."""
        # active tuning-cache entries ride the JSON line (ISSUE 6): the
        # perf numbers always ship with the tiles + verdicts that
        # dispatched them; hit/miss + race counters are already in the
        # registry via apex_tpu.tuning
        from apex_tpu.observability import goodput as goodput_mod
        from apex_tpu.tuning import cache as tuning_cache

        extras["tuning"] = {
            "cache": tuning_cache.cache_path(),
            "device_kind": tuning_cache.current_device_kind(),
            "entries": tuning_cache.entries_for(),
        }
        snap = listener.snapshot()
        retraces = sum(snap["retraces_by_fn"].values())
        extras["recompiles"] = snap["backend_compiles"]
        extras["retraces"] = retraces
        reg.gauge("jax/retraces_total").set(retraces)
        budget = os.environ.get("APEX_TPU_RETRACE_BUDGET")
        if budget and retraces > int(budget):
            # recorded, not raised: consumers and CI gates read this
            # field / event next to the numbers it qualifies
            extras["retrace_budget_exceeded"] = (
                f"{retraces} retraces > budget {int(budget)}")
            reg.event("retrace_budget_exceeded", retraces=retraces,
                      budget=int(budget), by_fn=snap["retraces_by_fn"])
        # goodput accounting (ISSUE 17): ledger this worker's own event
        # stream and publish the goodput/* gauge family BEFORE the dump
        # so it rides the metrics JSONL into metrics_report's compare
        # gate; the JSON line carries the summary object
        ledger = goodput_mod.ledger_from_records(reg.to_records())
        acc = goodput_mod.account(
            ledger, wall_s=time.perf_counter() - t_worker)
        goodput_mod.publish(acc, reg)
        extras["goodput"] = {
            "ratio": acc["goodput_ratio"],
            "fleet_ratio": acc["fleet_goodput"],
            "wall_s": acc["wall_s"],
            "productive_s": acc["productive_s"],
            "badput_top": acc["badput_top"],
            "steps": acc["steps"],
        }
        reg.dump(_metrics_path())
        # dump() rank-suffixes the shared path for fleet members
        # (ISSUE 12) — report the name that actually landed
        extras["metrics_jsonl"] = os.path.basename(
            obs.MetricRegistry.dump_path(_metrics_path()))
        # span-ring Perfetto export (ISSUE 7): the host-side span
        # timeline of everything this worker traced and dispatched,
        # loadable at ui.perfetto.dev (APEX_TPU_PERFETTO overrides the
        # path) — rewritten before every emit like the metrics JSONL
        perfetto = os.environ.get(
            "APEX_TPU_PERFETTO",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_SPANS.perfetto.json"))
        n_spans = obs.get_tracer().write_chrome_trace(perfetto)
        extras["profiling"] = {
            "perfetto": os.path.basename(perfetto), "spans": n_spans}

    def emit():
        finalize_metrics()
        print(json.dumps({
            "metric": "fused_adam_speedup_vs_eager",
            "value": round(speedup, 2),
            "unit": "x",
            "vs_baseline": round(speedup / TARGET_SPEEDUP, 2),
            **extras,
        }), flush=True)

    # headline lands NOW: a run killed at its time limit in a later
    # section still leaves this line behind
    emit()
    if not cpu_mode:
        # stop STARTING sections once the run is this old, so the one in
        # flight can finish inside an hour-long chip call (kernel-race
        # compiles are ~30s each); a skipped section is named in the JSON
        budget_s = 2300
        # BENCH_ONLY=kernels,bert runs a subset of the sections
        only = {s.strip() for s in os.environ.get("BENCH_ONLY", "").split(",")
                if s.strip()}
        secondary = (bench_llama, bench_resnet, bench_kernels, bench_bert,
                     bench_gpt2, bench_allreduce, bench_serving)
        if only:
            names = {fn.__name__.removeprefix("bench_") for fn in secondary}
            if only - names:
                # a typo must not silently spend chip time on nothing
                raise ValueError(
                    f"BENCH_ONLY entries not recognized: "
                    f"{sorted(only - names)} (valid: {sorted(names)})")
            secondary = tuple(
                fn for fn in secondary
                if fn.__name__.removeprefix("bench_") in only)
        for fn in secondary:
            spent = time.perf_counter() - t_worker
            if spent > budget_s:
                extras[fn.__name__ + "_skipped"] = (
                    f"run at {spent:.0f}s of {budget_s}s budget")
                print(f"skipping {fn.__name__}: {spent:.0f}s elapsed",
                      file=sys.stderr)
                continue
            try:
                fn(extras)
            finally:
                # free the bench's device memory before the next one: the
                # jit executable cache pins donated-in buffers, so without
                # this a 0.9B-param llama bench starves everything after
                # it (RESOURCE_EXHAUSTED behind llama's leftovers)
                gc.collect()
                jax.clear_caches()
                gc.collect()
        emit()


if __name__ == "__main__":
    worker()
