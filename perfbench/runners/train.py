"""Training cells: the compiled amp-O2 step, driven back to back.

The step is composed as `chip_smoke.py`'s train phase composes it:
`amp.initialize("O2")` (float32 master weights, bfloat16 model copy),
`fused_adam`, `handle.scaled_update`, `gpt2.loss_fn` with remat per layer and
the chunked tied-head cross entropy, state donated; under `dp > 1` the same
step inside `shard_map` with `sync_autodiff_gradients` and the overflow flag
reduced over `dp`. Set-up builds that one object with its state, drives it
through its first three steps from the seed (their readings decide `correct`),
and hands the same object to the window. Every step gets a fresh seeded batch,
made on the host and put on the device one step ahead of the step that uses it.
"""

import math
import statistics
import sys
import time

import numpy as np

from perfbench import traffic
from perfbench.references.common import seed_words

CHECK_STEPS = 3


def model_config(cfg):
    from apex_tpu.models import gpt2

    return gpt2.GPT2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        max_seq_len=cfg["max_seq_len"], ln_eps=cfg["ln_eps"])


class Trainer:
    """The compiled step with its state: the object the window drives."""

    def __init__(self, run, devices):
        import jax
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from apex_tpu import amp
        from apex_tpu.models import gpt2
        from apex_tpu.optimizers import fused_adam
        from apex_tpu.parallel import sync_autodiff_gradients

        cfg, recipe = run.config, run.config["train"]
        self.cfg = cfg
        self.dp = int(run.mix.get("dp", 1))
        self.seq_len = int(run.mix["seq_len"])
        self.rows = int(recipe["micro_batch"]) * self.dp
        self.lr = float(recipe["lr"])
        self.ref = run.reference()
        pcfg = model_config(cfg)
        handle = amp.initialize(opt_level=recipe["opt_level"], verbosity=0)
        tx = fused_adam(lr=self.lr)
        ddp = self.dp > 1

        def step(params, opt_state, sstate, batch):
            def scaled(p):
                loss = gpt2.loss_fn(handle.policy.cast_model(p), batch, pcfg,
                                    tp_axis=None,
                                    vocab_chunks=recipe["vocab_chunks"])
                return handle.scaler.scale_loss(loss, sstate), loss

            grads, loss = jax.grad(scaled, has_aux=True)(params)
            if ddp:
                grads = sync_autodiff_gradients(grads, axis_name="dp")
                loss = jax.lax.pmean(loss, "dp")
            updates, opt_state, sstate, _ = handle.scaled_update(
                tx, grads, opt_state, params, sstate)
            return (optax.apply_updates(params, updates), opt_state, sstate,
                    loss)

        def init_state(lo, hi):
            params = self.ref.init(lo, hi, cfg)
            return params, tx.init(params), handle.scaler.init()

        self.mesh = Mesh(np.array(devices[:self.dp]), ("dp",))
        replicated = NamedSharding(self.mesh, P())
        self.batch_sharding = NamedSharding(self.mesh, P("dp", None))
        self.replicated = replicated
        if ddp:
            shapes = jax.eval_shape(init_state, *seed_words(0))
            specs = jax.tree_util.tree_map(lambda _: P(), shapes)
            data = (P("dp", None), P("dp", None))
            step = jax.shard_map(step, mesh=self.mesh,
                                 in_specs=(*specs, data),
                                 out_specs=(*specs, P()))
        self.step = jax.jit(step, donate_argnums=(0, 1, 2))
        self.init_state = jax.jit(init_state, out_shardings=replicated)
        self.norms = jax.jit(self.ref.leaf_norms)
        self.delta_norms = jax.jit(lambda p, lo, hi: self.ref.leaf_norms(
            jax.tree_util.tree_map(lambda a, b: a - b, p,
                                   self.ref.init(lo, hi, cfg))))
        self.state = None

    def batch(self, seed, index):
        return traffic.train_batch(seed, index, self.rows, self.seq_len,
                                   self.cfg["vocab_size"])

    def put(self, batch):
        import jax

        return jax.device_put(batch, self.batch_sharding)

    def start(self, seed):
        self.state = self.init_state(*seed_words(seed))

    def call(self, batch):
        """The window's own call: one donated step; returns the loss (not
        waited for)."""
        *self.state, loss = self.step(*self.state, batch)
        return loss

    def first_steps(self, seed):
        """Drive the first CHECK_STEPS steps through `call`, and read what
        decides `correct` from the state: each loss, the first gradient's norm
        by leaf as the optimizer got it (Adam's first moment after one step is
        (1 - beta1) g), and the parameters' change by leaf after the steps."""
        from perfbench.references.gpt2 import ADAM_B1

        losses, grad_norms = [], None
        for i in range(CHECK_STEPS):
            losses.append(float(self.call(self.put(self.batch(seed, i)))))
            if i == 0:
                grad_norms = {k: float(v) / (1 - ADAM_B1) for k, v in
                              self.norms(self.state[1].mu).items()}
        delta = {k: float(v) for k, v in self.delta_norms(
            self.state[0], *seed_words(seed)).items()}
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": delta,
                "overflows": int(self.state[2].overflows)}

    def free(self):
        self.state = None


def reference_readings(run, trainer, seed, precision="float32", rows=None):
    """The reference over the same first steps, on weights and batches made
    anew from the seed; on several chips its blocks of rows are spread over
    them (plain jit, the compiler's own partitioning)."""
    import jax

    ref = trainer.ref
    params = jax.jit(lambda lo, hi: ref.init(lo, hi, trainer.cfg),
                     out_shardings=trainer.replicated)(*seed_words(seed))
    batches = [trainer.batch(seed, i) for i in range(CHECK_STEPS)]
    block = int(run.mix["check"].get("reference_block_rows", 4)) * trainer.dp
    put = (lambda b: jax.device_put(b, trainer.batch_sharding)) \
        if trainer.dp > 1 else None
    return ref.train_readings(params, batches, trainer.cfg, trainer.lr,
                              precision=precision, block_rows=block,
                              rows=rows, put=put)


def leaf_gaps(got, want, keep=None):
    """For each compared leaf, the gap between the program's norm and the
    reference's (not the norm of their difference), against the reference's
    norm of that leaf or of the median leaf, whichever is larger: some
    gradients are all but zero."""
    median = statistics.median(want.values())
    return {name: abs(got[name] - ref) / max(ref, median)
            for name, ref in want.items() if keep is None or name in keep}


def compare(got, want, limits):
    """The numbers compared, each beside its limit: the first gradient's norm
    by the worst leaf, and the parameters' change after the checked steps by
    the worst leaf and by the mean over the leaves (steady from seed to seed,
    where the worst leaf is set by whichever small leaf is noisiest: PERF.md).
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move under Adam by round-off alone, and are left out of the change.
    The losses are printed and not compared (PERF.md: no control and no fault
    separates them from a sound run)."""
    median = statistics.median(want["grad_norms"].values())
    moved = {k for k, g in want["grad_norms"].items() if g >= 1e-3 * median}
    grad = list(leaf_gaps(got["grad_norms"], want["grad_norms"]).values())
    delta = list(leaf_gaps(got["delta_norms"], want["delta_norms"],
                           moved).values())
    nan = any(v != v for v in grad + delta)     # a NaN is the worst there is
    out = {"grad_norm_gap_worst_leaf": math.nan if nan else max(grad),
           "delta_norm_gap_worst_leaf": math.nan if nan else max(delta),
           "delta_norm_gap_mean_leaf": statistics.fmean(delta)}
    out = {k: (v, limits[k]) for k, v in out.items()}
    if got.get("overflows"):
        out["scaler_overflows"] = (float(got["overflows"]), 0.0)
    return out


def loss_gaps(got, want):
    return [abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                want["losses"])]


def window(run, trainer, seed):
    """Back-to-back steps for `run.seconds`: step i is dispatched, batch
    i + 1 is made and put on the device while it runs, then step i - 1's loss
    is waited for. A step counts once its loss is there."""
    tokens_per_step = trainer.rows * trainer.seq_len
    trace_from = run.seconds - run.trace_seconds() if run.trace_on else None
    index = CHECK_STEPS
    batch = trainer.put(trainer.batch(seed, index))
    losses, done_at, pending = [], [], None
    run.open_window()
    t0 = run.counters["t_open"]

    def settle(loss):
        if loss is not None:
            losses.append(float(loss))
            done_at.append(time.monotonic() - t0)

    while True:
        if trace_from is not None and time.monotonic() - t0 >= trace_from:
            settle(pending)          # the traced window starts on an idle chip
            pending, trace_from = None, None
            run.start_trace()
        with run.span("bench:train_step"):
            loss = trainer.call(batch)
        index += 1
        with run.span("bench:make_batch"):
            batch = trainer.put(trainer.batch(seed, index))
        with run.span("bench:wait_loss"):
            settle(pending)
        pending = loss
        if time.monotonic() - t0 >= run.seconds:
            break
    settle(pending)
    run.close_window(t0 + done_at[-1])
    run.stop_trace()
    steps = len(losses)
    run.e2e["train_tokens_per_s_per_chip"] = (
        steps * tokens_per_step / run.window_s / trainer.dp)
    run.series["step_ms"] = [(b - a) * 1e3 for a, b in
                             zip([0.0] + done_at[:-1], done_at)]
    run.counters["steps"] = steps
    return losses


def run(run):
    from apex_tpu.observability import recompile

    devices = run.open_device()
    listener = recompile.install()
    trainer = Trainer(run, devices)
    trainer.start(run.seed)
    run.mark("state_made")
    got = trainer.first_steps(run.seed)
    run.mark("first_steps")
    compiles0 = listener.backend_compiles()
    losses = window(run, trainer, run.seed)
    run.counters["compiles_in_window"] = (listener.backend_compiles()
                                          - compiles0)
    run.read_memory_peak()
    trainer.free()
    want = reference_readings(run, trainer, run.seed)
    compared = compare(got, want, run.mix["check"]["limits"])
    print(f"loss gaps of the checked steps (not compared): "
          f"{loss_gaps(got, want)}", file=sys.stderr)
    failed = sum(not math.isfinite(x) for x in losses)
    return run.result(len(losses), failed, compared)


def calibrate(workload, seeds, control_seeds, seconds, emit, **run_args):
    """Readings that the limits are set from, in one process: for every seed
    the program's numbers against the reference; for the control seeds also the
    reference at fp8 put in the program's place, and the planted faults (half of
    the batch left out; on several chips, one chip's rows alone, which is what a
    missing gradient exchange computes). No window is measured."""
    from perfbench import harness

    run = harness.Run(workload, seeds[0], seconds, 0, **run_args)
    trainer = Trainer(run, run.open_device())
    no_limits = {k: math.inf for k in run.mix["check"]["limits"]}

    def numbers(got, want):
        out = {k: v for k, (v, _) in compare(got, want, no_limits).items()}
        out["loss_gaps"] = loss_gaps(got, want)
        out["grad_gap_by_leaf"] = leaf_gaps(got["grad_norms"],
                                            want["grad_norms"])
        out["delta_gap_by_leaf"] = leaf_gaps(got["delta_norms"],
                                             want["delta_norms"])
        return out

    for seed in seeds:
        t0 = time.monotonic()
        trainer.start(seed)
        got = trainer.first_steps(seed)
        trainer.free()
        want = reference_readings(run, trainer, seed)
        record = {"workload": workload, "seed": seed,
                  "program": numbers(got, want),
                  "reference_grad_norms": want["grad_norms"],
                  "losses": got["losses"], "reference_losses": want["losses"]}
        if seed in control_seeds:
            half = slice(0, trainer.rows // 2)
            record["control_fp8"] = numbers(reference_readings(
                run, trainer, seed, precision="fp8"), want)
            record["fault_half_batch"] = numbers(reference_readings(
                run, trainer, seed, rows=half), want)
            if trainer.dp > 1:
                one = slice(0, trainer.rows // trainer.dp)
                record["fault_no_exchange"] = numbers(reference_readings(
                    run, trainer, seed, rows=one), want)
        record["seconds"] = time.monotonic() - t0
        emit(record)
