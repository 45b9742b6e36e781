"""ImageNet-style ResNet trainer — TPU re-design of the reference's
flagship example (ref examples/imagenet/main_amp.py:1-543), feature for
feature: amp opt levels with loss-scale / keep-batchnorm-fp32 overrides,
DDP over the 'data' mesh axis, SyncBatchNorm, epoch loop with step-decay
+ warmup LR schedule, top-1/top-5 validation, checkpoint/save/resume
with best-accuracy tracking, and a prefetching input pipeline (the
DataLoader-workers analog, backed by the C++ host ring when built).

Data: ``--data DIR`` reads ``*.npz`` shards holding ``x`` [N,H,W,3]
float and ``y`` [N] int arrays; without it a deterministic synthetic
dataset is generated (so the example runs anywhere, ref uses fake_data
similarly). Try::

    python examples/imagenet_resnet50.py --smoke
    python examples/imagenet_resnet50.py --epochs 3 --steps-per-epoch 30
    python examples/imagenet_resnet50.py --resume auto --evaluate
    python examples/imagenet_resnet50.py --arch resnet50 --image-size 224
"""

from __future__ import annotations

import argparse
import collections
import os
import threading
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax


def parse_args():
    p = argparse.ArgumentParser(
        description="apex_tpu imagenet trainer (ref main_amp.py)")
    p.add_argument("--val-data", default="", metavar="DIR",
                   help="held-out shards for validation; without it the "
                        "val metrics are measured on the TRAINING shards "
                        "(a warning is printed)")
    p.add_argument("--data", default="", metavar="DIR",
                   help="dir of .npz shards (x,y); synthetic if empty")
    p.add_argument("--arch", "-a", default="tiny",
                   choices=["tiny", "resnet50", "resnet101"])
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--start-epoch", type=int, default=0)
    p.add_argument("--steps-per-epoch", type=int, default=20)
    p.add_argument("-b", "--batch", type=int, default=32,
                   help="global batch size")
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", "--wd", type=float, default=1e-4)
    p.add_argument("--warmup-epochs", type=float, default=1.0)
    p.add_argument("--decay-epochs", type=int, nargs="*", default=[30, 60, 80],
                   help="epochs at which lr steps down 10x (ref "
                        "adjust_learning_rate)")
    p.add_argument("--print-freq", "-p", type=int, default=10)
    p.add_argument("--workers", "-j", type=int, default=2,
                   help="prefetch worker threads (DataLoader analog)")
    p.add_argument("--resume", default="", metavar="PATH",
                   help="checkpoint dir to resume from ('auto' = "
                        "--checkpoint-dir)")
    p.add_argument("--checkpoint-dir", default="",
                   help="save checkpoints here each epoch (empty = no "
                        "saving)")
    p.add_argument("-e", "--evaluate", action="store_true",
                   help="validate only, no training")
    p.add_argument("--deterministic", action="store_true")
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--keep-batchnorm-fp32", default=None,
                   choices=[None, "True", "False"])
    p.add_argument("--loss-scale", default=None,
                   help="float or 'dynamic' (default: opt-level policy)")
    p.add_argument("--no-sync-bn", action="store_true")
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--smoke", action="store_true",
                   help="tiny 1-epoch run that asserts the loss decreased "
                        "(CI path)")
    args = p.parse_args()
    if args.smoke:
        # shrink everything NOT explicitly overridden on the CLI (a value
        # equal to the default is indistinguishable from unset, so check
        # the argv flags themselves)
        given = set(sys.argv[1:])

        def absent(*flags):
            return not (given & set(flags))

        if absent("--arch", "-a"):
            args.arch = "tiny"
        if absent("--steps-per-epoch"):
            args.steps_per_epoch = 10
        if absent("--batch", "-b"):
            args.batch = 32
        if absent("--image-size"):
            args.image_size = 32
        if absent("--epochs"):
            args.epochs = 1
    if args.loss_scale not in (None, "dynamic"):
        args.loss_scale = float(args.loss_scale)
    return args


# ------------------------------------------------------------------- data


class ShardDataset:
    """npz shards or deterministic synthetic batches; one sample row =
    [pixels..., label] so the prefetch ring carries a single buffer."""

    def __init__(self, data_dir, n_batches, batch, image_size, classes,
                 seed):
        self.batch, self.hw, self.classes = batch, image_size, classes
        self.n_batches = n_batches
        self.seed = seed
        self.row = image_size * image_size * 3 + 1
        self._cache = collections.OrderedDict()
        self._cache_lock = threading.Lock()
        self.files = []
        if data_dir:
            self.files = sorted(
                os.path.join(data_dir, f) for f in os.listdir(data_dir)
                if f.endswith(".npz"))
            if not self.files:
                raise FileNotFoundError(f"no .npz shards in {data_dir}")

    # shard access is sequential/cyclic, so a tiny LRU suffices; unbounded
    # caching would grow host memory to the whole dataset on an
    # ImageNet-scale --data dir
    _CACHE_SHARDS = 4

    def _shard(self, path):
        """Cache decompressed shards: np.load + array access per batch
        would re-decompress the whole file on the prefetch hot path.
        fill() runs on multiple prefetch worker threads — the lock keeps
        the evicting LRU consistent (and the decompress single-flight)."""
        with self._cache_lock:
            if path in self._cache:
                self._cache.move_to_end(path)
                return self._cache[path]
            f = np.load(path)
            shard = (np.asarray(f["x"]), np.asarray(f["y"]))
            self._cache[path] = shard
            while len(self._cache) > self._CACHE_SHARDS:
                self._cache.popitem(last=False)
            return shard

    def fill(self, batch_idx, out):
        """Prefetch callback: writes batch ``batch_idx`` into ``out``
        [batch, row] float32 (runs on a worker thread)."""
        if self.files:
            xs, ys = self._shard(self.files[batch_idx % len(self.files)])
            n = len(ys)
            idx = (np.arange(self.batch) + batch_idx * self.batch) % n
            x = xs[idx].astype(np.float32).reshape(self.batch, -1)
            y = ys[idx].astype(np.float32)[:, None]
        else:
            rng = np.random.default_rng(self.seed + batch_idx)
            y_int = rng.integers(0, self.classes, self.batch)
            # class-dependent means make synthetic data learnable
            x = (rng.standard_normal((self.batch, self.row - 1)) * 0.5
                 + (y_int[:, None] / self.classes - 0.5) * 2.0)
            x, y = x.astype(np.float32), y_int.astype(np.float32)[:, None]
        out[:] = np.concatenate([x, y], axis=1)

    def unpack(self, rows):
        x = rows[:, :-1].reshape(self.batch, self.hw, self.hw, 3)
        y = rows[:, -1].astype(np.int32)
        return x, y

    def loader(self, n_slots, n_workers):
        from apex_tpu.runtime.host import PrefetchLoader

        return PrefetchLoader(
            self.fill, self.n_batches, (self.batch, self.row),
            np.float32, n_slots=n_slots, n_workers=max(n_workers, 1))


# ------------------------------------------------------------------ meters


def accuracy_counts(logits, y, topk=(1, 5)):
    """Per-shard correct counts for top-k (ref main_amp.py accuracy())."""
    out = []
    for k in topk:
        k = min(k, logits.shape[-1])
        top = jax.lax.top_k(logits, k)[1]
        out.append(jnp.sum(jnp.any(top == y[:, None], axis=-1)))
    return out


def main():
    args = parse_args()
    if args.deterministic:
        np.random.seed(0)

    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from examples._common import ensure_devices

    ensure_devices(args.devices)

    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    import apex_tpu.amp as amp
    from apex_tpu.checkpoint import CheckpointManager
    from apex_tpu.models import resnet
    from apex_tpu.optimizers import fused_sgd

    n_dev = args.devices
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    assert args.batch % n_dev == 0, "global batch must divide the mesh"

    build = {"tiny": resnet.tiny, "resnet50": resnet.resnet50,
             "resnet101": resnet.resnet101}[args.arch]
    model = build(num_classes=args.classes,
                  sync_bn=not args.no_sync_bn, axis_name="data",
                  dtype=jnp.bfloat16 if args.opt_level in ("O2", "O3")
                  else jnp.float32)

    ds = ShardDataset(args.data, args.steps_per_epoch, args.batch,
                      args.image_size, args.classes, seed=100)
    # validation needs HELD-OUT shards (ref main_amp.py's separate val
    # dir); measuring on the training shards inflates top-1/top-5 and
    # corrupts best-checkpoint selection
    if args.data and not args.val_data:
        print("WARNING: no --val-data given; validation metrics are "
              "measured on the TRAINING shards and overstate accuracy",
              file=sys.stderr)
    val_ds = ShardDataset(args.val_data or args.data, 4, args.batch,
                          args.image_size, args.classes, seed=9000)

    x0 = jnp.zeros((2, args.image_size, args.image_size, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(1), x0, train=False)
    params32 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), variables["params"])
    batch_stats = variables["batch_stats"]

    # amp.initialize resolves opt level + user overrides into the dtype
    # policy and scaler (ref main_amp.py amp.initialize(model, optimizer,
    # opt_level, keep_batchnorm_fp32, loss_scale))
    _, handle = amp.initialize(
        params32, opt_level=args.opt_level,
        keep_batchnorm_fp32=args.keep_batchnorm_fp32,
        loss_scale=args.loss_scale, verbosity=0)
    policy, scaler = handle.policy, handle.scaler
    sstate = handle.scaler_state

    # warmup + step-decay schedule (ref adjust_learning_rate: linear
    # warmup over the first epochs, /10 at each decay epoch). The second
    # schedule in join_schedules sees (step - warmup_steps), so the decay
    # boundaries shift into that frame — otherwise every drop would land
    # one warmup-period late.
    spe = args.steps_per_epoch
    warmup_steps = max(int(args.warmup_epochs * spe), 1)
    decay_bounds = {int(e * spe) - warmup_steps: 0.1
                    for e in args.decay_epochs
                    if int(e * spe) > warmup_steps}
    lr_sched = optax.join_schedules(
        [optax.linear_schedule(args.lr / 10, args.lr, warmup_steps),
         optax.piecewise_constant_schedule(args.lr, decay_bounds)],
        [warmup_steps])
    tx = fused_sgd(lr=lr_sched, momentum=args.momentum,
                   weight_decay=args.weight_decay)
    opt_state = tx.init(params32)  # fp32 master state (O2 master weights)

    def train_step(master, opt_state, sstate, batch_stats, x, y):
        def loss_fn(master):
            model_params = policy.cast_model(master)
            logits, mut = model.apply(
                {"params": model_params, "batch_stats": batch_stats},
                x, train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y).mean()
            return scaler.scale_loss(loss, sstate), (loss, mut["batch_stats"])

        grads, (loss, new_stats) = jax.grad(loss_fn, has_aux=True)(master)
        # DDP allreduce: this body runs with check_vma=False, so nothing
        # is typed and autodiff sums nothing for us — EVERY grad leaf
        # arrives per-rank local; reduce them all explicitly
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, "data"), grads)
        if args.no_sync_bn:
            # non-sync BN computes per-shard running stats, but the P()
            # out_specs store ONE tree — average them at the storage
            # boundary (sync_bn already psums inside the layer, so its
            # stats are identical across ranks and skip this)
            new_stats = jax.tree_util.tree_map(
                lambda s: jax.lax.pmean(s, "data"), new_stats)
        updates, opt_state, sstate, overflow = amp.scaled_update(
            tx, scaler, grads, opt_state, master, sstate)
        master = optax.apply_updates(master, updates)
        loss = jax.lax.pmean(loss, "data")
        return master, opt_state, sstate, new_stats, loss, overflow

    def eval_step(master, batch_stats, x, y):
        logits = model.apply(
            {"params": policy.cast_model(master),
             "batch_stats": batch_stats}, x, train=False)
        c1, c5 = accuracy_counts(logits.astype(jnp.float32), y)
        return (jax.lax.psum(c1, "data"), jax.lax.psum(c5, "data"))

    stats_specs = jax.tree_util.tree_map(lambda _: P(), batch_stats)
    # check_vma=False: an untyped body whose P() out_specs are kept
    # honest by the explicit pmeans above (sync_bn already psums its
    # statistics) — and by the spmd analysis target that traces it
    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(), stats_specs, P("data"), P("data")),
        out_specs=(P(), P(), P(), stats_specs, P(), P()),
        check_vma=False,
    ))
    evalf = jax.jit(shard_map(
        eval_step, mesh=mesh,
        in_specs=(P(), stats_specs, P("data"), P("data")),
        out_specs=(P(), P()),
        check_vma=False,
    ))

    # ------------------------------------------------------ resume / ckpt
    manager = None
    if args.checkpoint_dir:
        manager = CheckpointManager(args.checkpoint_dir, max_to_keep=3)
    best_acc1 = 0.0
    start_epoch = args.start_epoch
    resume_dir = (args.checkpoint_dir if args.resume == "auto"
                  else args.resume)
    if resume_dir:
        rm = CheckpointManager(resume_dir)
        if rm.latest_step() is not None:
            template = {"params": params32, "opt_state": opt_state,
                        "sstate": sstate, "batch_stats": batch_stats,
                        "epoch": np.zeros((), np.int32),
                        "best_acc1": np.zeros((), np.float32)}
            state = rm.restore(template)
            params32, opt_state = state["params"], state["opt_state"]
            sstate, batch_stats = state["sstate"], state["batch_stats"]
            start_epoch = int(state["epoch"]) + 1
            best_acc1 = float(state["best_acc1"])
            print(f"=> resumed from '{resume_dir}' "
                  f"(epoch {int(state['epoch'])}, "
                  f"best_acc1 {best_acc1:.3f})")
        else:
            print(f"=> no checkpoint found at '{resume_dir}'")

    def validate():
        """top-1/top-5 over the val split (ref validate())."""
        n, c1, c5 = 0, 0, 0
        for rows in val_ds.loader(2, args.workers):
            x, y = val_ds.unpack(rows)
            a, b = evalf(params32, batch_stats, jnp.asarray(x),
                         jnp.asarray(y))
            c1, c5, n = c1 + int(a), c5 + int(b), n + len(y)
        print(f"val: top1 {100*c1/n:.2f}%  top5 {100*c5/n:.2f}%  ({n})")
        return 100 * c1 / n

    if args.evaluate:
        validate()
        return

    first_loss = last_loss = None
    for epoch in range(start_epoch, args.epochs):
        t0 = time.perf_counter()
        seen = 0
        # prefetching input pipeline (C++ ring when built, threads
        # otherwise) — the reference's --workers DataLoader analog
        for it, rows in enumerate(ds.loader(4, args.workers)):
            x, y = ds.unpack(rows)
            (params32, opt_state, sstate, batch_stats, loss,
             overflow) = step(params32, opt_state, sstate, batch_stats,
                              jnp.asarray(x), jnp.asarray(y))
            seen += args.batch
            if first_loss is None:
                first_loss = float(loss)
                t0 = time.perf_counter()  # exclude compile
                seen = 0
            if it % args.print_freq == 0 or it == spe - 1:
                lr_now = float(lr_sched(epoch * spe + it))
                print(f"epoch {epoch:3d} step {it:4d}  "
                      f"loss {float(loss):.4f}  lr {lr_now:.4f}  "
                      f"scale {float(sstate.loss_scale):.0f}  "
                      f"overflow {bool(overflow)}")
        dt = time.perf_counter() - t0
        if seen:
            print(f"epoch {epoch}: {seen / dt:.1f} images/s")
        last_loss = float(loss)
        acc1 = validate()
        if manager is not None:
            is_best = acc1 > best_acc1
            best_acc1 = max(acc1, best_acc1)
            manager.save(epoch, {
                "params": params32, "opt_state": opt_state,
                "sstate": sstate, "batch_stats": batch_stats,
                "epoch": np.asarray(epoch, np.int32),
                "best_acc1": np.asarray(best_acc1, np.float32)})
            print(f"=> saved epoch {epoch}"
                  + (" (new best)" if is_best else ""))

    if first_loss is not None:
        verdict = "decreased" if last_loss < first_loss else "NOT decreased"
        print(f"loss {first_loss:.4f} -> {last_loss:.4f} ({verdict})")
        # a resumed run starts near the loss floor of the tiny synthetic
        # set, so the hard decrease contract only binds from scratch
        if args.smoke and start_epoch == 0 and last_loss >= first_loss:
            raise SystemExit("smoke: loss did not decrease")


if __name__ == "__main__":
    main()
