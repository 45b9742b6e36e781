"""BERT MLM pretraining with FusedLAMB + FusedLayerNorm over a dp mesh —
the BASELINE.json "BERT-base FusedLAMB + FusedLayerNorm" config (ref
apex/optimizers/fused_lamb.py + csrc/multi_tensor_lamb.cu powering the
NVIDIA BERT recipe; the TPU analog fuses the whole LAMB step into one jit).

Data-parallel like the reference recipe: LAMB's layerwise trust ratios and
global grad-norm clip are norms over FULL parameter tensors, so the
optimizer runs on replicated params with dp-mean'd grads (sharding params
across tp would silently localize those norms — the reference's BERT runs
LAMB under DDP for the same reason).

    python examples/bert_train.py --dp 8 --steps 10
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=8)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=4, help="per-dp-rank batch")
    p.add_argument("--seq", type=int, default=32)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--mask-prob", type=float, default=0.15)
    p.add_argument("--checkpoint-dir", default="",
                   help="save train state here every --save-every steps")
    p.add_argument("--save-every", type=int, default=5)
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest step in --checkpoint-dir")
    args = p.parse_args()

    n_dev = args.dp
    from examples._common import (
        ensure_devices, opt_partition_specs, resume_exhausted)

    ensure_devices(n_dev)

    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    from apex_tpu.models import bert
    from apex_tpu.optimizers import fused_lamb
    from apex_tpu.transformer.tensor_parallel.mappings import _to_varying

    dp = args.dp
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(dp), ("dp",))

    cfg = bert.tiny(num_layers=args.layers, num_heads=4, hidden_size=64,
                    vocab_size=256, max_seq_len=args.seq)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    specs = jax.tree_util.tree_map(lambda _: P(), params)
    tx = fused_lamb(lr=args.lr)  # trust-ratio update (ref fused_lamb.py)

    B, S = args.batch, args.seq
    MASK_ID = 3

    def pmean(t, ax):
        return jax.lax.pmean(_to_varying(t, ax), ax)

    def train_step(params, opt_state, tokens, targets, loss_mask):
        def loss_fn(params):
            vary = jax.tree_util.tree_map(
                lambda a: _to_varying(a, "dp"), params)
            return bert.loss_fn(vary, (tokens, targets, loss_mask), cfg,
                                tp_axis=None)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # dp-mean every grad; LAMB then sees the same full-tensor grads on
        # every rank, so its trust ratios and clip norm are exact
        grads = jax.tree_util.tree_map(lambda g: pmean(g, "dp"), grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        loss = jax.lax.pmean(loss, "dp")
        return params, opt_state, loss

    data_spec = P("dp", None)
    with mesh:
        opt_state = tx.init(params)
        opt_specs = opt_partition_specs(tx, params, specs)

        step = jax.jit(shard_map(
            train_step, mesh=mesh,
            in_specs=(specs, opt_specs, data_spec, data_spec, data_spec),
            out_specs=(specs, opt_specs, P()),
        ))

        manager = start_it = None
        if args.checkpoint_dir:
            from apex_tpu.checkpoint import CheckpointManager

            manager = CheckpointManager(args.checkpoint_dir, max_to_keep=2)
            if args.resume and manager.latest_step() is not None:
                template = {"params": params, "opt": opt_state,
                            "it": np.zeros((), np.int32)}
                st = manager.restore(template)
                params, opt_state = st["params"], st["opt"]
                start_it = int(st["it"]) + 1
                print(f"=> resumed from step {int(st['it'])}")
                if resume_exhausted(start_it, args.steps):
                    return

        key = jax.random.PRNGKey(1)
        first = loss = None
        for it in range(start_it or 0, args.steps):
            key, k1, k2 = jax.random.split(key, 3)
            clean = jax.random.randint(k1, (B * dp, S), 4, cfg.vocab_size)
            mask = jax.random.bernoulli(k2, args.mask_prob, (B * dp, S))
            tokens = jnp.where(mask, MASK_ID, clean)
            t0 = time.perf_counter()
            params, opt_state, loss = step(
                params, opt_state, tokens, clean,
                mask.astype(jnp.float32))
            loss = float(loss)
            if first is None:
                first = loss
            print(f"step {it:3d}  mlm loss {loss:.4f}  "
                  f"({(time.perf_counter() - t0) * 1e3:.0f} ms)")
            if manager is not None and (it % args.save_every == 0
                                        or it == args.steps - 1):
                manager.save(it, {"params": params, "opt": opt_state,
                                  "it": np.asarray(it, np.int32)})

    print(f"mesh dp={dp} FusedLAMB: loss {first:.4f} -> {loss:.4f} "
          f"({'decreased' if loss < first else 'NOT decreased'})")


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
