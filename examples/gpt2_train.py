"""GPT-2 tensor-parallel training — the BASELINE.json "GPT-2 345M
apex.transformer tensor-parallel + fused softmax" config (ref
apex/transformer/tensor_parallel/layers.py + csrc/megatron softmax
kernels; here the causal fused softmax is the Pallas kernel inside the
model and the whole step is one jit over a dp x tp mesh).

    python examples/gpt2_train.py --dp 2 --tp 4 --steps 10
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--tp", type=int, default=4)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=4, help="per-dp-rank batch")
    p.add_argument("--seq", type=int, default=32)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--checkpoint-dir", default="",
                   help="save train state here every --save-every steps")
    p.add_argument("--save-every", type=int, default=5)
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest step in --checkpoint-dir")
    args = p.parse_args()

    n_dev = args.dp * args.tp
    from examples._common import (
        ensure_devices, opt_partition_specs, resume_exhausted)

    ensure_devices(n_dev)

    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    from apex_tpu.models import gpt2
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.transformer.tensor_parallel.mappings import _to_varying

    dp, tp = args.dp, args.tp
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(dp, tp),
                ("dp", "tp"))

    cfg = gpt2.tiny(num_layers=args.layers, num_heads=2 * tp,
                    hidden_size=32 * tp, vocab_size=128 * tp,
                    max_seq_len=args.seq)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    specs = gpt2.param_specs(cfg)
    tx = fused_adam(lr=args.lr)

    B, S = args.batch, args.seq

    def pmean(t, ax):
        return jax.lax.pmean(_to_varying(t, ax), ax)

    def train_step(params, opt_state, tokens, targets):
        def loss_fn(params):
            vary = params
            for ax in ("dp", "tp"):
                vary = jax.tree_util.tree_map(
                    lambda a, ax=ax: _to_varying(a, ax), vary)
            return gpt2.loss_fn(vary, (tokens, targets), cfg, tp_axis="tp")

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree_util.tree_map(lambda g: pmean(g, "dp"), grads)
        grads = jax.tree_util.tree_map(
            lambda g, s: g if "tp" in s else pmean(g, "tp"), grads, specs)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        loss = jax.lax.pmean(jax.lax.pmean(loss, "dp"), "tp")
        return params, opt_state, loss

    data_spec = P("dp", None)
    with mesh:
        opt_state = tx.init(params)
        opt_specs = opt_partition_specs(tx, params, specs)

        step = jax.jit(shard_map(
            train_step, mesh=mesh,
            in_specs=(specs, opt_specs, data_spec, data_spec),
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
            key, sub = jax.random.split(key)
            tokens = jax.random.randint(sub, (B * dp, S), 0, cfg.vocab_size)
            targets = jnp.roll(tokens, -1, axis=-1)
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, tokens,
                                           targets)
            loss = float(loss)
            if first is None:
                first = loss
            print(f"step {it:3d}  loss {loss:.4f}  "
                  f"({(time.perf_counter() - t0) * 1e3:.0f} ms)")
            if manager is not None and (it % args.save_every == 0
                                        or it == args.steps - 1):
                manager.save(it, {"params": params, "opt": opt_state,
                                  "it": np.asarray(it, np.int32)})

    print(f"mesh dp={dp} tp={tp}: loss {first:.4f} -> {loss:.4f} "
          f"({'decreased' if loss < first else 'NOT decreased'})")


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
