"""Mixture-of-Experts training over a dp x ep mesh — expert parallelism
via tiled all_to_all (apex_tpu.transformer.moe; no reference analog — the
CUDA reference predates MoE, SURVEY §1 lists 'ep' among the mesh axes).

Tokens shard over BOTH axes (ep doubles as data parallelism for the
tokens, the Megatron ep-within-dp layout); expert weights shard over 'ep'
only, the router replicates.

    python examples/moe_train.py --dp 2 --ep 4 --steps 10
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
    p.add_argument("--ep", type=int, default=4)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=16, help="tokens per rank")
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--experts-per-rank", type=int, default=2)
    p.add_argument("--top-k", type=int, default=2)
    p.add_argument("--lr", type=float, default=1e-2)
    args = p.parse_args()

    n_dev = args.dp * args.ep
    from examples._common import ensure_devices, opt_partition_specs

    ensure_devices(n_dev)

    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    from apex_tpu.optimizers import fused_adam
    from apex_tpu.transformer.moe import (
        MoEConfig,
        init_moe_params,
        moe_mlp,
        moe_param_specs,
    )
    from apex_tpu.transformer.tensor_parallel.mappings import make_varying

    dp, ep = args.dp, args.ep
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(dp, ep),
                ("dp", "ep"))

    cfg = MoEConfig(hidden_size=args.hidden,
                    ffn_hidden_size=2 * args.hidden,
                    num_experts=args.experts_per_rank * ep,
                    top_k=args.top_k, capacity_factor=2.0)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    specs = moe_param_specs(cfg)
    tx = fused_adam(lr=args.lr)

    def pmean(t, ax):
        return jax.lax.pmean(make_varying(t, ax), ax)

    def train_step(params, opt_state, x, target):
        def loss_fn(params):
            vary = params
            for ax in ("dp", "ep"):
                vary = jax.tree_util.tree_map(
                    lambda a, ax=ax: make_varying(a, ax), vary)
            y, aux = moe_mlp(vary, x, cfg, ep_axis="ep")
            mse = jnp.mean((y - target) ** 2)
            for ax in ("dp", "ep"):
                mse = jax.lax.pmean(mse, ax)
                aux = jax.lax.pmean(aux, ax)
            return mse + aux, mse

        (loss, mse), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        # router replicated over both token-shard axes; experts ep-sharded
        grads = {"router": pmean(pmean(grads["router"], "ep"), "dp"),
                 "wi": pmean(grads["wi"], "dp"),
                 "wo": pmean(grads["wo"], "dp")}
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state, mse

    data_spec = P(("dp", "ep"), None)
    with mesh:
        opt_state = tx.init(params)
        opt_specs = opt_partition_specs(tx, params, specs)

        step = jax.jit(shard_map(
            train_step, mesh=mesh,
            in_specs=(specs, opt_specs, data_spec, data_spec),
            out_specs=(specs, opt_specs, P()),
        ))

        key = jax.random.PRNGKey(1)
        B = args.batch * n_dev
        first = loss = None
        for it in range(args.steps):
            key, sub = jax.random.split(key)
            x = jax.random.normal(sub, (B, cfg.hidden_size))
            target = jnp.sin(3.0 * x)
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, x, target)
            loss = float(loss)
            if first is None:
                first = loss
            print(f"step {it:3d}  mse {loss:.4f}  "
                  f"({(time.perf_counter() - t0) * 1e3:.0f} ms)")

    print(f"mesh dp={dp} ep={ep} experts={cfg.num_experts} "
          f"top{cfg.top_k}: mse {first:.4f} -> {loss:.4f} "
          f"({'decreased' if loss < first else 'NOT decreased'})")


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
