"""Long-context Llama training with ring-attention context parallelism.

The sequence dimension is sharded over the 'cp' mesh axis: each device
holds seq/cp tokens, and attention runs as a ring — K/V blocks circulate
via ``ppermute`` while each device accumulates its queries' online
softmax (apex_tpu/transformer/context_parallel.py). Peak activation
memory per device is O(seq/cp · d): no device ever materializes a score
matrix for the full sequence, which is what makes 100k+-token contexts
fit. Optionally composes with dp (data parallelism) on the same mesh.

This is the capability Apex's users reach for Megatron-LM's context
parallelism for; the reference itself has no single-file analog (its
pieces live in apex/transformer). TPU-native shape: one ``shard_map``
carries the ring attention, the dp gradient mean, and the fused-Adam
update in a single jitted step.

    python examples/long_context.py --cp 4 --dp 2 --seq 512 --steps 10
"""

from __future__ import annotations

import argparse
import functools
import time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cp", type=int, default=4)
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--seq", type=int, default=512,
                   help="GLOBAL sequence length (seq/cp per device)")
    p.add_argument("--batch", type=int, default=4,
                   help="global batch (batch/dp per dp rank)")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=1e-3)
    args = p.parse_args()

    n_dev = args.cp * args.dp
    from examples._common import ensure_devices

    ensure_devices(n_dev)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    from apex_tpu.models import llama
    from apex_tpu.optimizers import fused_adam

    if args.seq % args.cp:
        raise SystemExit(f"--seq {args.seq} must divide by --cp {args.cp}")
    if args.batch % args.dp:
        raise SystemExit(f"--batch {args.batch} must divide by --dp "
                         f"{args.dp}")

    cfg = llama.tiny(max_seq_len=args.seq)
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(args.dp, args.cp),
                ("dp", "cp"))
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tx = fused_adam(lr=args.lr)
    opt_state = tx.init(params)

    # one fixed batch (overfit => deterministic decrease); tokens are
    # sharded [batch/dp, seq/cp] per device
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.seq), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1)

    def step(params, opt_state, tokens, targets):
        def loss_fn(p):
            # ring attention makes the ACTIVATIONS globally correct over
            # cp, but llama.loss_fn's CE mean covers only this device's
            # seq shard — average it over cp (and dp) to the global loss
            loss = llama.loss_fn(p, (tokens, targets), cfg, tp_axis=None,
                                 cp_axis="cp")
            return jax.lax.pmean(jax.lax.pmean(loss, "cp"), "dp")

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # params are replicated over BOTH axes, so their grads must be
        # averaged over both — each rank's backward pass contributes only
        # its own tokens' share
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(jax.lax.pmean(g, "cp"), "dp"), grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state, loss

    jstep = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P("dp", "cp"), P("dp", "cp")),
        out_specs=(P(), P(), P())))

    # ground truth: the sharded global loss at init must equal the
    # single-device loss on the full batch — catches any missing cp/dp
    # reduction that mere loss-decrease would hide
    ref = float(llama.loss_fn(params, (tokens, targets), cfg,
                              tp_axis=None, cp_axis=None))
    _, _, l0 = jstep(params, opt_state, tokens, targets)
    if abs(float(l0) - ref) > 5e-3 * max(1.0, abs(ref)):
        raise SystemExit(f"cp-sharded loss {float(l0):.5f} != "
                         f"single-device loss {ref:.5f}")
    print(f"parity: sharded loss {float(l0):.5f} == single-device "
          f"{ref:.5f} OK")

    losses = []
    for i in range(args.steps):
        t0 = time.perf_counter()
        params, opt_state, loss = jstep(params, opt_state, tokens, targets)
        losses.append(float(loss))
        print(f"step {i:3d}  loss {losses[-1]:.4f}  "
              f"({(time.perf_counter() - t0) * 1e3:.0f} ms)", flush=True)

    verdict = "decreased" if losses[-1] < losses[0] else "NOT decreased"
    print(f"ring-attention cp={args.cp} dp={args.dp} seq={args.seq}: "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f} ({verdict})")
    if losses[-1] >= losses[0]:
        raise SystemExit(1)


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
