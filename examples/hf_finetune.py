"""Fine-tune an imported HuggingFace checkpoint, then sample from it —
the interop loop in one script: ``transformers`` weights →
``models.convert`` → fp32 DDP fine-tuning with FusedAdam + chunked CE →
``models.generate`` KV-cache decoding.

Offline-friendly: with no checkpoint to download, a randomly initialized
tiny HF Llama stands in (``--hf-dir`` loads a local pretrained dir via
``transformers.AutoModelForCausalLM`` instead). Synthetic token data;
the loss-decrease verdict and a generation round-trip are the checks.

    python examples/hf_finetune.py --steps 20
    python examples/hf_finetune.py --hf-dir /path/to/llama --steps 100
"""

from __future__ import annotations

import argparse
import time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--hf-dir", default="",
                   help="local HF checkpoint dir (empty = tiny random)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8, help="global batch")
    p.add_argument("--seq", type=int, default=32)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--vocab-chunks", type=int, default=4)
    p.add_argument("--sample-tokens", type=int, default=8)
    args = p.parse_args()

    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from examples._common import ensure_devices

    ensure_devices(args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    import transformers

    from apex_tpu.models import convert, generate, llama
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.parallel import sync_autodiff_gradients

    # ---- import the checkpoint
    if args.hf_dir:
        hf = transformers.AutoModelForCausalLM.from_pretrained(args.hf_dir)
    else:
        import torch

        torch.manual_seed(0)
        hf = transformers.LlamaForCausalLM(transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128))
    params, cfg = convert.llama_from_hf(hf, dtype=jnp.float32)
    del hf
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"imported llama: {n/1e6:.2f}M params, vocab {cfg.vocab_size}")

    # ---- DDP fine-tuning step (replicated params, dp-sharded batch)
    mesh = Mesh(np.array(jax.devices()[:args.devices]), ("dp",))
    tx = fused_adam(lr=args.lr)
    opt_state = tx.init(params)

    def train_step(params, opt_state, tokens, targets):
        def loss_fn(p):
            return llama.loss_fn(p, (tokens, targets), cfg, tp_axis=None,
                                 cp_axis=None,
                                 vocab_chunks=args.vocab_chunks)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = sync_autodiff_gradients(grads, axis_name="dp")
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "dp"))

    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P("dp"), P("dp")),
        out_specs=(P(), P(), P())))

    # fixed synthetic batch (overfit -> deterministic decrease)
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.seq), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1)

    first = loss = None
    t0 = time.perf_counter()
    for it in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        loss = float(loss)
        if first is None:
            first, t0 = loss, time.perf_counter()
        if it % 5 == 0 or it == args.steps - 1:
            print(f"step {it:3d}  loss {loss:.4f}")
    dt = (time.perf_counter() - t0) / max(args.steps - 1, 1)
    print(f"{dt*1e3:.0f} ms/step")

    # ---- sample from the fine-tuned weights
    prompt = tokens[:1, :4]
    out = generate.greedy_generate(params, prompt, cfg,
                                   args.sample_tokens)
    print(f"prompt {np.asarray(prompt[0]).tolist()} -> "
          f"{np.asarray(out[0, 4:]).tolist()}")

    verdict = "decreased" if loss < first else "NOT decreased"
    print(f"hf-finetune: loss {first:.4f} -> {loss:.4f} ({verdict})")
    if loss >= first:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
