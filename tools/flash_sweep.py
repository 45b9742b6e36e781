#!/usr/bin/env python3
"""python tools/flash_sweep.py [--repo DIR] [--shape BH,S,D] [--tiles] [--out F]

Times the Pallas flash attention kernels alone, causal, bf16, at q, k, v
`[BH, S, D]` (default `256,1024,64`: GPT-2 345M's training attention at 16
rows of 16 heads; chip only, a minute or two): the forward
(`apex_flash_fwd`) and the backward (`apex_flash_bwd_dq` and
`apex_flash_bwd_dkv` with the row term before them), each at the tiles
`pallas_config.flash_blocks` gives; with `--tiles` at every tile pair of
`FWD_TILES` and `BWD_TILES` too (what a tuning cache entry would pin).

`--repo` imports `apex_tpu` from another checkout, so that two trees' kernels
are timed by the same script (one process each: a chip belongs to one
process). A time is `runtime.timing.time_fn` over independent calls: at a
millisecond a call the dispatch hides behind the device. Prints a line a
measurement and writes them all as JSON to `--out`.
"""

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FWD_TILES = ((256, 256), (256, 512), (512, 512), (512, 1024), (1024, 512),
             (1024, 1024))
BWD_TILES = ((128, 128), (128, 256), (256, 256), (256, 512), (512, 256),
             (512, 512), (512, 1024), (1024, 512), (1024, 1024))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--repo", default=HERE)
    parser.add_argument("--shape", default="256,1024,64")
    parser.add_argument("--tiles", action="store_true")
    parser.add_argument("--iters", type=int, default=40)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.repo))

    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import flash_attention as fa
    from apex_tpu.ops import pallas_config
    from apex_tpu.runtime import timing

    if jax.default_backend() != "tpu":
        sys.exit("tools/flash_sweep.py times a chip: no TPU was found")
    bh, s, d = (int(x) for x in args.shape.split(","))
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(key, (bh, s, d), jnp.bfloat16)
                   for key in ks)
    scale = d ** -0.5
    fwd = functools.partial(fa._flash_fwd_pallas, causal=True, scale=scale)
    bwd = functools.partial(fa._flash_bwd_pallas, causal=True, scale=scale)
    o, lse = fwd(q, k, v, block_q=512, block_k=512)

    def ms(fn, *xs):
        return 1e3 * timing.time_fn(fn, *xs, iters=args.iters, warmup=3)

    runs = [("fwd", pallas_config.flash_blocks("fwd", s, s, d), "auto"),
            ("bwd", pallas_config.flash_blocks("bwd", s, s, d), "auto")]
    if args.tiles:
        runs += [("fwd", t, "swept") for t in FWD_TILES]
        runs += [("bwd", t, "swept") for t in BWD_TILES]
    found = []
    for kind, (bq, bk), how in runs:
        if kind == "fwd":
            t = ms(lambda q, k, v: fwd(q, k, v, block_q=bq, block_k=bk),
                   q, k, v)
        else:
            t = ms(lambda q, k, v, o, lse, do: bwd(
                q, k, v, o, lse, do, block_q=bq, block_k=bk),
                q, k, v, o, lse, do)
        row = {"repo": os.path.abspath(args.repo), "kind": kind,
               "shape": [bh, s, d], "block_q": bq, "block_k": bk,
               "tiles": how, "ms": t,
               "device": jax.devices()[0].device_kind}
        found.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(found, f, indent=1)


if __name__ == "__main__":
    main()
