#!/usr/bin/env python3
"""python tools/gmm_sweep.py [--shapes lfm2_prefill,...] [--tiles] [--out F]

Races the Pallas grouped product (`ops/grouped_matmul.gmm`, `apex_gmm`)
against XLA's `jax.lax.ragged_dot` at the four shapes `dropless_experts` runs
at in the serving cells (chip only; one command, a few minutes):

- LFM2 prefill: 4,096 / 10,240 / 16,384 sorted rows on 64 groups from group
  192 of a stack of 512, `[2048, 1536]` and `[1536, 2048]`;
- LFM2 decode: 128 rows on the same groups (about 55 hit);
- Trinity prefill: 8,192 / 16,384 sorted rows, an eighth of them in the 32
  groups held from group 64 of a stack of 128, `[3072, 3072]`;
- Trinity decode: 128 rows, about 16 of them in a group.

Rows fall on groups as a uniform router deals them (a multinomial draw from
`--seed`). A time is the slope between a jitted `lax.scan` of 8 products and
one of 24 (`runtime.timing.time_fn`): the dispatch cancels; the scan's carry
is the `sizes`, to which each product adds a zero read from its own result,
so no product is hoisted or dropped, and the rows and the weights are the
program's arguments, copied by nothing. With `--tiles` every row and
column tile of `TM` x `TN` is timed too (what chose `grouped_matmul.tiles`),
and `--megablox` adds `jax.experimental.pallas.ops.tpu.megablox.gmm` as a
yardstick, given the layer's `[count, k, n]` slice (which the program never
cuts). Prints a line a measurement and writes them all as JSON to `--out`.
"""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (rows, share of the rows that fall on a held group, G, at, count,
# [(k, n), ...])
SHAPES = {
    "lfm2_prefill": ((4096, 10240, 16384), 1.0, 512, 192, 64,
                     ((2048, 1536), (1536, 2048))),
    "lfm2_decode": ((128,), 1.0, 512, 192, 64, ((2048, 1536), (1536, 2048))),
    "trinity_prefill": ((8192, 16384), 0.125, 128, 64, 32, ((3072, 3072),)),
    "trinity_decode": ((128,), 0.125, 128, 64, 32, ((3072, 3072),)),
}
TM = (64, 128, 256)
TN = (512, 1024, 1536, 2048)


def deal(rng, m, share, groups, at, count):
    """`sizes [G]`: of `m` rows, `share` of them dealt over the `count`
    groups from `at` on, the others past every group."""
    import numpy as np

    held = rng.binomial(m, share) if share < 1 else m
    sizes = np.zeros(groups, np.int32)
    sizes[at:at + count] = rng.multinomial(held, np.full(count, 1 / count))
    return sizes


def main(argv=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops import grouped_matmul as gm
    from apex_tpu.ops import pallas_config
    from apex_tpu.runtime import timing
    from perfbench import peaks

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--tiles", action="store_true")
    parser.add_argument("--megablox", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        sys.exit("tools/gmm_sweep.py times a chip: no TPU was found")
    peak = peaks.peak(jax.devices()[0].device_kind)
    rng = np.random.default_rng(args.seed)
    found = []

    def time_of(product, xs, w, sizes):
        def scan_of(length):
            def run(xs, w, sizes):
                def body(sizes, _):
                    ys = product(xs, w, sizes)
                    zero = (ys[0, 0] != ys[0, 0]).astype(sizes.dtype)
                    return sizes + zero, None

                return jax.lax.scan(body, sizes, None, length=length)[0]

            return timing.time_fn(jax.jit(run), xs, w, sizes, iters=8,
                                  warmup=2)

        return (scan_of(24) - scan_of(8)) / 16

    for name in args.shapes.split(","):
        rows, share, groups, at, count, widths = SHAPES[name]
        for k, n in widths:
            w = jax.random.normal(jax.random.PRNGKey(args.seed),
                                  (groups, k, n), jnp.bfloat16) * k ** -0.5
            for m in rows:
                sizes = deal(rng, m, share, groups, at, count)
                xs = jax.random.normal(jax.random.PRNGKey(1), (m, k),
                                       jnp.bfloat16)
                held, hit = int(sizes.sum()), int((sizes > 0).sum())
                floor_ms = 1e3 * max(
                    (hit * k * n + held * (k + n)) * 2
                    / peak["hbm_bytes_per_s"],
                    2 * held * k * n / peak["flops_per_s"])
                row = {"shape": name, "m": m, "k": k, "n": n, "rows_held":
                       held, "groups_hit": hit, "floor_ms": floor_ms}
                # name: (product, its weights, its sizes)
                sizes = jnp.asarray(sizes)
                products = {"ragged_dot": (jax.lax.ragged_dot, w, sizes),
                            "apex_gmm": (lambda xs, w, s: gm.gmm(
                                xs, w, s, at, count), w, sizes)}
                if args.tiles:
                    for tm in TM:
                        for tn in TN:
                            if n % tn or tm > max(m, 64) or gm.vmem_bytes(
                                    tm, tn, k, 2) > gm.VMEM_BUDGET:
                                continue
                            products[f"apex_gmm {tm}x{tn}"] = (
                                lambda xs, w, s, tm=min(tm, m), tn=tn:
                                gm.gmm_tiled(xs, w, s, at, count, tm, tn),
                                w, sizes)
                if args.megablox:
                    megablox = importlib.import_module(
                        "jax.experimental.pallas.ops.tpu.megablox.gmm")
                    for tiling in ((128, k, 512), (256, k, 512),
                                   (512, 512, 1024)):
                        if m % min(tiling[0], m):
                            continue
                        products[f"megablox {tiling}"] = (
                            lambda xs, w, s, tiling=tiling: megablox.gmm(
                                xs, w, s, preferred_element_type=xs.dtype,
                                tiling=(min(tiling[0], m),) + tiling[1:]),
                            w[at:at + count], sizes[at:at + count])
                want = None
                for what, (product, its_w, its_sizes) in products.items():
                    try:
                        with pallas_config.force("on"):
                            ms = time_of(product, xs, its_w, its_sizes) * 1e3
                            got = np.asarray(jax.jit(product)(
                                xs, its_w, its_sizes)[:held], np.float32)
                    except Exception as e:      # a tile Mosaic refuses
                        print(f"{name} m={m} {k}x{n} {what}: {e!r:.300}",
                              flush=True)
                        continue
                    want = got if want is None else want
                    row[what] = {"ms": ms, "floor_share": floor_ms / ms,
                                 "max_gap": float(np.abs(got - want).max())}
                    print(f"{name} m={m} {k}x{n} held={held} hit={hit} "
                          f"floor={floor_ms:.3f} ms  {what}: {ms:.3f} ms "
                          f"gap {row[what]['max_gap']:.3g}", flush=True)
                found.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(found, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
