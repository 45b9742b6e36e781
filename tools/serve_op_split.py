#!/usr/bin/env python3
"""python tools/serve_op_split.py --workload <serving cell> [--seed N] [--out F]

Where a serving cell's device time goes, by program and by the program's own
scopes: one traced run of the cell as `perfbench/run.py` drives it (chip
only), then every device operation of the traced window is booked to the
program it ran in (`_decode_step`, or a prefill bucket) and named by the
`jax.named_scope` its instruction carries in that program's compiled text
(`llama/attention`, `llama/short_conv`, `llama/head`, `moe/route`,
`moe/experts`, `moe/shared`; `observability.profiling.hlo_scopes`). The
grouped products of the expert layers are booked by their name whatever scope
they stand in: `apex_gmm`, the Pallas kernel (`ops/grouped_matmul`, since PR
36), and `ragged-dot`, XLA's custom call, which carries no scope (a program
from before PR 36, or one that took the fallback). Prints, a program kind, its
runs, its mean device milliseconds and the milliseconds of each scope in a
run; the same as one JSON object to `--out`. PERF.md's section 5 is written
from this.
"""

import argparse
import collections
import inspect
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# device operations booked by name, not by scope
GROUPED_PRODUCTS = ("apex_gmm", "ragged-dot")


def programs_text(scheduler):
    """{program kind: compiled text} of the engine's decode step and of
    every prefill bucket it compiled, from the shapes of what it holds."""
    import jax
    import jax.numpy as jnp

    def struct(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    cache, rows = scheduler.cache, scheduler.max_batch
    held = [cache.k_pages, cache.v_pages, cache.conv_state]
    batch = [scheduler._newest, jnp.asarray(scheduler._tables),
             jnp.zeros(rows, jnp.int32), jnp.zeros(rows, bool),
             jnp.zeros(rows, bool), jnp.zeros(rows, jnp.int32)]
    params, scales = struct(scheduler.params), struct(scheduler._scales)
    out = {"decode": scheduler._decode.lower(
        params, scales, *struct(held), *struct(batch)).compile().as_text()}
    for bucket, prefill in scheduler._prefills.items():
        out[f"prefill{bucket}"] = prefill.lower(
            params, scales, jax.ShapeDtypeStruct((1, bucket), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    return out


def kind_of(program):
    found = re.search(r"_serving_prefill_s(\d+)", program)
    if found:
        return "prefill" + found.group(1)
    return "decode" if "_decode_step" in program else None


def split(trace, texts):
    """{kind: {"runs", "ms", "scopes": {scope: ms a run}}} over the trace."""
    from apex_tpu.observability.profiling.hlo_scopes import op_scopes

    scopes = {kind: {name.lstrip("%"): scope for name, (_, scope)
                     in op_scopes(text).items()}
              for kind, text in texts.items()}
    out = {}
    for device, programs in trace.modules.items():
        ops = sorted((start, name, dur)
                     for name, start, dur in trace.ops.get(device, ()))
        for program, start, dur in programs:
            kind = kind_of(program)
            if kind not in scopes:
                continue
            row = out.setdefault(kind, {"runs": 0, "ms": 0.0,
                                        "scopes": collections.Counter()})
            row["runs"] += 1
            row["ms"] += dur / 1e6
            for at, name, took in ops:
                if start <= at < start + dur:
                    stem = name.lstrip("%")
                    scope = next(
                        (p for p in GROUPED_PRODUCTS if p in stem),
                        scopes[kind].get(stem) or "(no scope)")
                    row["scopes"][scope] += took / 1e6
        break                                   # the first device that ran
    for row in out.values():
        row["ms"] /= row["runs"]
        row["scopes"] = {scope: ms / row["runs"] for scope, ms
                         in row["scopes"].most_common()}
    return out


def main(argv=None):
    from perfbench import harness
    from perfbench.runners import serve

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 1)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    run = harness.Run(args.workload, args.seed, bench["run_seconds"], 1)
    runner = run.runner()
    servers = [c for _, c in inspect.getmembers(runner, inspect.isclass)
               if issubclass(c, serve.Server) and c.__module__
               == runner.__name__]
    run.open_device()
    server = (servers or [serve.Server])[0](run)
    serve.drive(run, server)
    found = split(run.trace, programs_text(server.engine.scheduler))
    for kind, row in sorted(found.items()):
        print(f"{kind}: {row['runs']} runs of {row['ms']:.3f} ms; a run: "
              + ", ".join(f"{scope} {ms:.3f}"
                          for scope, ms in row["scopes"].items()))
    print(f"serve_tokens_per_s {run.e2e.get('serve_tokens_per_s')}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(found, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
