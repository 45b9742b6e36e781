#!/usr/bin/env python3
"""python tools/train_op_scopes.py --workload <training cell> [op ...]

Name the device operations of a training cell's step: compile the step on the
attached chip exactly as `perfbench/runners/train.py` builds it, read the
compiled text with `observability.profiling.hlo_scopes.op_scopes`, and print,
for each operation named (as a device trace or the ledger's `breakdown` names
them: `fusion.323`, `copy.170`), its phase, its scope, its output shape and
its own `op_name`. With no operation named: how many instructions fall in
each phase and scope. PERF.md's section 5 is written from this.
"""

import argparse
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compiled_step_text(workload):
    import jax
    import jax.numpy as jnp

    from perfbench import harness
    from perfbench.references.common import seed_words
    from perfbench.runners import train

    run = harness.Run(workload, 0, 1, 0)
    trainer = train.Trainer(run, run.open_device())
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=trainer.replicated),
        jax.eval_shape(trainer.init_state, *seed_words(0)))
    tokens = jax.ShapeDtypeStruct((trainer.rows, trainer.seq_len), jnp.int32,
                                  sharding=trainer.batch_sharding)
    return trainer.step.lower(*state, (tokens, tokens)).compile().as_text()


def main(argv=None):
    from apex_tpu.observability.profiling.hlo_scopes import op_scopes

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("ops", nargs="*")
    args = parser.parse_args(argv)
    text = compiled_step_text(args.workload)
    scopes = op_scopes(text)
    if not args.ops:
        for (phase, scope), n in sorted(collections.Counter(
                scopes.values()).items(), key=lambda kv: -kv[1]):
            print(f"{n:6d} {phase:10s} {scope}")
        return 0
    for op in args.ops:
        line = re.search(rf"^\s*(?:ROOT\s+)?%?{re.escape(op)}\s*=\s*(\S+).*$",
                         text, re.M)
        if line is None:
            print(f"{op}: not in the compiled step")
            continue
        own = re.search(r'op_name="([^"]*)"', line.group(0))
        phase, scope = scopes[op]
        print(f"{op}: {phase} {scope or '-'}; {line.group(1)}; "
              f"op_name {own.group(1) if own else '-'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
