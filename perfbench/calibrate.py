#!/usr/bin/env python3
"""python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2]

Reads on the chip, in one process, what the limits of `correct` are set from
(PERF.md, "How correct is decided"): the program's numbers on every seed, and
on the control seeds the control's and the planted faults'. One JSON object a
line, to standard output and to --out. The benchmark's own runs do not run it.
"""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=15.0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    from perfbench import harness

    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.find(bench["workloads"], args.workload, "workload")
    entry = harness.find(bench["configs"], cell["config"], "configuration")
    runner = importlib.import_module(
        "perfbench.runners."
        + harness.load_json(harness.ROOT, entry["file"])["runner"])
    out = open(args.out, "a") if args.out else None

    def emit(record):
        text = json.dumps(record)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()

    runner.calibrate(args.workload, seeds, controls, args.seconds, emit)


if __name__ == "__main__":
    main()
