#!/usr/bin/env python3
"""python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json, in one process, on the machine it is
started on. No accelerator, or fewer chips than the cell asks for, is an error.
The last line of standard output is the result.
"""

import time

T_START = time.monotonic()          # set-up counts from here, imports included

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None, require_tpu=True, root=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from perfbench import harness

    run = harness.Run(args.workload, args.seed, args.seconds, args.trace,
                      t_start=T_START,
                      root=root or harness.ROOT, require_tpu=require_tpu)
    run.mark("imports")
    line = run.runner().run(run)
    harness.emit(line)
    return line


if __name__ == "__main__":
    main()
    sys.exit(0)
