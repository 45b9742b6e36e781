#!/usr/bin/env python3
"""python3 tools/idle_split.py --workload <cell> --seed <n> [--seconds 45]

One traced run of a serving cell, as `perfbench/run.py --trace 1` makes it
(its result line carries the per-layer metrics BENCHMARK.json lists for the
cell), and then every reading of the split of the device's idle time
(`perfbench/readers/idle_split.py`: the program's own pauses, launch gaps,
the host's wait, time with nothing to run), whatever the cell lists. The
split's table and the clock's fit are printed on standard
error; the run's result line, then one line `{"idle_split": {...}}` with the
readings beside the old pairing's clock check and the run's end-to-end
metrics with the profiler on, end standard output. On the chip only, as the
benchmark.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

READINGS = ("pause_ms_per_decode_step", "host_wait_ms_per_decode_step",
            "host_wait_ms_per_admission", "host_wait_share", "clock_bound_us")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=45.0)
    args = parser.parse_args(argv)

    from perfbench import harness, trace_reduce
    from perfbench.readers import idle_split, serving_idle

    run = harness.Run(args.workload, args.seed, args.seconds, 1,
                      t_start=T_START)
    run.mark("imports")
    line = run.runner().run(run)
    harness.emit(line)
    got = {what: idle_split.read(run, what) for what in READINGS}
    got["span_clock_slack_us"] = serving_idle.read(run, "clock_slack_us")
    # the end-to-end readings of this traced run, beside an untraced one's
    got["end_to_end"] = run.e2e
    split = idle_split.split(run)
    if split is not None:
        got["seconds"] = {cls: sum(v[0] for v in rows.values())
                          for cls, rows in split[0].items()}
    if run.trace is not None and run.trace.ops:
        got["device_idle_share"] = 100 * trace_reduce.idle_share(run.trace)
        got["traced_s"] = run.trace.window_s
    print(json.dumps({"idle_split": got}), flush=True)


if __name__ == "__main__":
    main()
