#!/usr/bin/env python3
"""python3 perfbench/sweep.py --workload <open-loop cell> --rates 2,3,4,5 --seconds 20

Finds, once, the highest arrival rate the engine sustains under a cell's mix:
the knee. Each rate gets the mix's own traffic at that rate for a short window,
in one process; a line a rate reports the tails, how many of the window's
requests had not yet got a first token when the window closed (a queue that
grows), and how long the drain took. The rate written into the mix file is
0.8 of the knee. The benchmark's own runs do not run this.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    from perfbench import harness, stats
    from perfbench.runners import serve

    for rate in [float(r) for r in args.rates.split(",")]:
        run = harness.Run(args.workload, args.seed, args.seconds, 0)
        run.mix["arrivals"]["rate_per_s"] = rate
        run.open_device()
        server = serve.Server(run)
        attempted, failed, t_close = serve.drive(run, server)
        waiting = sum(1 for p, r in server.requests if p.in_window and
                      (r.first_token_s is None or r.first_token_s > t_close))
        record = {"rate_per_s": rate, "requests": attempted, "failed": failed,
                  "ttft_p50_ms": stats.percentile(run.series["ttft_ms"], 50),
                  "ttft_p90_ms": run.e2e["ttft_p90_ms"],
                  "tpot_p50_ms": stats.percentile(run.series["tpot_ms"], 50),
                  "tpot_p90_ms": run.e2e["tpot_p90_ms"],
                  "waiting_at_close": waiting,
                  "drain_s": run.counters["t_end"] - t_close,
                  "occupancy_mean": sum(s["occupancy"] for s in
                                        run.series["steps"])
                  / max(1, len(run.series["steps"]))}
        text = json.dumps(record)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
        server.free()
        server.params = None


if __name__ == "__main__":
    main()
