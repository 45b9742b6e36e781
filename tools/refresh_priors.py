#!/usr/bin/env python
"""Regenerate analysis/hbm_priors.json from the newest calibration
capture (ISSUE 19 satellite).

The committed priors file is the calibration loop's (PR 14) memory —
per-target measured/modeled HBM ratios the estimator and the planner
price on. This one-shot refreshes it from, in order of preference:

  1. ``--from DUMP.jsonl``   explicit bench metrics dump (reads the
     ``memory_calibration`` event lines);
  2. the newest ``BENCH_*_live.json`` / ``BENCH_BASELINE.jsonl`` in
     the repo root that carries calibration events;
  3. ``--live``              a fresh ``calibrate_targets()`` run on
     the current backend (run on a TPU it replaces CPU ratios with
     on-silicon ones).

Output is deterministic (sorted keys, fixed rounding, no clocks), so
an unchanged capture regenerates a byte-identical file and the diff in
review is exactly the ratio drift. The result is validated through
``memory_checks.load_hbm_priors`` before it lands — this tool can
never commit a file the loader would refuse.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # runnable as `python tools/refresh_priors.py`
    sys.path.insert(0, REPO)
PRIORS_PATH = os.path.join(REPO, "apex_tpu", "analysis",
                           "hbm_priors.json")


def rows_from_events(events) -> dict:
    """{target: row} from memory_calibration event payloads (the last
    event per target wins — newest capture)."""
    rows = {}
    for ev in events:
        target = ev.get("target")
        ratio = ev.get("ratio")
        if not target or not isinstance(ratio, (int, float)):
            continue
        rows[str(target)] = {
            "ratio": round(float(ratio), 4),
            "modeled_bytes": int(ev.get("modeled_bytes", 0)),
            "measured_bytes": int(ev.get("measured_bytes", 0)),
        }
    return rows


def events_from_jsonl(path):
    """memory_calibration events from a bench metrics dump (either the
    per-line record format of BENCH_BASELINE.jsonl or a single bench
    JSON object with an events list)."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if not isinstance(rec, dict):
                continue
            if rec.get("event") == "memory_calibration":
                events.append(rec)
            for ev in rec.get("events", ()) or ():
                if isinstance(ev, dict) and \
                        ev.get("event") == "memory_calibration":
                    events.append(ev)
    return events


def newest_capture() -> str | None:
    cands = sorted(
        glob.glob(os.path.join(REPO, "BENCH_*_live.json"))
        + glob.glob(os.path.join(REPO, "BENCH_BASELINE.jsonl")),
        key=lambda p: os.path.getmtime(p), reverse=True)
    for path in cands:
        if events_from_jsonl(path):
            return path
    return None


def rows_from_live() -> tuple[dict, str]:
    from apex_tpu.observability.memory.calibrate import calibrate_targets
    from apex_tpu.observability.registry import MetricRegistry

    results = calibrate_targets(registry=MetricRegistry())
    rows = {}
    for name, row in sorted(results.items()):
        if "ratio" not in row:
            print(f"refresh_priors: {name} skipped: {row.get('error')}",
                  file=sys.stderr)
            continue
        rows[name] = {
            "ratio": round(float(row["ratio"]), 4),
            "modeled_bytes": int(row["modeled_bytes"]),
            "measured_bytes": int(row["measured_bytes"]),
        }
    import jax

    backend = jax.default_backend()
    return rows, backend


def build_document(rows: dict, backend: str, source: str) -> dict:
    ratios = [r["ratio"] for r in rows.values()]
    return {
        "_comment": (
            "Calibrated HBM correction priors (ISSUE 19): per-target "
            "measured/modeled ratios distilled from the bench "
            "memory_calibration captures (apex_tpu.observability."
            "memory.calibrate). Consumed by estimate_hbm_and_comms("
            "priors=...) and apex_tpu.analysis.planner pruning; "
            "validated loudly by memory_checks.load_hbm_priors. "
            "Regenerate with: python tools/refresh_priors.py (--live "
            "on a TPU replaces CPU-backend ratios with on-silicon "
            "ones)."),
        "schema_version": 1,
        "backend": backend,
        "source": source,
        "default_ratio": round(statistics.median(ratios), 4),
        "priors": {k: rows[k] for k in sorted(rows)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="regenerate analysis/hbm_priors.json from the "
                    "newest calibration capture")
    ap.add_argument("--from", dest="dump", default=None,
                    help="bench metrics dump to read "
                         "memory_calibration events from")
    ap.add_argument("--live", action="store_true",
                    help="run calibrate_targets() fresh instead of "
                         "reading a capture")
    ap.add_argument("--out", default=PRIORS_PATH,
                    help=f"output path (default {PRIORS_PATH})")
    args = ap.parse_args(argv)

    if args.live:
        rows, backend = rows_from_live()
        source = "calibrate_targets() live run"
    else:
        dump = args.dump or newest_capture()
        if dump is None:
            print("refresh_priors: no capture with memory_calibration "
                  "events found (and --live not given) — nothing to "
                  "refresh", file=sys.stderr)
            return 1
        rows = rows_from_events(events_from_jsonl(dump))
        backend = "cpu"
        for suffix in ("_live.json",):
            if dump.endswith(suffix):
                backend = "tpu"  # live captures only land on-silicon
        source = f"memory_calibration events from " \
                 f"{os.path.relpath(dump, REPO)}"
    if not rows:
        print("refresh_priors: capture carried no usable calibration "
              "rows", file=sys.stderr)
        return 1

    doc = build_document(rows, backend, source)
    text = json.dumps(doc, indent=2, sort_keys=False) + "\n"

    # the loader is the schema authority: never write a file it
    # would refuse
    import tempfile

    from apex_tpu.analysis.memory_checks import load_hbm_priors

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as tmp:
        tmp.write(text)
    try:
        load_hbm_priors(tmp.name)
    finally:
        os.unlink(tmp.name)

    with open(args.out, "w") as f:
        f.write(text)
    print(f"refresh_priors: wrote {len(rows)} prior(s) "
          f"(default_ratio {doc['default_ratio']}) to {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
