"""``python -m apex_tpu.tuning`` — one-shot offline tune-all.

    python -m apex_tpu.tuning                 # sweep every kernel,
                                              # write + print the cache
    python -m apex_tpu.tuning --kernel flat_adam
    python -m apex_tpu.tuning --export TUNING_CACHE.json  # repo-
                                              # committable copy too
    python -m apex_tpu.tuning --json          # machine-readable report

Runs on whatever backend the environment provides: real on-device
races on TPU, the deterministic roofline fallback elsewhere. Exit 0 when
every requested kernel tuned, 1 when any sweep failed.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys

from apex_tpu.tuning import cache, search_space, tuner


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.tuning",
        description="apex_tpu Pallas kernel autotuner (offline tune-all)")
    ap.add_argument("--kernel", action="append", default=[],
                    choices=list(search_space.KERNELS),
                    help="tune only these kernels (repeatable; "
                         "default: all)")
    ap.add_argument("--export", default=None, metavar="PATH",
                    help="also copy the written cache to PATH (a "
                         "repo-committable evidence artifact)")
    ap.add_argument("--no-write", dest="write", action="store_false",
                    help="sweep and report without touching the cache")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    results = tuner.tune_all(kernels=args.kernel or None,
                             write=args.write)

    path = cache.cache_path()
    if args.export and args.write:
        shutil.copyfile(path, args.export)
        print(f"exported tuning cache to {args.export}", file=sys.stderr)

    failed = [r for r in results if "error" in r]
    if args.json:
        print(json.dumps({"cache_path": path if args.write else None,
                          "results": results}, indent=1))
    else:
        for r in results:
            if "error" in r:
                print(f"{r['kernel']}: ERROR {r['error']}")
            else:
                e = r["entry"]
                print(f"{r['kernel']:22s} {r['bucket']:28s} "
                      f"{json.dumps(e['params'])} "
                      f"pallas {e['pallas_ms']} ms / xla {e['xla_ms']} ms"
                      f" -> {'pallas' if e['use_pallas'] else 'xla'}"
                      f" [{e['source']}]")
        if args.write:
            print(f"cache: {path}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
