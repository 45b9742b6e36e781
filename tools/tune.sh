#!/usr/bin/env bash
# One-shot offline kernel autotune: sweep every registered Pallas kernel
# on the CURRENT backend (real on-device races on TPU; the
# docs/kernel_cost_study.md roofline fallback elsewhere — deterministic,
# so this is CI-runnable), write the persistent per-device tuning cache
# (~/.cache/apex_tpu/tuning_cache.json or APEX_TPU_TUNING_CACHE) and
# print the winners. Dispatch consults the cache on the next trace; a
# race verdict flips pallas_config._KERNEL_AUTO with the cache file as
# its provenance evidence artifact (docs/tuning.md).
#
#   bash tools/tune.sh                          # tune all, write cache
#   bash tools/tune.sh --kernel flat_adam       # one kernel
#   bash tools/tune.sh --export TUNING_CACHE.json  # repo-committable copy
#   bash tools/tune.sh --no-write --json        # dry sweep report
set -euo pipefail
cd "$(dirname "$0")/.."

exec python -m apex_tpu.tuning "$@"
