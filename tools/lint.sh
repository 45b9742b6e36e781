#!/usr/bin/env bash
# apex_tpu static-analysis gate: every apex_tpu.analysis engine over the
# canonical target set, failing on any finding not grandfathered in
# tests/run_analysis/baseline.json.
#
#   bash tools/lint.sh                 # the tier-1 gate (run by
#                                      # tests/run_analysis/test_repo_selfcheck.py)
#   bash tools/lint.sh --changed-only  # AST + concurrency engines over
#                                      # files changed vs the merge base
#                                      # only (LINT_BASE, default main);
#                                      # jaxpr/dataflow targets still
#                                      # run in full
#   bash tools/lint.sh --write-baseline tests/run_analysis/baseline.json
#
# Extra args are forwarded to `python -m apex_tpu.analysis` (which
# ignores --baseline when --write-baseline is given). That includes the
# ISSUE 18 ergonomics flags: `--engines ast,state` narrows the run to an
# explicit engine subset (composes with --changed-only, since the
# forwarded args reach both exec paths) and `--list-targets` prints the
# registered jaxpr/dataflow/sharding/spmd/state/memory targets with
# their owning engine. The checkpoint/state-flow engine (ISSUE 18) runs
# its four resume-path targets here like any other tracing engine and
# gets its own line in the per-engine wall-time breakdown; the
# memory-liveness engine (ISSUE 19, `--engines memory`) does the same
# with its four donated-carry targets, which the gate holds at 0
# findings.
#
# Wall-time budget (ISSUE 14 satellite): the CLI fails (exit 2, LOUD)
# when the summed engine wall time exceeds LINT_TIME_BUDGET_S (default
# 180s; <= 0 disables) — the growing engine stack must not silently rot
# tier-1 runtime. The per-engine breakdown is printed on every run.
#
# Goodput gate (ISSUE 17 satellite): after the analysis engines, the
# full run also exercises `tools/metrics_report.py --compare` against
# the pinned BENCH_BASELINE.jsonl (self-compare by default; set
# BENCH_COMPARE_CURRENT to a fresh bench dump to gate a real run).
set -euo pipefail
cd "$(dirname "$0")/.."

# CPU backend + an 8-device virtual mesh, same environment the test
# suite runs under (tests/conftest.py), so the tp_collectives jaxpr
# target sees a multi-device mesh without hardware.
export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

if [[ "${1:-}" == "--changed-only" ]]; then
    shift
    # Narrow the path-driven engines (AST + host-concurrency — both
    # consume the same explicit path list) to python files changed
    # since the merge base (working tree + index + committed-vs-base;
    # deleted files drop out via the existence filter). The jaxpr +
    # dataflow/sharding targets are NOT narrowed: they trace whole
    # entry points, so an edit anywhere in a traced module can move
    # their verdicts.
    #
    # LINT_DIFF_REPORT: path to a stored `--json` dump from the merge
    # base (generate once per base rev: `python -m apex_tpu.analysis
    # --json > base.json`). When set, the gate fails only on findings
    # NEW relative to that run — pre-existing base findings and their
    # churn never block a branch, which is what keeps --changed-only
    # usable as the fast CI gate.
    diff_args=()
    if [[ -n "${LINT_DIFF_REPORT:-}" ]]; then
        if [[ ! -f "${LINT_DIFF_REPORT}" ]]; then
            echo "LINT_DIFF_REPORT=${LINT_DIFF_REPORT} does not exist" >&2
            exit 2
        fi
        diff_args+=(--diff "${LINT_DIFF_REPORT}")
    fi
    base="$(git merge-base HEAD "${LINT_BASE:-main}" 2>/dev/null || true)"
    changed="$(
        { git diff --name-only "${base:-HEAD}" -- 2>/dev/null;
          git diff --name-only --cached 2>/dev/null;
          git diff --name-only 2>/dev/null; } \
        | sort -u \
        | grep -E '^(apex_tpu|examples|tools)/.*\.py$|^(bench|chip_smoke)\.py$' || true)"
    ast_paths=()
    while IFS= read -r f; do
        [[ -n "$f" && -e "$f" ]] && ast_paths+=("$f")
    done <<< "$changed"
    if [[ ${#ast_paths[@]} -eq 0 ]]; then
        # nothing changed under the linted paths: skip both path-driven
        # engines entirely (an empty explicit path list would be
        # rejected as a typo by the CLI's loud-failure rule)
        exec python -m apex_tpu.analysis \
            --baseline tests/run_analysis/baseline.json \
            --no-ast --no-concurrency \
            ${diff_args[@]+"${diff_args[@]}"} "$@"
    fi
    exec python -m apex_tpu.analysis \
        --baseline tests/run_analysis/baseline.json \
        ${diff_args[@]+"${diff_args[@]}"} "${ast_paths[@]}" "$@"
fi

rc=0
python -m apex_tpu.analysis \
    --baseline tests/run_analysis/baseline.json \
    apex_tpu examples tools bench.py chip_smoke.py "$@" || rc=$?

# Goodput regression gate (ISSUE 17 satellite): compare a bench metrics
# dump against the pinned BENCH_BASELINE.jsonl. By default the baseline
# is compared against itself — a deterministic arming check that proves
# the gate parses the pinned dump and the goodput/* family is present
# (a broken baseline or renamed gauge fails loudly here, not silently
# in CI). Point BENCH_COMPARE_CURRENT at a fresh `python bench.py`
# dump to gate a real run's goodput ratio against the baseline.
if [[ -f BENCH_BASELINE.jsonl ]]; then
    current="${BENCH_COMPARE_CURRENT:-BENCH_BASELINE.jsonl}"
    if [[ ! -f "$current" ]]; then
        echo "BENCH_COMPARE_CURRENT=$current does not exist" >&2
        exit 2
    fi
    python tools/metrics_report.py "$current" \
        --compare BENCH_BASELINE.jsonl || rc=$?
else
    echo "WARNING: BENCH_BASELINE.jsonl missing - goodput gate skipped" >&2
fi

exit "$rc"
