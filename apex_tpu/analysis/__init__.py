"""apex_tpu.analysis — static TPU lint (SURVEY: sanitizer/pyprof-adjacent
correctness tooling, rebuilt as compile-time analysis).

Two engines, one CLI, one pytest gate:

- **jaxpr engine** (:mod:`.jaxpr_checks`): trace a function with
  abstract avals on any backend and walk the closed jaxpr for donation
  races, retrace hazards, collective-axis mismatches against the live
  ``parallel_state`` mesh, and Pallas BlockSpec tiling/VMEM problems.
  On top of it, the **dataflow engine** (:mod:`.dataflow`) runs a
  forward abstract interpretation (dtype/cast/taint lattice) powering
  the **precision-flow checks** (:mod:`.precision_checks`):
  low-precision accumulation, master-weight discipline, unsafe exp,
  cast churn, loss-scale bypass. The **sharding engine**
  (:mod:`.sharding_flow`) runs the placement analog (PartitionSpec /
  distinctness lattice + liveness walk) powering the **sharding-flow
  checks** (:mod:`.sharding_checks`): implicit reshards, replicated
  large inputs, psum→slice reduce-scatter opportunities, dead
  collectives, and the per-device peak-HBM budget — plus the
  per-target comms-bytes/peak-HBM estimates bench.py reports. The
  **rank-consistency engine** (:mod:`.spmd_checks`) proves the SPMD
  contracts over the same walk: no collective under rank-divergent
  control, no rank-distinct value stored where out_specs claim
  replication, coordinated RNG, anchored host effects. The
  **checkpoint/state-flow engine** (:mod:`.state_checks`) closes the
  resume loop: a step-carry fixpoint over the train-step jaxpr proves
  every live state leaf reaches the checkpoint save tree, matches the
  manifest's format-2 ``state_schema``, restores without dtype
  narrowing, re-shards legally onto every elastic candidate mesh, and
  is never read after being donated on the resume path. The
  **memory-liveness engine** (:mod:`.memory_checks`) rides the same
  walk with a live-interval lattice — every value gets a birth/death
  step, donation credit, and peak-composition record — powering
  missed-donation, remat-opportunity (roofline-priced), peak-spike,
  live-range-upcast, and offload-candidate, plus the calibrated HBM
  priors (``hbm_priors.json``) the planner prunes on.
- **AST engine** (:mod:`.ast_checks`): lint driver code (apex_tpu,
  examples/, tools/, bench.py, chip_smoke.py) for host-sync
  anti-patterns — hand-rolled timed regions, host pulls and Python RNG
  inside jit, mutable defaults.

CLI: ``python -m apex_tpu.analysis`` (see :mod:`.cli`). Gate:
``tools/lint.sh`` + ``tests/run_analysis/`` with a checked-in baseline.
Docs: ``docs/analysis.md``.
"""

from apex_tpu.analysis.ast_checks import (
    AST_CHECKS,
    lint_paths,
    lint_source,
)
from apex_tpu.analysis.concurrency_checks import (
    CONCURRENCY_CHECKS,
    run_concurrency_findings,
)
from apex_tpu.analysis.findings import (
    Finding,
    load_baseline,
    new_findings,
    save_baseline,
)
from apex_tpu.analysis.jaxpr_checks import JAXPR_CHECKS, analyze_fn
from apex_tpu.analysis.memory_checks import (
    MEMORY_CHECKS,
    analyze_memory,
    analyze_memory_jaxpr,
    load_hbm_priors,
    prior_for,
)
from apex_tpu.analysis.precision_checks import (
    PRECISION_CHECKS,
    analyze_precision,
)
from apex_tpu.analysis.sharding_checks import (
    SHARDING_CHECKS,
    analyze_sharding,
    analyze_sharding_jaxpr,
)
from apex_tpu.analysis.planner import (
    PLAN_MODELS,
    Plan,
    PlanError,
    plan,
)
from apex_tpu.analysis.spmd_checks import (
    SPMD_CHECKS,
    analyze_spmd,
)
from apex_tpu.analysis.state_checks import (
    STATE_CHECKS,
    analyze_state,
)
from apex_tpu.analysis.targets import (
    TARGETS,
    run_memory_findings,
    run_precision_findings,
    run_sharding_findings,
    run_spmd_findings,
    run_state_findings,
    run_targets,
)

__all__ = [
    "AST_CHECKS", "CONCURRENCY_CHECKS", "Finding", "JAXPR_CHECKS",
    "MEMORY_CHECKS",
    "PLAN_MODELS",
    "PRECISION_CHECKS", "Plan", "PlanError",
    "SHARDING_CHECKS", "SPMD_CHECKS", "STATE_CHECKS", "TARGETS",
    "analyze_fn",
    "analyze_memory", "analyze_memory_jaxpr",
    "analyze_precision",
    "analyze_sharding", "analyze_sharding_jaxpr", "analyze_spmd",
    "analyze_state",
    "lint_paths", "lint_source", "load_baseline", "load_hbm_priors",
    "new_findings", "plan", "prior_for", "run_concurrency_findings",
    "run_memory_findings",
    "run_precision_findings",
    "run_sharding_findings", "run_spmd_findings", "run_state_findings",
    "run_targets",
    "save_baseline",
]
