"""apex_tpu.observability.numerics — the numerics observability tier
(ISSUE 9).

The stack could already time, trace and profile every step (ISSUEs
2+7); this package makes it numerically SIGHTED:

- :mod:`~apex_tpu.observability.numerics.stats` — jit-safe
  ``tensor_stats(tree)``: amax / l2 / underflow-fraction /
  zero-fraction / finite-flag for a whole pytree in one fused
  on-device reduction, pulled to host only on the
  :class:`StatsCollector`'s decimated cadence (one fetch per pull,
  never a per-tensor wait);
- :mod:`~apex_tpu.observability.numerics.history` —
  :class:`AmaxHistory` rings, the fp8 delayed-scaling primitive
  (ROADMAP item 5's substrate); ring state is a pytree that
  checkpoints bit-identical through ``checkpoint.py``'s atomic
  manifest;
- :mod:`~apex_tpu.observability.numerics.nan_probe` — NaN/Inf
  provenance: replay a failing step's jaxpr under the unified
  interpreter's non-finite taint lattice
  (``analysis.interp.NonFiniteLattice``) and name the first offending
  primitive + source location (or the poisoned input tensor paths);
- :mod:`~apex_tpu.observability.numerics.health` —
  :class:`HealthMonitor`: grad-norm-spike, loss-plateau/spike and
  scaler-overflow-streak detectors emitting the ``numerics/*``
  counter family.

Consumers: ``StepReporter`` records carry a ``numerics`` block,
``ResilientTrainLoop`` attaches probe provenance to rollback events
and ``TrainAborted`` reports, the amp scaler's ``report()`` feeds the
streak detector, bench.py emits a ``numerics`` object (stats-pass
overhead budgeted <2% of step time), and
``tools/metrics_report.py --compare`` gates finite→non-finite flips
and >10x grad-norm p50 jumps. Docs: ``docs/observability.md``.
"""

from apex_tpu.observability.numerics.health import (  # noqa: F401
    HealthMonitor,
)
from apex_tpu.observability.numerics.history import (  # noqa: F401
    F8_E4M3_MAX,
    F8_E5M2_MAX,
    AmaxHistory,
    AmaxHistoryState,
)
from apex_tpu.observability.numerics.nan_probe import (  # noqa: F401
    Provenance,
    probe_fn,
    probe_tree,
    step_provenance,
)
from apex_tpu.observability.numerics.stats import (  # noqa: F401
    TENSOR_STAT_FIELDS,
    StatsCollector,
    TreeStats,
    host_tensor_stats,
    leaf_paths,
    nonfinite_paths,
    summarize_stats,
    tensor_stats,
    tree_paths,
)

__all__ = [
    "TENSOR_STAT_FIELDS", "TreeStats", "tensor_stats",
    "host_tensor_stats", "leaf_paths", "tree_paths",
    "nonfinite_paths", "summarize_stats", "StatsCollector",
    "AmaxHistory", "AmaxHistoryState", "F8_E4M3_MAX", "F8_E5M2_MAX",
    "Provenance", "probe_fn", "probe_tree", "step_provenance",
    "HealthMonitor",
]
