"""apex_tpu.observability.memory — the memory observability tier
(ISSUE 15).

The stack could already see time (spans, flight recorder), numerics
(stats, NaN provenance) and the fleet (skew, desync) — this package
makes it memory-SIGHTED, and grounds the sharding cost model in
measurement:

- :mod:`~apex_tpu.observability.memory.hbm` —
  :class:`MemoryMonitor`: decimated live-bytes snapshots
  (``jax.live_arrays()`` per-device attribution +
  ``device.memory_stats()`` where reported), per-step high-watermarks,
  top-k largest buffers, the ``memory/*`` gauge family, and
  identity-stamped ``rank_path``-suffixed dumps;
- :mod:`~apex_tpu.observability.memory.compiled` —
  :class:`CompiledMemoryCapture`: hooks the PR 2 recompile listener so
  every jitted-fn compile records XLA's ``memory_analysis()``
  (argument/output/temp/generated-code bytes) — a per-executable
  static memory view;
- :mod:`~apex_tpu.observability.memory.calibrate` —
  :func:`calibrate_targets`: re-compile the registered sharding-flow
  targets and publish ``memory/hbm_calibration_ratio{target=}`` =
  XLA-measured / estimator-modeled peak, so cost-model drift becomes a
  gated regression (``tools/metrics_report.py --compare``) instead of
  silent planner mis-pruning;
- :mod:`~apex_tpu.observability.memory.oom` — OOM forensics:
  RESOURCE_EXHAUSTED parsing, the ``memrec_*.json`` post-mortem
  artifact, and the verdict
  :class:`~apex_tpu.resilience.ResilientTrainLoop` attaches to
  ``rollback`` events and ``TrainAborted.report["memory"]`` (the
  ``oom`` fault kind makes the path chaos-testable).

Consumers: ``StepReporter`` records carry a ``memory`` block, flight
records grow a ``memory`` section, ``pallas_config.device_hbm_bytes``
prefers the live ``bytes_limit``, bench.py emits the ``memory`` JSON
object (snapshot cadence derived to keep overhead <2% of step time),
and ``examples/llama_train.py`` runs the monitor.
Docs: ``docs/observability.md`` ("Memory telemetry").

This package (plus ``ops/pallas_config.py``) is the sanctioned home of
raw memory introspection — direct ``jax.live_arrays()`` /
``.memory_stats()`` / ``device_memory_profile()`` calls elsewhere are
linted (``raw-memory-introspection``).
"""

from apex_tpu.observability.memory.calibrate import (  # noqa: F401
    DEFAULT_CALIBRATION_TARGETS,
    calibrate_targets,
)
from apex_tpu.observability.memory.compiled import (  # noqa: F401
    CompiledMemoryCapture,
    current_capture,
    install_compiled_capture,
    memory_analysis_fields,
    uninstall_compiled_capture,
)
from apex_tpu.observability.memory.hbm import (  # noqa: F401
    MEMORY_SCHEMA_VERSION,
    MemoryMonitor,
    active_monitor,
    device_live_bytes,
    device_memory_stats,
    flight_section,
    live_buffer_records,
    memory_snapshot,
    set_active_monitor,
)
from apex_tpu.observability.memory.oom import (  # noqa: F401
    OOM_MARKERS,
    dump_memrec,
    is_oom_error,
    oom_forensics,
    parse_resource_exhausted,
)

__all__ = [
    "MEMORY_SCHEMA_VERSION", "MemoryMonitor", "memory_snapshot",
    "live_buffer_records", "device_live_bytes", "device_memory_stats",
    "active_monitor", "set_active_monitor", "flight_section",
    "CompiledMemoryCapture", "install_compiled_capture",
    "uninstall_compiled_capture", "current_capture",
    "memory_analysis_fields",
    "DEFAULT_CALIBRATION_TARGETS", "calibrate_targets",
    "OOM_MARKERS", "is_oom_error", "parse_resource_exhausted",
    "dump_memrec", "oom_forensics",
]
