"""apex_tpu.observability — unified runtime telemetry (ISSUE 2).

The single layer the whole stack reports through:

- :mod:`~apex_tpu.observability.registry` — thread-safe metrics
  (counter/gauge/histogram/device-synced timer), structured events,
  JSONL export and the merge/summary reader;
- :mod:`~apex_tpu.observability.scope` — named trace scopes on both the
  host (``TraceAnnotation``) and device (``named_scope`` → HLO metadata)
  timelines, wired into the pipeline/tensor-parallel/DDP/optimizer hot
  paths;
- :mod:`~apex_tpu.observability.recompile` — runtime compile/retrace
  accounting via ``jax.monitoring`` compile events, with a
  budget guard that fails a run on steady-state retraces;
- :mod:`~apex_tpu.observability.step_report` — per-training-step
  records (step time, tokens/s, MFU, loss scale, overflow count);
- :mod:`~apex_tpu.observability.profiling` — span tracing (ring
  buffer + Perfetto export), per-step phase attribution, xplane
  device attribution, and the stall flight recorder (ISSUE 7);
- :mod:`~apex_tpu.observability.numerics` — on-device tensor stats
  (fused amax/l2/underflow/finite pass, decimated host pulls), amax
  history rings (the fp8 delayed-scaling substrate), NaN/Inf
  provenance via jaxpr replay, and training-health detectors
  (ISSUE 9);
- :mod:`~apex_tpu.observability.fleet` — cross-rank telemetry
  (ISSUE 12): rank identity + automatic ``.rank{i}`` artifact
  suffixing, the grad-sync barrier-wait probe + straggler detector,
  on-device desync fingerprints, and the fleet merge readers
  (metrics shards and flight records);
- :mod:`~apex_tpu.observability.memory` — the memory tier (ISSUE 15):
  live HBM telemetry (decimated live-bytes snapshots, watermarks,
  top-k buffers), per-executable compiled memory stats off the
  recompile listener, measured-vs-modeled HBM calibration of the
  sharding cost model, and OOM forensics (``memrec_*.json``);
- :mod:`~apex_tpu.observability.goodput` — the run ledger + goodput
  accounting tier (ISSUE 17): every artifact family normalized into
  one ordered, rank-aware timeline, wall-clock classified into causes
  (productive step / init / compile / data wait / checkpoint / stall /
  preempt drain / restart / rollback replay), and the ``goodput/*``
  gauge family (ratio, lost-seconds-by-cause, badput top-3, fleet
  min); event names are pinned by the
  :mod:`~apex_tpu.observability.events` catalog;
- ``python -m apex_tpu.observability report <metrics.jsonl>`` — the
  summary CLI (also ``tools/metrics_report.py``); ``... trace <run>``
  exports a span dump or xplane capture as Perfetto JSON;
  ``... fleet <shards>`` joins per-rank shards into one fleet view;
  ``... goodput <run>`` renders the run-ledger accounting table.

The modules themselves import jax lazily and never force backend init —
but importing them through the ``apex_tpu`` package still runs the
parent ``__init__`` (which imports jax). Truly backend-free processes
(the bench *launcher*) therefore write the JSONL event format inline
rather than importing this package; the record shape is pinned by
:func:`~apex_tpu.observability.registry.append_event`.
"""

from apex_tpu.observability.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    Timer,
    append_event,
    get_registry,
    read_jsonl,
    set_registry,
    summarize,
)
from apex_tpu.observability.recompile import (  # noqa: F401
    RecompileListener,
    RetraceBudgetExceeded,
    retrace_guard,
)
from apex_tpu.observability.recompile import (  # noqa: F401
    install as install_recompile_listener,
)
from apex_tpu.observability.recompile import (  # noqa: F401
    uninstall as uninstall_recompile_listener,
)
from apex_tpu.observability.profiling import (  # noqa: F401
    FlightRecorder,
    SpanTracer,
    StepPhases,
    get_tracer,
    host_span,
    set_tracer,
    span,
)
from apex_tpu.observability import numerics  # noqa: F401
from apex_tpu.observability.numerics import (  # noqa: F401
    AmaxHistory,
    HealthMonitor,
    StatsCollector,
)
from apex_tpu.observability import memory  # noqa: F401
from apex_tpu.observability.memory import (  # noqa: F401
    CompiledMemoryCapture,
    MemoryMonitor,
    calibrate_targets,
    install_compiled_capture,
)
from apex_tpu.observability import fleet  # noqa: F401
from apex_tpu.observability.fleet import (  # noqa: F401
    DesyncDetector,
    StragglerDetector,
    merge_fleet,
    merge_flight_records,
    process_identity,
    rank_path,
)
from apex_tpu.observability import goodput  # noqa: F401
from apex_tpu.observability.goodput import (  # noqa: F401
    RunLedger,
    ledger_from_records,
)
from apex_tpu.observability.goodput import (  # noqa: F401
    account as account_goodput,
)
from apex_tpu.observability.events import (  # noqa: F401
    EVENT_CATALOG,
    GOODPUT_CRITICAL,
)
from apex_tpu.observability.scope import annotate, scope  # noqa: F401
from apex_tpu.observability.step_report import (  # noqa: F401
    STEP_RECORD_FIELDS,
    StepReporter,
    peak_flops,
    transformer_step_flops,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "Timer", "MetricRegistry",
    "get_registry", "set_registry", "read_jsonl", "summarize",
    "append_event",
    "RecompileListener", "RetraceBudgetExceeded", "retrace_guard",
    "install_recompile_listener", "uninstall_recompile_listener",
    "scope", "annotate",
    "span", "host_span", "SpanTracer", "get_tracer", "set_tracer",
    "StepPhases", "FlightRecorder",
    "StepReporter", "STEP_RECORD_FIELDS", "peak_flops",
    "transformer_step_flops",
    "numerics", "StatsCollector", "AmaxHistory", "HealthMonitor",
    "memory", "MemoryMonitor", "CompiledMemoryCapture",
    "install_compiled_capture", "calibrate_targets",
    "fleet", "DesyncDetector", "StragglerDetector", "merge_fleet",
    "merge_flight_records", "process_identity", "rank_path",
    "goodput", "RunLedger", "ledger_from_records", "account_goodput",
    "EVENT_CATALOG", "GOODPUT_CRITICAL",
]
