"""apex_tpu.observability.profiling — span tracing, per-step phase
attribution and the stall flight recorder (ISSUE 7).

The profiling tier the reference ships as ``apex.pyprof``, rebuilt on
PR 2's registry/scope plumbing:

- :mod:`~apex_tpu.observability.profiling.spans` — always-on
  ring-buffer span tracer for host code (``span()``, ``host_span()``;
  code under ``jit`` keeps the bare ``scope()``), exported as
  Chrome/Perfetto trace-event JSON;
- :mod:`~apex_tpu.observability.profiling.hlo_scopes` — which phase
  and scope of the train step each compiled instruction belongs to;
- :mod:`~apex_tpu.observability.profiling.xplane` — device-side
  per-phase attribution from a ``jax.profiler`` capture (the library
  form of ``tools/trace_report.py``);
- :mod:`~apex_tpu.observability.profiling.step_phases` — host↔device
  correlation per training step: the StepReporter phase breakdown
  (host/data/compute/comms + overlap efficiency);
- :mod:`~apex_tpu.observability.profiling.flight_recorder` — stall
  watchdog + SIGQUIT post-mortem dumps.

CLI: ``python -m apex_tpu.observability trace <run>`` exports either a
span dump or an xplane capture as Perfetto-loadable JSON.

``apex_tpu/pyprof`` remains as the legacy reference-named shim; its
parse/report internals are consumed here and new code should import
from this package.
"""

from apex_tpu.observability.profiling.flight_recorder import (  # noqa: F401
    FlightRecorder,
)
from apex_tpu.observability.profiling.spans import (  # noqa: F401
    Span,
    SpanTracer,
    get_tracer,
    decode_span_payload,
    host_span,
    load_spans,
    set_tracer,
    span,
    spans_from_dicts,
    to_trace_events,
    write_chrome_trace,
)
from apex_tpu.observability.profiling.step_phases import (  # noqa: F401
    StepPhases,
    classify_span,
    compute_breakdown,
    device_phase_fields,
)
from apex_tpu.observability.profiling.xplane import (  # noqa: F401
    PHASES,
    DeviceAttribution,
    attribute_capture,
    attribute_report,
    capture_trace_events,
    phase_of,
)

__all__ = [
    "Span", "SpanTracer", "span", "host_span", "get_tracer", "set_tracer",
    "to_trace_events", "write_chrome_trace", "load_spans",
    "decode_span_payload", "spans_from_dicts",
    "StepPhases", "classify_span", "compute_breakdown",
    "device_phase_fields",
    "PHASES", "DeviceAttribution", "attribute_capture",
    "attribute_report", "capture_trace_events", "phase_of",
    "FlightRecorder",
]
