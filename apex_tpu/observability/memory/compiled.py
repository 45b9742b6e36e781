"""Per-executable static memory view from XLA (ISSUE 15 tentpole
piece 2).

XLA already knows exactly what every compiled program will allocate —
``compiled.memory_analysis()`` reports argument / output / temp /
generated-code bytes per executable — but nothing in the stack ever
read it. :class:`CompiledMemoryCapture` hooks the PR 2 recompile
listener so every jitted-fn compile records that static view into the
registry:

- the listener turns each ``jax.monitoring`` backend-compile duration
  event — fired *after* the executable exists — into a named
  ``"compile"`` notification followed by ``"backend_compile"``; the
  capture remembers the pending name on the first and sweeps
  ``client.live_executables()`` for new executables on the second,
  attributing their ``get_compiled_memory_stats()`` to the function
  that just compiled;
- :meth:`CompiledMemoryCapture.capture` is the explicit AOT path
  (``jit(fn).lower(*args).compile()`` + record) the calibration tier
  uses for programs it builds itself.

Per function the capture keeps the LATEST stats plus a compile count;
gauges land as ``memory/compiled_total_bytes{fn=}`` so the
biggest-executable view rides every metrics dump, and the full table
rides ``MemoryMonitor.dump`` / ``memrec_*.json`` OOM artifacts.

jax-lazy like the rest of the package; a failed sweep degrades to a
counter, never an exception in the monitoring hook it rides.
"""

from __future__ import annotations

import threading
from typing import Optional

__all__ = [
    "memory_analysis_fields", "CompiledMemoryCapture",
    "install_compiled_capture", "uninstall_compiled_capture",
    "current_capture",
]

#: the CompiledMemoryStats fields recorded per executable, in table
#: order ("alias" bytes are donation credit: argument bytes re-used as
#: outputs).
COMPILED_STAT_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


def memory_analysis_fields(analysis) -> "dict | None":
    """A ``compiled.memory_analysis()`` / ``get_compiled_memory_stats``
    result as a plain dict (+ the derived ``total_bytes`` = argument +
    output + temp − alias, the executable's device footprint). None
    when the backend returned nothing."""
    if analysis is None:
        return None
    out = {}
    for attr, key in COMPILED_STAT_FIELDS:
        value = getattr(analysis, attr, None)
        if value is None:
            return None
        out[key] = int(value)
    out["total_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                          + out["temp_bytes"] - out["alias_bytes"])
    return out


class CompiledMemoryCapture:
    """Collects per-executable XLA memory stats; see module doc.

    Thread-safe: the recompile listener's observers fire from whatever
    thread compiled.
    """

    def __init__(self, registry=None):
        self._registry = registry
        self._lock = threading.Lock()
        self._by_fn: dict = {}
        # executables are keyed by wrapper id(): jaxlib exposes no
        # stable fingerprint/name and LoadedExecutable is not
        # weakref-able. The wrapper objects ARE stable across
        # live_executables() calls (probed at install; a build that
        # hands out fresh wrappers per call would misattribute, so the
        # sweep self-disables there). Residual limitation: an id
        # reused after an executable unloads can shadow one later
        # executable's row — a missed telemetry row, never a wrong one.
        self._seen_execs: set = set()
        self._pending_fn: Optional[str] = None
        self._listener = None
        self._sweep_disabled = False

    def _reg(self):
        if self._registry is not None:
            return self._registry
        from apex_tpu.observability.registry import get_registry
        return get_registry()

    # ---------------------------------------------------------- hooks

    def install(self) -> "CompiledMemoryCapture":
        """Attach to the (installed-if-needed) recompile listener.
        Executables alive *before* install are primed as seen, so a
        pre-existing program is never misattributed to the next
        compile. Wrapper identity is probed: a jaxlib build whose
        ``live_executables()`` returns fresh wrapper objects per call
        would defeat both the priming and the new-executable diff, so
        the sweep self-disables (counted) rather than misattribute."""
        from apex_tpu.observability import recompile

        self._listener = recompile.install()
        first = self._live_executables()
        second = self._live_executables()
        if first and {id(ex) for ex in first}.isdisjoint(
                id(ex) for ex in second):
            self._sweep_disabled = True
            self._reg().counter(
                "memory/compiled_sweep_unstable_wrappers").inc()
        with self._lock:
            for ex in first + second:
                self._seen_execs.add(id(ex))
        self._listener.add_observer(self._observe)
        return self

    def uninstall(self) -> None:
        if self._listener is not None:
            self._listener.remove_observer(self._observe)
            self._listener = None

    def _observe(self, kind: str, name) -> None:
        if kind == "compile":
            with self._lock:
                self._pending_fn = name
        elif kind == "backend_compile":
            self.sweep()

    @staticmethod
    def _live_executables() -> list:
        import jax

        try:
            return list(jax.devices()[0].client.live_executables())
        except Exception:  # noqa: BLE001 — optional PJRT surface
            return []

    def sweep(self) -> int:
        """Record every live executable not yet seen, attributed to the
        last per-function compile record (``<unattributed>`` when the
        log feed degraded). Returns how many were recorded."""
        if self._sweep_disabled:
            return 0
        execs = self._live_executables()
        recorded = 0
        with self._lock:
            fn_name = self._pending_fn or "<unattributed>"
            fresh = [ex for ex in execs
                     if id(ex) not in self._seen_execs]
            for ex in fresh:
                self._seen_execs.add(id(ex))
            self._pending_fn = None
        for ex in fresh:
            try:
                fields = memory_analysis_fields(
                    ex.get_compiled_memory_stats())
            except Exception:  # noqa: BLE001 — backend without the
                # stats surface: count the miss, keep the run alive
                fields = None
            if fields is None:
                self._reg().counter(
                    "memory/compiled_stats_unavailable").inc()
                continue
            self.record(fn_name, fields)
            recorded += 1
        return recorded

    # --------------------------------------------------------- record

    def record(self, fn_name: str, fields: dict) -> dict:
        """Record one executable's stats under ``fn_name`` (latest
        wins; ``compiles`` counts how many landed)."""
        with self._lock:
            row = self._by_fn.setdefault(fn_name, {"compiles": 0})
            row["compiles"] += 1
            row.update({k: v for k, v in fields.items()})
            snapshot = dict(row)  # copied under the lock: a
            # concurrent record() of the same fn mutates `row`
        reg = self._reg()
        reg.counter("memory/compiled_captures", fn=fn_name).inc()
        reg.gauge("memory/compiled_total_bytes", fn=fn_name).set(
            fields["total_bytes"])
        return snapshot

    def capture(self, fn, *args, name: Optional[str] = None,
                donate_argnums=(), **kwargs):
        """AOT-compile ``fn(*args, **kwargs)`` and record its memory
        analysis under ``name``; returns ``(compiled, fields)``. The
        explicit path for programs the runtime never dispatches (the
        calibration tier's sharding-target traces)."""
        import jax

        name = name or getattr(fn, "__name__", "fn")
        compiled = jax.jit(fn, donate_argnums=donate_argnums).lower(
            *args, **kwargs).compile()
        fields = memory_analysis_fields(compiled.memory_analysis())
        if fields is not None:
            self.record(name, fields)
        return compiled, fields

    # ----------------------------------------------------------- read

    def snapshot(self) -> dict:
        """{fn name: {compiles, argument/output/temp/alias/
        generated_code/total bytes}} — the per-executable table."""
        with self._lock:
            return {name: dict(row)
                    for name, row in sorted(self._by_fn.items())}


# ------------------------------------------------------ process default

_CURRENT: "CompiledMemoryCapture | None" = None
_CURRENT_LOCK = threading.Lock()


def install_compiled_capture(registry=None) -> CompiledMemoryCapture:
    """Install (or return the already-installed) process capture —
    idempotent, like ``recompile.install``."""
    global _CURRENT
    with _CURRENT_LOCK:
        if _CURRENT is None:
            _CURRENT = CompiledMemoryCapture(registry=registry).install()
        elif registry is not None:
            _CURRENT._registry = registry
        return _CURRENT


def uninstall_compiled_capture() -> None:
    """Detach the process capture (its table stays readable)."""
    global _CURRENT
    with _CURRENT_LOCK:
        if _CURRENT is not None:
            _CURRENT.uninstall()
            _CURRENT = None


def current_capture() -> "CompiledMemoryCapture | None":
    return _CURRENT
