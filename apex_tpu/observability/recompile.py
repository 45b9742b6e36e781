"""Runtime recompile/retrace accounting (ISSUE 2 tentpole piece 3).

``apex_tpu.analysis`` lints recompile *hazards* statically (unhashable
static args, closure captures); this module counts what actually
happened at runtime and turns the count into a budget a bench run can
fail on. One feed, installed by :func:`install`: ``jax.monitoring``
duration events (``/jax/core/compile/*``). Each carries the jitted
function's name as ``fun_name`` (``"jit(train_step)"``), so the
``backend_compile_duration`` event — fired once per jit-cache miss,
whether XLA compiled or the persistent cache answered — gives both the
process totals and the PER-FUNCTION compile counts (retraces =
compiles - 1). An event that arrives without a name is counted in
``unnamed_compiles``, and every per-function read then raises: a guard
that cannot see must not report zero.

Counts also land in a :class:`~apex_tpu.observability.registry
.MetricRegistry`: counter ``jax/compiles{fn=...}``, histogram
``jax/backend_compile_secs``.
"""

from __future__ import annotations

import collections
import contextlib
import re
import threading

from apex_tpu.observability.registry import get_registry

__all__ = [
    "RecompileListener", "RetraceBudgetExceeded", "install", "uninstall",
    "current", "retrace_guard",
]

_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_COMPILE = "/jax/core/compile/backend_compile_duration"
_JIT_NAME_RE = re.compile(r"^\w+\((.*)\)$")     # "jit(step)" -> "step"


class RetraceBudgetExceeded(RuntimeError):
    """A guarded region retraced more than its budget allows."""


class RecompileListener:
    """Aggregates compile activity while installed; see module doc."""

    def __init__(self, registry=None):
        self.registry = registry
        self._lock = threading.Lock()
        self.compiles_by_fn = collections.Counter()
        self.totals = collections.Counter()      # event name -> count
        self.seconds = collections.defaultdict(float)
        # compile events that carried no function name (see module doc)
        self.unnamed_compiles = 0
        # compile observers (ISSUE 15): callbacks cb(kind, name) fired
        # on every backend-compile event, first as "compile" with the
        # function's name, then as "backend_compile" — the executable
        # exists by then, the moment the memory tier sweeps
        # live_executables for its per-executable memory_analysis view
        self._observers: list = []
        self.observer_errors = 0

    # ---- feed: jax.monitoring duration events

    def _on_duration(self, name: str, secs: float, fun_name=None) -> None:
        if not name.startswith("/jax/core/compile/"):
            return
        with self._lock:
            self.totals[name] += 1
            self.seconds[name] += secs
        if name != _EV_COMPILE:
            return
        if self.registry is not None:
            self.registry.histogram("jax/backend_compile_secs").observe(secs)
        if fun_name is None:
            with self._lock:
                self.unnamed_compiles += 1
        else:
            m = _JIT_NAME_RE.match(fun_name)
            fn_name = m.group(1) if m else fun_name
            with self._lock:
                self.compiles_by_fn[fn_name] += 1
            if self.registry is not None:
                self.registry.counter("jax/compiles", fn=fn_name).inc()
            self._notify("compile", fn_name)
        self._notify("backend_compile", None)

    # ---- compile observers (ISSUE 15)

    def add_observer(self, cb) -> None:
        """Register ``cb(kind, name)`` to fire on compile activity
        (``kind`` in {"compile", "backend_compile"}); idempotent."""
        with self._lock:
            if cb not in self._observers:
                self._observers.append(cb)

    def remove_observer(self, cb) -> None:
        with self._lock:
            if cb in self._observers:
                self._observers.remove(cb)

    def _notify(self, kind: str, name) -> None:
        with self._lock:
            observers = list(self._observers)
        for cb in observers:
            try:
                cb(kind, name)
            except Exception:  # noqa: BLE001 — an observer must never
                # break the compile (or the logging filter) it rides
                with self._lock:  # += is a read-modify-write; compile
                    # records land from jax's logging + monitoring
                    # hooks on whatever thread compiled
                    self.observer_errors += 1

    # ---- read side

    def _check_named(self) -> None:
        if self.unnamed_compiles:
            raise RuntimeError(
                f"{self.unnamed_compiles} compile event(s) arrived from "
                f"jax.monitoring without a fun_name: per-function compile "
                f"counts are unavailable under this jax, so no retrace "
                f"count can be trusted")

    def compiles(self, fn: "str | None" = None):
        """Per-function compile counts (dict), or one function's count."""
        with self._lock:
            self._check_named()
            if fn is not None:
                return self.compiles_by_fn.get(fn, 0)
            return dict(self.compiles_by_fn)

    def retraces(self, fn: "str | None" = None):
        """Compiles beyond the first per function — the recompiles a
        steady-state training loop should never see."""
        with self._lock:
            self._check_named()
            table = {name: n - 1 for name, n in self.compiles_by_fn.items()
                     if n > 1}
            if fn is not None:
                return table.get(fn, 0)
            return table

    def total_retraces(self) -> int:
        return sum(self.retraces().values())

    def backend_compiles(self) -> int:
        """Process-total backend compiles from jax.monitoring (includes
        jax-internal helper jits the per-function table may not name)."""
        with self._lock:
            return self.totals[_EV_COMPILE]

    def snapshot(self) -> dict:
        with self._lock:
            self._check_named()
            return {
                "compiles_by_fn": dict(self.compiles_by_fn),
                "retraces_by_fn": {n: c - 1 for n, c in
                                   self.compiles_by_fn.items() if c > 1},
                "backend_compiles": self.totals[_EV_COMPILE],
                "backend_compile_secs": round(
                    self.seconds[_EV_COMPILE], 3),
                "trace_events": self.totals[_EV_TRACE],
            }


class _State:
    def __init__(self):
        self.listener: "RecompileListener | None" = None
        self.monitoring_registered = False
        self.lock = threading.Lock()


_STATE = _State()


def _monitoring_callback(name, secs, fun_name=None, **_kw):
    listener = _STATE.listener
    if listener is not None:
        listener._on_duration(name, secs, fun_name)


def install(registry=None) -> RecompileListener:
    """Install (or return the already-installed) process listener.

    Idempotent: repeated calls return the same listener (updating its
    registry only if one is passed). ``jax.monitoring`` has no
    single-listener unregister, so the monitoring hook is registered
    once per process and routed through the module state — after
    :func:`uninstall` it goes inert rather than away.
    """
    import jax

    with _STATE.lock:
        if _STATE.listener is not None:
            if registry is not None:
                _STATE.listener.registry = registry
            return _STATE.listener
        listener = RecompileListener(
            registry if registry is not None else get_registry())
        if not _STATE.monitoring_registered:
            jax.monitoring.register_event_duration_secs_listener(
                _monitoring_callback)
            _STATE.monitoring_registered = True
        _STATE.listener = listener
        return listener


def uninstall() -> None:
    """Deactivate the monitoring hook. Counts on the returned-by-install
    listener stop growing but remain readable."""
    with _STATE.lock:
        _STATE.listener = None


def current() -> "RecompileListener | None":
    return _STATE.listener


@contextlib.contextmanager
def retrace_guard(budget: int = 0, registry=None, fns=None):
    """Fail a region that retraces more than ``budget`` times.

    The runtime teeth behind the analysis subsystem's static
    "recompile hazard" lint: wrap a bench/training loop and any
    steady-state retrace beyond the budget raises
    :class:`RetraceBudgetExceeded` naming the offending functions.
    First-compiles are free — only compiles of a function already
    compiled once inside OR before the region count.

        with retrace_guard(budget=0):
            for batch in data:
                train_step(params, batch)   # must not retrace

    ``fns``: optional iterable of jitted-function names to watch; other
    names are ignored. Use it when the region also BUILDS inputs —
    jax's internal helper jits (``broadcast_in_dim``, ...) recompile per
    fresh shape and would otherwise spend the budget on noise.
    """
    listener = install(registry=registry)
    watch = None if fns is None else set(fns)
    before = listener.compiles()
    yield listener
    after = listener.compiles()
    retraced = {}
    for fn_name, n in after.items():
        if watch is not None and fn_name not in watch:
            continue
        prior = before.get(fn_name, 0)
        # compiles in-region beyond the function's first-ever compile
        in_region = n - prior
        free = 1 if prior == 0 else 0
        if in_region - free > 0:
            retraced[fn_name] = in_region - free
    total = sum(retraced.values())
    if registry is not None or listener.registry is not None:
        reg = registry if registry is not None else listener.registry
        reg.counter("jax/guarded_retraces").inc(total)
    if total > budget:
        raise RetraceBudgetExceeded(
            f"{total} retrace(s) exceed budget {budget}: " + ", ".join(
                f"{name} x{n}" for name, n in sorted(retraced.items())))
