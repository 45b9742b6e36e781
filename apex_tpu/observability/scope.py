"""Named trace scopes — one helper for both timelines.

``jax.profiler.TraceAnnotation`` marks the HOST timeline (visible while
the Python frame is open: dispatch, schedule phases, timer brackets).
``jax.named_scope`` attaches the name to the HLO metadata of every op
built inside it, so the DEVICE timeline of the next on-silicon capture
carries the same names — that is what finally lets ``trace_report.py``
attribute per-kernel time to "fused_adam/flat/pallas" vs
"fused_adam/flat/xla" instead of anonymous fusions (the per-kernel race
table the ISSUE wants).

:func:`scope` enters both. Inside traced code the annotation half only
brackets trace time (harmless); the named_scope half is the one that
survives into the compiled program. Without an active profiler both are
no-ops costing two context-manager enters.

jax is imported lazily so ``apex_tpu.observability`` stays importable
in backend-free processes (the bench launcher, the report CLI).
"""

from __future__ import annotations

import contextlib

__all__ = ["scope", "host_annotation", "annotate"]

_jax = None


def _get_jax():
    global _jax
    if _jax is None:
        import jax
        _jax = jax
    return _jax


@contextlib.contextmanager
def scope(name: str):
    """Open a named region on both the host and device timelines."""
    jax = _get_jax()
    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield


def host_annotation(name: str):
    """The host half of :func:`scope` alone, for code that builds no
    HLO."""
    return _get_jax().profiler.TraceAnnotation(name)


def annotate(name: str):
    """Decorator form: every call to the wrapped fn runs under
    :func:`scope(name)` (default: the function's qualname)."""
    def deco(fn):
        import functools

        label = name or getattr(fn, "__qualname__", repr(fn))

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with scope(label):
                return fn(*args, **kwargs)
        return wrapped
    return deco
