"""Deterministic fault injection (ISSUE 5 tentpole piece 1).

A :class:`FaultPlan` is a seeded schedule of simulated failures — the
kinds a preemptible TPU fleet actually produces:

- ``preempt``      a maintenance-event/SIGTERM-style preemption signal;
- ``ckpt_torn``    a checkpoint write killed after the data, before the
                   commit marker (the classic torn write);
- ``ckpt_enospc``  a checkpoint write refused at open (disk full);
- ``step_exc``     a transient exception out of the train step (the
                   flaky-collective / transient-RPC class);
- ``nan_grads``    a NaN/overflow storm poisoning the step's output;
- ``stall``        a step that hangs far past its normal duration (a
                   wedged collective or a lost device): the loop sleeps
                   ``stall_s`` inside the step, which is what the
                   observability flight recorder's watchdog exists to
                   catch (docs/profiling.md);
- ``oom``          a step that dies RESOURCE_EXHAUSTED (an allocation
                   the device cannot satisfy): raises
                   :class:`InjectedOom`, whose message is shaped like
                   the real XLA string so the memory tier's OOM
                   forensics (parse + ``memrec_*.json`` + the
                   ``TrainAborted.report["memory"]`` verdict) are
                   chaos-testable on CPU (docs/observability.md).

Faults fire at fixed steps (``kind@7``) or at seeded per-step draws
(``kind~0.05``); both are fully deterministic in (seed, kind, step), so
a chaos run is reproducible bit-for-bit. Each planned fault fires *once
per process* (:meth:`FaultPlan.should_fire` spends it) — replayed steps
after a rollback see a healthy world, exactly like a transient hardware
fault, and a restarted process that resumed past the fault's step never
re-draws it.

Checkpoint faults are injected through
:func:`inject_checkpoint_failures`, a context manager that arms
``apex_tpu.checkpoint``'s module-level fault hook — any test or bench
run becomes a chaos run without code changes (``bench.py`` wires it to
the ``APEX_TPU_FAULT_PLAN`` env var).
"""

from __future__ import annotations

import contextlib
import errno
import random
from typing import Optional

__all__ = [
    "KINDS", "FaultInjected", "TornWrite", "DiskFull",
    "TransientStepError", "InjectedOom", "FaultPlan", "corrupt_tree",
    "inject_checkpoint_failures",
]

KINDS = ("preempt", "ckpt_torn", "ckpt_enospc", "step_exc", "nan_grads",
         "stall", "oom")


class FaultInjected(Exception):
    """Base of every injected fault (so tests can tell simulated
    failures from real ones)."""


class TornWrite(FaultInjected, OSError):
    """A checkpoint write killed between data and commit marker."""


class DiskFull(FaultInjected, OSError):
    """An injected ENOSPC at checkpoint-write open."""

    def __init__(self, path: str):
        super().__init__(errno.ENOSPC,
                         "injected: no space left on device", path)


class TransientStepError(FaultInjected):
    """A transient train-step failure (retryable by design)."""


#: the simulated allocation an injected OOM asks for (1 GiB — big
#: enough to be unmistakably an allocation, stable for chaos asserts).
INJECTED_OOM_BYTES = 1 << 30


class InjectedOom(FaultInjected, RuntimeError):
    """A simulated RESOURCE_EXHAUSTED step death. The message mirrors
    the real XLA string so ``observability.memory.oom``'s classifier
    AND parser see it exactly like the production failure."""

    def __init__(self, step: int,
                 requested_bytes: int = INJECTED_OOM_BYTES):
        super().__init__(
            f"RESOURCE_EXHAUSTED: Out of memory while trying to "
            f"allocate {int(requested_bytes)} bytes. "
            f"(injected oom fault at step {step})")
        self.step = step
        self.requested_bytes = int(requested_bytes)


class FaultPlan:
    """A seeded, deterministic fault schedule.

    ``steps``: {kind: set of step indices} for fixed firings;
    ``probs``: {kind: p} for per-step seeded draws. Query with
    :meth:`should_fire` (spends the fault for this process) or
    :meth:`scheduled` (pure read).
    """

    def __init__(self, seed: int = 0, steps: Optional[dict] = None,
                 probs: Optional[dict] = None):
        self.seed = int(seed)
        self._steps = {k: frozenset(int(s) for s in v)
                       for k, v in (steps or {}).items()}
        self._probs = {k: float(p) for k, p in (probs or {}).items()}
        for kind in list(self._steps) + list(self._probs):
            if kind not in KINDS:
                raise ValueError(
                    f"unknown fault kind {kind!r}; valid: {list(KINDS)}")
        for kind, p in self._probs.items():
            if not 0.0 <= p <= 1.0:
                raise ValueError(
                    f"fault prob for {kind!r} must be in [0, 1], got {p}")
        self._spent: set = set()

    # ------------------------------------------------------------ spec

    @classmethod
    def parse(cls, text: str) -> "FaultPlan":
        """Parse a compact spec: comma-separated tokens of ``seed=N``,
        ``kind@step`` (multiple steps join with ``+``: ``preempt@4+9``)
        and ``kind~prob`` (seeded per-step draw). Example::

            "seed=3,preempt@12,ckpt_torn@4,step_exc~0.02"
        """
        seed, steps, probs = 0, {}, {}
        for token in (text or "").split(","):
            token = token.strip()
            if not token:
                continue
            if token.startswith("seed="):
                seed = int(token[5:])
            elif "@" in token:
                kind, _, at = token.partition("@")
                try:
                    fired = {int(s) for s in at.split("+")}
                except ValueError:
                    raise ValueError(
                        f"bad fault step list in token {token!r}")
                steps.setdefault(kind, set()).update(fired)
            elif "~" in token:
                kind, _, p = token.partition("~")
                probs[kind] = float(p)
            else:
                raise ValueError(
                    f"bad fault token {token!r}: expected seed=N, "
                    f"kind@step[+step...], or kind~prob")
        return cls(seed=seed, steps=steps, probs=probs)

    def spec(self) -> str:
        """Canonical spec string (parse(spec()) round-trips)."""
        parts = [f"seed={self.seed}"]
        for kind in KINDS:
            if kind in self._steps and self._steps[kind]:
                at = "+".join(str(s) for s in sorted(self._steps[kind]))
                parts.append(f"{kind}@{at}")
            if kind in self._probs:
                parts.append(f"{kind}~{self._probs[kind]}")
        return ",".join(parts)

    def __repr__(self):
        return f"FaultPlan({self.spec()!r})"

    # ----------------------------------------------------------- draws

    def scheduled(self, kind: str, step: int) -> bool:
        """Pure read: does the plan place ``kind`` at ``step``?
        Probabilistic kinds draw deterministically from
        (seed, kind, step) — any process asking gets the same answer."""
        if step in self._steps.get(kind, ()):
            return True
        p = self._probs.get(kind)
        if p is None:
            return False
        return random.Random(f"{self.seed}:{kind}:{step}").random() < p

    def should_fire(self, kind: str, step: int, spend: bool = True) -> bool:
        """Scheduled AND not already fired this process. ``spend=True``
        marks it fired — a retry/rollback replay of the same step sees
        the fault as past, like a real transient."""
        if (kind, step) in self._spent or not self.scheduled(kind, step):
            return False
        if spend:
            self._spent.add((kind, step))
        return True

    def faults_at(self, step: int) -> tuple:
        """All kinds scheduled at ``step`` (pure read)."""
        return tuple(k for k in KINDS if self.scheduled(k, step))

    def reset(self) -> None:
        """Forget spent faults (a fresh process would)."""
        self._spent.clear()


def corrupt_tree(tree):
    """NaN-fill every inexact leaf — the injected 'numeric storm'.
    Integer/bool leaves (step counters, rng keys) pass through."""
    import jax
    import jax.numpy as jnp

    def poison(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.inexact):
            return jnp.full_like(leaf, jnp.nan)
        return leaf

    return jax.tree_util.tree_map(poison, tree)


def _count(registry, kind: str) -> None:
    reg = registry
    if reg is None:
        from apex_tpu.observability import get_registry
        reg = get_registry()
    reg.counter("resilience/faults_injected", kind=kind).inc()


@contextlib.contextmanager
def inject_checkpoint_failures(plan: FaultPlan, registry=None):
    """Arm ``apex_tpu.checkpoint``'s fault hook with this plan's
    ``ckpt_torn`` / ``ckpt_enospc`` schedule. Saves without a step index
    (plain ``save_checkpoint(path, state)``) key as step ``-1``."""
    from apex_tpu import checkpoint as ckpt

    def hook(stage, step, path):
        s = -1 if step is None else int(step)
        if stage == "pre_write" and plan.should_fire("ckpt_enospc", s):
            _count(registry, "ckpt_enospc")
            raise DiskFull(path)
        if stage == "pre_commit" and plan.should_fire("ckpt_torn", s):
            _count(registry, "ckpt_torn")
            raise TornWrite(
                f"injected: write of {path} killed before commit marker")

    prev = ckpt._FAULT_HOOK
    ckpt._FAULT_HOOK = hook
    try:
        yield plan
    finally:
        ckpt._FAULT_HOOK = prev
