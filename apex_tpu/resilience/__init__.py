"""apex_tpu.resilience — fault injection, preemption handling, and
auto-resume training runtime (ISSUE 5).

PRs 1–4 built the *static* safety net (lint, precision, sharding flow)
and the telemetry spine; this package is the *runtime* one: a training
job on a preemptible TPU fleet survives being killed, torn mid-write,
or numerically poisoned — and a seeded fault-injection harness proves
it deterministically on CPU.

- :mod:`~apex_tpu.resilience.faults` — :class:`FaultPlan`: seeded
  schedules of preemptions, torn/ENOSPC checkpoint writes, transient
  step exceptions and NaN storms; injectors are context managers.
- :mod:`~apex_tpu.resilience.retry` — :class:`Policy` /
  :class:`Deadline`: exponential backoff + jitter with attempt,
  per-exception-class and wall-clock budgets; every retry/give-up is a
  ``resilience/*`` counter.
- :mod:`~apex_tpu.resilience.preemption` —
  :class:`PreemptionWatcher`: SIGTERM + pluggable sensors behind one
  thread-safe flag; :data:`EXIT_PREEMPTED` (75) is the resumable exit
  code.
- :mod:`~apex_tpu.resilience.loop` — :class:`ResilientTrainLoop`:
  auto-resume from the newest *valid* checkpoint, periodic + emergency
  saves, amp-overflow skip integration, and the skip → rollback →
  abort degradation ladder.

See docs/resilience.md for the fault kinds, cookbook, exit-code
contract and resume guarantees.
"""

from apex_tpu.resilience.faults import (  # noqa: F401
    KINDS,
    DiskFull,
    FaultInjected,
    FaultPlan,
    TornWrite,
    TransientStepError,
    corrupt_tree,
    inject_checkpoint_failures,
)
from apex_tpu.resilience.loop import (  # noqa: F401
    Preempted,
    ResilientTrainLoop,
    TrainAborted,
    chaos_probe,
)
from apex_tpu.resilience.preemption import (  # noqa: F401
    EXIT_PREEMPTED,
    PreemptionWatcher,
    env_sensor,
    file_sensor,
)
from apex_tpu.resilience.retry import (  # noqa: F401
    DEFAULT_RETRYABLE,
    Deadline,
    Policy,
)

__all__ = [
    "KINDS", "FaultPlan", "FaultInjected", "TornWrite", "DiskFull",
    "TransientStepError", "corrupt_tree", "inject_checkpoint_failures",
    "Policy", "Deadline", "DEFAULT_RETRYABLE",
    "PreemptionWatcher", "env_sensor", "file_sensor", "EXIT_PREEMPTED",
    "ResilientTrainLoop", "Preempted", "TrainAborted", "chaos_probe",
]
