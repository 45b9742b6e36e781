"""ResilientTrainLoop (ISSUE 5 tentpole piece 4): the runtime safety
net around a user step function.

Guarantees (proved by the chaos suite in ``tests/run_resilience/``):

- **Auto-resume**: ``run`` restores from the newest *valid* checkpoint
  (commit marker + manifest, ``apex_tpu.checkpoint``), garbage-collects
  torn-write leftovers first, and falls back to the previous valid step
  when the newest one fails to load. A run preempted and restarted
  reaches **bit-identical** params to an uninterrupted run, provided
  ``step_fn(state, step)`` is deterministic in its arguments (derive
  per-step randomness with ``jax.random.fold_in(key, step)``).
- **Periodic + emergency checkpointing** through
  :class:`~apex_tpu.checkpoint.CheckpointManager` (async-capable);
  preemption forces a synchronous, retry-wrapped emergency save, then
  raises :class:`Preempted` (or exits with
  :data:`~apex_tpu.resilience.preemption.EXIT_PREEMPTED`).
- **Graceful-degradation ladder** on failure:
  1. *skip step* — an amp-scaler overflow (``metrics["overflow"]``
     truthy, the ``amp.scaled_update`` protocol) is counted and
     trusted: the scaler already kept params/opt state via its in-graph
     ``lax.cond`` skip, so a non-finite loss that step is expected;
  2. *restore last checkpoint* — non-finite state/metrics (or a step
     that kept failing through the retry policy) rolls back to the
     newest valid checkpoint and replays;
  3. *abort with a structured report* — more than ``max_rollbacks``
     rollbacks *without intervening progress* (the budget resets once a
     completed step passes the failure point) raises
     :class:`TrainAborted` carrying the full report dict (also emitted
     as a ``train_aborted`` registry event).

Every decision lands as a ``resilience/*`` counter/event in the
:mod:`apex_tpu.observability` registry.

ISSUE 9: a health failure additionally runs the numerics NaN probe —
the offending tensor paths (one fused stats pass over the bad state)
plus, when the step function traces, the first non-finite primitive
and its source location from a jaxpr replay
(:func:`apex_tpu.observability.numerics.step_provenance`). The
verdict rides every ``rollback`` event and the
:class:`TrainAborted` report's ``numerics`` block, so an injected
``nan_grads``/``corrupt_tree`` chaos fault — or the real thing — is
fully attributable from the abort artifact alone.

ISSUE 15: a step that dies RESOURCE_EXHAUSTED-shaped (the ``oom``
chaos fault, or the real thing) additionally runs the memory tier's
OOM forensics — a ``memrec_*.json`` post-mortem lands next to the
checkpoints and the compact verdict (requested bytes, largest live
buffer, watermark) rides every ``rollback`` event and the
:class:`TrainAborted` report's ``memory`` block
(:func:`apex_tpu.observability.memory.oom_forensics`;
``memory_forensics=False`` opts out, ``memory_monitor=`` pins the
watermark source).

ISSUE 12: pass ``desync_detector=`` (an
:class:`apex_tpu.observability.fleet.DesyncDetector`) and return the
step's gathered fingerprint matrix
(:func:`~apex_tpu.observability.fleet.fingerprint_gather`) in
``metrics["fleet_fingerprint"]`` — the loop checks it after every
healthy step; a cross-rank divergence is treated as a rung-2 failure
(rollback → replay → abort), with the fleet verdict — offending rank,
first divergent step, tensor path — attached to every ``rollback``
event and the :class:`TrainAborted` report's ``fleet`` block.
"""

from __future__ import annotations

import sys
import time
from typing import Any, Callable, Optional

from apex_tpu import checkpoint as ckpt
from apex_tpu.resilience import faults as faults_mod
from apex_tpu.resilience.preemption import EXIT_PREEMPTED

__all__ = ["Preempted", "TrainAborted", "ResilientTrainLoop",
           "chaos_probe", "resume_path"]


class Preempted(RuntimeError):
    """Raised after the emergency checkpoint when preemption tripped.

    ``exit_code`` is the resumable-exit contract
    (:data:`~apex_tpu.resilience.preemption.EXIT_PREEMPTED`); ``step``
    is the last COMPLETED step (resume continues at ``step + 1``);
    ``checkpoint_path`` is the emergency save (None if it failed — the
    last periodic checkpoint then covers resume, replaying the gap).
    """

    def __init__(self, step: int, checkpoint_path: Optional[str],
                 reason: str = ""):
        super().__init__(
            f"preempted after step {step}"
            + (f" ({reason})" if reason else "")
            + (f"; emergency checkpoint at {checkpoint_path}"
               if checkpoint_path else "; emergency checkpoint FAILED"))
        self.exit_code = EXIT_PREEMPTED
        self.step = step
        self.checkpoint_path = checkpoint_path
        self.reason = reason


class TrainAborted(RuntimeError):
    """The ladder's last rung: training cannot make progress.

    ``report`` is a structured dict (step, rollbacks, last error,
    resume provenance, counter snapshot) — the artifact an oncall
    actually needs, not a bare traceback."""

    def __init__(self, report: dict):
        super().__init__(f"training aborted at step {report.get('step')}: "
                         f"{report.get('reason')}")
        self.report = report


def _is_finite_number(v) -> bool:
    import math

    try:
        return math.isfinite(float(v))
    except (TypeError, ValueError):
        return True  # non-numeric metric values are not health signals


class ResilientTrainLoop:
    """Wrap ``step_fn(state, step) -> (state, metrics)`` with
    auto-resume, checkpointing, retries and the degradation ladder.

    Parameters
    ----------
    step_fn: the user step. ``state`` is any pytree (include the amp
        scaler state and anything else that must survive preemption);
        ``metrics`` is a dict — ``loss`` (and any float values) feed
        the health check, ``overflow`` marks an amp-scaler skip step.
    directory: checkpoint dir; None disables persistence (the ladder
        then degrades to "rollback to the run's starting state").
    save_every: periodic-save cadence in steps (a save also lands on
        the final step); 0 disables periodic saves.
    retry_policy: :class:`~apex_tpu.resilience.retry.Policy` wrapping
        the step call AND checkpoint I/O. None = no retries.
    fault_plan: :class:`~apex_tpu.resilience.faults.FaultPlan` — chaos
        mode. Checkpoint faults additionally need
        :func:`~apex_tpu.resilience.faults.inject_checkpoint_failures`
        armed (``run`` arms it automatically when a plan is present).
    watcher: :class:`~apex_tpu.resilience.preemption.PreemptionWatcher`
        polled after every step.
    stall_s: how long an injected ``stall`` fault sleeps inside the
        step (the hang a flight-recorder watchdog is meant to catch).
    flight_recorder: an
        :class:`apex_tpu.observability.FlightRecorder` — the loop
        brackets every step *attempt* with its
        ``step_started``/``step_finished`` pair (injected faults
        included, so a chaos ``stall`` is observed exactly like a real
        hang) and its watchdog dumps a post-mortem when one stalls.
        The loop does not install() it — callers own its lifecycle.
    validate: ``f(state, metrics, step) -> bool`` health check override.
        Default: every float metric is finite, and every
        ``check_state_every`` steps all inexact state leaves are finite
        (reduced on device, one host sync — set it to k>1 or 0 on real
        hardware if the per-step fetch matters).
    numerics_provenance: run the NaN probe on health failures (see
        module docstring). Post-mortem-path only — costs nothing on
        healthy steps; disable for step functions whose replay side
        effects are unacceptable.
    memory_monitor: an
        :class:`apex_tpu.observability.MemoryMonitor` whose watermark
        feeds the OOM verdict (default: the process's active monitor);
        ``memory_forensics=False`` disables the OOM post-mortem path
        entirely. Like the NaN probe, this costs nothing on healthy
        steps.
    auto_resume: restore from ``directory`` on :meth:`run` entry.
    exit_on_preempt: call ``sys.exit(EXIT_PREEMPTED)`` instead of
        raising :class:`Preempted` (process-boundary behavior for real
        deployments; tests keep the exception).
    on_resume: callback ``f(step)`` after a successful restore.
    """

    def __init__(self, step_fn: Callable[[Any, int], tuple], *,
                 directory: Optional[str] = None, save_every: int = 0,
                 max_to_keep: int = 3, async_save: bool = False,
                 retry_policy=None, fault_plan=None, watcher=None,
                 validate=None, check_state_every: int = 1,
                 max_rollbacks: int = 2, auto_resume: bool = True,
                 deep_validate_resume: bool = False,
                 exit_on_preempt: bool = False, on_resume=None,
                 registry=None, stall_s: float = 2.0,
                 flight_recorder=None, numerics_provenance: bool = True,
                 desync_detector=None, memory_monitor=None,
                 memory_forensics: bool = True):
        self.step_fn = step_fn
        self.directory = directory
        self.save_every = save_every
        self.retry_policy = retry_policy
        self.fault_plan = fault_plan
        self.watcher = watcher
        self.validate = validate
        self.check_state_every = check_state_every
        self.max_rollbacks = max_rollbacks
        self.auto_resume = auto_resume
        self.deep_validate_resume = deep_validate_resume
        self.exit_on_preempt = exit_on_preempt
        self.on_resume = on_resume
        self._registry = registry
        self.stall_s = float(stall_s)
        self.flight_recorder = flight_recorder
        self.numerics_provenance = numerics_provenance
        self.desync_detector = desync_detector
        self.memory_monitor = memory_monitor
        self.memory_forensics = memory_forensics
        self.manager = (ckpt.CheckpointManager(
            directory, max_to_keep=max_to_keep, async_save=async_save)
            if directory else None)
        #: step the last run() resumed from (None = cold start).
        self.resumed_from: Optional[int] = None

    # -------------------------------------------------------- plumbing

    def _reg(self):
        if self._registry is not None:
            return self._registry
        from apex_tpu.observability import get_registry
        return get_registry()

    def _call(self, fn, *args, **kwargs):
        if self.retry_policy is not None:
            return self.retry_policy.call(fn, *args, **kwargs)
        return fn(*args, **kwargs)

    # ------------------------------------------------------ checkpoints

    def _save(self, state, step: int) -> Optional[str]:
        """Periodic save; a failure (after retries) degrades to a
        counter + event — training continues on the last good save.

        ISSUE 17: the save is timed through the registry Timer
        (resilience code never reads a raw clock) and the elapsed host seconds ride the event as
        ``duration_s``, the run ledger's ``ckpt_save`` interval. With
        ``async_save`` this is the host-blocking enqueue time, which
        is exactly the wall time the training loop lost."""
        reg = self._reg()
        timer = reg.timer("resilience/ckpt_save_s")
        timer.start()
        try:
            path = self._call(self.manager.save, step, {"state": state})
        except Exception as e:  # noqa: BLE001 — degradation rung 0
            duration = timer.stop()
            reg.counter("resilience/checkpoint_failures").inc()
            reg.event("checkpoint_failed", step=step, error=repr(e)[:200],
                      duration_s=round(duration, 6))
            return None
        duration = timer.stop()
        reg.event("checkpoint_saved", step=step,
                  duration_s=round(duration, 6))
        return path

    def _emergency_save(self, state, step: int) -> Optional[str]:
        """Synchronous, retry-wrapped save issued on preemption — the
        process is about to die, so flush any in-flight async write
        first and write blocking."""
        if self.manager is None:
            return None
        reg = self._reg()
        try:
            self.manager.wait_until_finished()
        except Exception as e:  # noqa: BLE001 — the async write may be
            # the thing that is broken; the sync save below still counts
            reg.event("emergency_flush_failed", step=step,
                      error=repr(e)[:200])
        timer = reg.timer("resilience/emergency_save_s")
        timer.start()
        try:
            path = self._call(ckpt.save_checkpoint, self.directory,
                              {"state": state}, step=step)
            timer.stop()
            reg.counter("resilience/emergency_saves").inc()
            return path
        except Exception as e:  # noqa: BLE001
            duration = timer.stop()
            reg.counter("resilience/checkpoint_failures").inc()
            reg.event("emergency_save_failed", step=step,
                      error=repr(e)[:200], duration_s=round(duration, 6))
            return None

    def _resume(self, state):
        """(state, start_step): restore the newest valid checkpoint,
        walking back to older valid steps when a restore itself fails."""
        reg = self._reg()
        gc_timer = reg.timer("resilience/ckpt_gc_s")
        gc_timer.start()
        removed = ckpt.gc_partial_checkpoints(
            self.directory,
            keep=() if self.manager is None
            else ((self.manager._writer.in_flight_tmp,)
                  if self.manager._writer is not None
                  and self.manager._writer.in_flight_tmp else ()))
        gc_s = gc_timer.stop()
        if removed:
            reg.counter("resilience/gc_partial").inc(len(removed))
            reg.event("gc_partial_checkpoints",
                      removed=[p.rsplit("/", 1)[-1] for p in removed],
                      duration_s=round(gc_s, 6))
        candidates = list(reversed(ckpt.valid_steps(
            self.directory, deep=self.deep_validate_resume)))
        if not candidates:
            # no marker-bearing step at all: a dir written by a
            # pre-marker writer. Honor restore_checkpoint's legacy
            # fallback rather than silently restarting from step 0 over
            # (and then overwriting) the old progress.
            legacy = ckpt.latest_step(self.directory)
            if legacy is not None:
                candidates = [legacy]
        for step in candidates:
            restore_timer = reg.timer("resilience/ckpt_restore_s")
            restore_timer.start()
            try:
                restored = ckpt.restore_checkpoint(
                    self.directory, target={"state": state}, step=step)
            except Exception as e:  # noqa: BLE001 — fall back to the
                # previous valid step rather than dying on a bad restore
                duration = restore_timer.stop()
                reg.counter("resilience/restore_failures").inc()
                reg.event("restore_failed", step=step,
                          error=repr(e)[:200],
                          duration_s=round(duration, 6))
                continue
            duration = restore_timer.stop()
            reg.counter("resilience/resumes").inc()
            reg.event("resumed", step=step,
                      duration_s=round(duration, 6))
            self.resumed_from = step
            if self.on_resume is not None:
                self.on_resume(step)
            return restored["state"], step + 1
        return state, 0

    # ----------------------------------------------------- health check

    def _healthy(self, state, metrics, step: int) -> bool:
        if self.validate is not None:
            return bool(self.validate(state, metrics, step))
        for key, value in (metrics or {}).items():
            if key == "overflow":
                continue
            if not _is_finite_number(value):
                return False
        if self.check_state_every and step % self.check_state_every == 0:
            import jax
            import jax.numpy as jnp

            # reduce per-leaf finiteness on DEVICE, pull one scalar —
            # a per-leaf bool() would serialize the loop on host fetches
            ok = None
            for leaf in jax.tree_util.tree_leaves(state):
                if hasattr(leaf, "dtype") and jnp.issubdtype(
                        leaf.dtype, jnp.inexact):
                    finite = jnp.all(jnp.isfinite(leaf))
                    ok = finite if ok is None else jnp.logical_and(
                        ok, finite)
            if ok is not None and not bool(ok):
                return False
        return True

    # -------------------------------------------------------------- run

    def run(self, state, num_steps: int):
        """Drive ``step_fn`` to ``num_steps`` completed steps; returns
        the final state. ``state`` doubles as the restore template
        (structure/dtype/sharding of every leaf must match what was
        saved)."""
        import contextlib

        with contextlib.ExitStack() as stack:
            if self.fault_plan is not None:
                stack.enter_context(faults_mod.inject_checkpoint_failures(
                    self.fault_plan, registry=self._registry))
            return self._run(state, num_steps)

    def _run(self, state, num_steps: int):
        reg = self._reg()
        # ISSUE 17: the startup interval (gc + restore + template
        # setup) is an attempt boundary the run ledger needs — a cold
        # attempt's startup is `init`, a resumed attempt's is
        # `restart`. Timed via the registry Timer like every other
        # phase here (no raw clocks in resilience code).
        startup_timer = reg.timer("resilience/startup_s")
        startup_timer.start()
        self.resumed_from = None
        start = 0
        if self.manager is not None and self.auto_resume:
            state, start = self._resume(state)
        reg.event("attempt_start", start_step=start,
                  num_steps=num_steps,
                  resumed=self.resumed_from is not None,
                  startup_s=round(startup_timer.stop(), 6))
        fallback_state, fallback_step = state, start
        plan = self.fault_plan
        step, rollbacks = start, 0
        # rollbacks bound failures WITHOUT intervening progress: once a
        # completed step passes the one that triggered the last
        # rollback, the failure provably recovered and the budget resets
        recovery_target = -1
        last_error = None

        while step < num_steps:
            # ---- the step itself (transient failures retried)
            def attempt(_step=step, _state=state):
                recorder = self.flight_recorder
                if recorder is not None:
                    recorder.step_started(_step)
                try:
                    if plan is not None and plan.should_fire(
                            "step_exc", _step):
                        reg.counter("resilience/faults_injected",
                                    kind="step_exc").inc()
                        raise faults_mod.TransientStepError(
                            f"injected transient failure at step {_step}")
                    if plan is not None and plan.should_fire("oom",
                                                             _step):
                        # a RESOURCE_EXHAUSTED-shaped death (ISSUE 15):
                        # the generic failure rung below classifies it
                        # and runs the memory forensics, exactly like
                        # the real thing
                        reg.counter("resilience/faults_injected",
                                    kind="oom").inc()
                        raise faults_mod.InjectedOom(_step)
                    if plan is not None and plan.should_fire("stall",
                                                             _step):
                        # a hung step, not a failed one: the step
                        # completes after stall_s, so only a watchdog
                        # (the flight recorder's) observes it — exactly
                        # the production wedge this simulates. The span
                        # keeps the hang attributable: a flight dump
                        # taken mid-stall shows this open region
                        from apex_tpu.observability import span

                        reg.counter("resilience/faults_injected",
                                    kind="stall").inc()
                        with span("resilience/stall_fault"):
                            time.sleep(self.stall_s)
                    result = self.step_fn(_state, _step)
                except BaseException:
                    # a raised attempt's near-zero duration is NOT a
                    # step time: under a retry storm it would collapse
                    # the trailing median until every healthy step
                    # read as a stall
                    if recorder is not None:
                        recorder.step_finished(record=False)
                    raise
                if recorder is not None:
                    recorder.step_finished()
                return result

            # ISSUE 17: every completed step attempt leaves a
            # `step_done` event with its host wall seconds — the run
            # ledger's `productive_step` / `rollback_replay` interval
            # source (a step index completing twice is a replay). The
            # timer wraps the whole retried call, so a retry storm's
            # wall time is honestly attributed to the step it served.
            step_timer = reg.timer("resilience/step_s")
            step_timer.start()
            try:
                new_state, metrics = self._call(attempt)
            except (Preempted, TrainAborted, KeyboardInterrupt,
                    SystemExit):
                step_timer.cancel()
                raise
            except Exception as e:  # noqa: BLE001 — ladder rung 2
                step_timer.cancel()
                last_error = e
                recovery_target = max(recovery_target, step)
                memory = self._probe_memory(e, step)
                state, step, rollbacks = self._rollback(
                    fallback_state, fallback_step, rollbacks, step, e,
                    memory=memory)
                continue
            reg.event("step_done", step=step,
                      duration_s=round(step_timer.stop(), 6))

            if plan is not None and plan.should_fire("nan_grads", step):
                reg.counter("resilience/faults_injected",
                            kind="nan_grads").inc()
                new_state = faults_mod.corrupt_tree(new_state)

            # ---- health ladder
            overflow = bool((metrics or {}).get("overflow", False))
            if overflow:
                # rung 1: the amp scaler's in-graph cond already skipped
                # the update — params/opt state are last step's, by design
                reg.counter("resilience/overflow_skips").inc()
            elif not self._healthy(new_state, metrics, step):
                last_error = ValueError(
                    f"non-finite state/metrics at step {step}")
                recovery_target = max(recovery_target, step)
                prov = self._probe_numerics(state, new_state, step)
                state, step, rollbacks = self._rollback(
                    fallback_state, fallback_step, rollbacks, step,
                    last_error, numerics=prov)
                continue

            # ---- fleet desync check (ISSUE 12): a step can be
            # numerically healthy on every rank yet silently divergent
            # ACROSS ranks — treated exactly like a health failure
            verdict = self._check_desync(metrics, step)
            if verdict is not None:
                last_error = ValueError(
                    f"cross-rank desync at step {step}: rank "
                    f"{verdict.get('rank')} diverged at "
                    f"{verdict.get('tensor_path')}")
                recovery_target = max(recovery_target, step)
                state, step, rollbacks = self._rollback(
                    fallback_state, fallback_step, rollbacks, step,
                    last_error, fleet=verdict)
                continue

            state = new_state
            if rollbacks and step > recovery_target:
                rollbacks = 0  # made it past the failure point

            # ---- preemption poll (after the completed step, so the
            # emergency checkpoint carries it and resume never replays
            # into a re-drawn preemption fault)
            tripped = self.watcher is not None and self.watcher.check()
            if plan is not None and plan.should_fire("preempt", step):
                reg.counter("resilience/faults_injected",
                            kind="preempt").inc()
                if self.watcher is not None:
                    self.watcher.trip("fault-plan")
                else:
                    reg.counter("resilience/preemptions").inc()
                    reg.event("preemption", reason="fault-plan")
                tripped = True
            if tripped:
                reason = (self.watcher.reason or "preempted"
                          if self.watcher is not None else "fault-plan")
                # the drain interval (flush + emergency save) is what
                # the preemption actually cost before the process
                # dies — the ledger's `preempt_drain` cause (ISSUE 17)
                drain_timer = reg.timer("resilience/preempt_drain_s")
                drain_timer.start()
                path = self._emergency_save(state, step)
                reg.event("preempt_exit", step=step, reason=reason,
                          checkpoint=bool(path),
                          duration_s=round(drain_timer.stop(), 6))
                if self.exit_on_preempt:
                    sys.exit(EXIT_PREEMPTED)
                raise Preempted(step, path, reason)

            # ---- periodic checkpoint
            if self.manager is not None and self.save_every and (
                    step % self.save_every == 0
                    or step == num_steps - 1):
                self._save(state, step)

            step += 1

        if self.manager is not None:
            drain_timer = reg.timer("resilience/ckpt_save_s")
            drain_timer.start()
            try:
                self.manager.wait_until_finished()
                drain_timer.stop()
            except Exception as e:  # noqa: BLE001 — the final async
                # commit failing must not cost the trained state; the
                # last committed checkpoint stands (degradation rung 0)
                duration = drain_timer.stop()
                reg.counter("resilience/checkpoint_failures").inc()
                reg.event("checkpoint_failed", step=num_steps - 1,
                          error=repr(e)[:200],
                          duration_s=round(duration, 6))
        return state

    # ------------------------------------------------------- provenance

    def _probe_numerics(self, prev_state, bad_state, step: int):
        """NaN provenance for a failed health check (ISSUE 9): the
        offending tensor paths + (when the step traces) the first
        non-finite primitive. Never raises — a broken probe degrades
        to None and the ladder proceeds on the original error."""
        if not self.numerics_provenance:
            return None
        try:
            from apex_tpu.observability.numerics import step_provenance

            prov = step_provenance(self.step_fn, prev_state, bad_state,
                                   step).as_dict()
        except Exception as e:  # noqa: BLE001 — the probe is
            # diagnostics; it must never mask the health failure
            prov = {"ok": False,
                    "message": f"numerics probe failed: {e!r:.200}"}
        reg = self._reg()
        reg.counter("numerics/probes").inc()
        reg.event("numerics_provenance", step=step, **prov)
        return prov

    def _probe_memory(self, error, step: int):
        """ISSUE 15: OOM forensics for a RESOURCE_EXHAUSTED-shaped step
        death — dump a ``memrec_*.json`` post-mortem and return the
        compact verdict (requested bytes, largest live buffer,
        watermark). None for non-OOM failures; never raises — the
        forensics are diagnostics and must not mask the step error."""
        if not self.memory_forensics:
            return None
        # classification FIRST, outside the forensics guard: if the
        # memory tier itself cannot import or classify, a non-OOM step
        # death must stay a non-OOM step death — a mislabeled
        # TrainAborted would send the oncall to the wrong subsystem
        try:
            from apex_tpu.observability.memory import (
                is_oom_error,
                oom_forensics,
            )
        except Exception:  # noqa: BLE001 — trimmed install: no
            # memory tier, no verdict
            return None
        try:
            if not is_oom_error(error):
                return None
        except Exception:  # noqa: BLE001 — cannot classify ⇒ not OOM
            return None
        try:
            verdict = oom_forensics(
                error, monitor=self.memory_monitor,
                registry=self._registry, directory=self.directory,
                step=step)
        except Exception as e:  # noqa: BLE001 — diagnostics only
            verdict = {"error": f"memory forensics failed: {e!r:.200}"}
        reg = self._reg()
        reg.counter("memory/oom_probes").inc()
        reg.event("memory_verdict", step=step, **{
            k: v for k, v in verdict.items() if k != "error"})
        return verdict

    # ---------------------------------------------------- fleet desync

    def _check_desync(self, metrics, step: int):
        """ISSUE 12: run the fleet desync detector over the step's
        gathered fingerprint (``metrics["fleet_fingerprint"]``).
        Returns the verdict dict or None; a broken detector degrades
        to a counter + event, never a masked step."""
        if self.desync_detector is None or not metrics:
            return None
        gathered = metrics.get("fleet_fingerprint")
        if gathered is None:
            return None
        try:
            return self.desync_detector.check(step, gathered)
        except Exception as e:  # noqa: BLE001 — diagnostics must not
            # fail a healthy step
            reg = self._reg()
            reg.counter("fleet/desync_check_failures").inc()
            reg.event("fleet_desync_check_failed", step=step,
                      error=repr(e)[:200])
            return None

    # --------------------------------------------------------- rollback

    def _rollback(self, fallback_state, fallback_step: int,
                  rollbacks: int, step: int, error, numerics=None,
                  fleet=None, memory=None):
        """Rung 2: restore the newest valid checkpoint (or the run's
        starting state) and hand back the replay position. Rung 3:
        past ``max_rollbacks``, abort with the structured report
        (``numerics`` = the probe verdict, ``fleet`` = the desync
        verdict, ``memory`` = the OOM forensics verdict — all attached
        to the rollback event and the abort report)."""
        reg = self._reg()
        rollbacks += 1
        reg.counter("resilience/rollbacks").inc()
        event_fields = {"step": step, "attempt": rollbacks,
                        "error": repr(error)[:200]}
        if numerics is not None:
            event_fields["numerics"] = {
                k: numerics.get(k) for k in
                ("kind", "primitive", "source", "output_paths")}
        if fleet is not None:
            event_fields["fleet"] = {
                k: fleet.get(k) for k in
                ("rank", "tensor_path", "first_divergent_step",
                 "max_delta")}
        if memory is not None:
            event_fields["memory"] = {
                k: memory.get(k) for k in
                ("requested_bytes", "largest_buffer",
                 "watermark_bytes", "memrec")}
        reg.event("rollback", **event_fields)
        if rollbacks > self.max_rollbacks:
            report = {
                "step": step,
                "rollbacks": rollbacks - 1,
                "max_rollbacks": self.max_rollbacks,
                "reason": "rollback budget exhausted",
                "last_error": repr(error)[:500],
                "resumed_from": self.resumed_from,
                "directory": self.directory,
                "counters": {
                    m.name: m.value for m in reg.metrics()
                    if m.kind == "counter"
                    and m.name.startswith("resilience/")},
            }
            if numerics is not None:
                report["numerics"] = numerics
            if fleet is not None:
                report["fleet"] = fleet
            if memory is not None:
                report["memory"] = memory
            reg.event("train_aborted", **report)
            raise TrainAborted(report)
        if self.manager is not None:
            for s in reversed(ckpt.valid_steps(self.directory)):
                restore_timer = reg.timer("resilience/ckpt_restore_s")
                restore_timer.start()
                try:
                    restored = ckpt.restore_checkpoint(
                        self.directory, target={"state": fallback_state},
                        step=s)
                except Exception as e:  # noqa: BLE001
                    duration = restore_timer.stop()
                    reg.counter("resilience/restore_failures").inc()
                    reg.event("restore_failed", step=s,
                              error=repr(e)[:200],
                              duration_s=round(duration, 6))
                    continue
                duration = restore_timer.stop()
                # a rollback restore is a `resumed`-shaped interval for
                # the ledger: same name, same duration contract, plus
                # the rollback marker so accounting can tell the two
                # apart (in-process rollback vs process restart)
                reg.event("resumed", step=s, rollback=True,
                          duration_s=round(duration, 6))
                return restored["state"], s + 1, rollbacks
        return fallback_state, fallback_step, rollbacks


# -------------------------------------------------------- resume path

def resume_path(step_fn: Callable, *, holds_fallback: bool = True
                ) -> Callable:
    """The loop's post-restore composition as one traceable function —
    the ``state_resilient_resume_path`` target of the state engine's
    ``restore-donation-hazard`` check.

    ``run()`` keeps the restored pytree alive past the first step in
    two ways: ``fallback_state`` (held for ``_rollback``) and the
    emergency-save path. A ``step_fn`` compiled with
    ``donate_argnums=(0,)`` therefore donates buffers the loop still
    references — fine on CPU, use-after-free on TPU where donation
    actually invalidates the buffer. The returned function mirrors
    that shape: ``resume(restored, step) -> (new_state, metrics[,
    restored])``, returning the retained restored reference when
    ``holds_fallback`` (the loop's real behavior). Static proof, not a
    runtime check: trace it with
    :func:`apex_tpu.analysis.state_checks.check_restore_donation` — a
    non-donating ``step_fn`` (the loop's documented contract) is
    clean; a donating one flags the held reference.
    """

    if holds_fallback:
        def resume(restored, step):
            # fallback_state = restored — the reference _rollback and
            # the emergency save still need after step_fn runs
            fallback_state = restored
            new_state, metrics = step_fn(restored, step)
            return new_state, metrics, fallback_state
    else:
        def resume(restored, step):
            return step_fn(restored, step)
    resume.__name__ = f"resume_path({getattr(step_fn, '__name__', 'step')})"
    return resume


# --------------------------------------------------------------- probe

def chaos_probe(spec: str, directory: str, *, steps: int = 24,
                save_every: int = 4, seed: int = 0, max_restarts: int = 8,
                registry=None) -> dict:
    """Self-contained chaos smoke: a tiny deterministic SGD loop run
    under fault plan ``spec``, restarted on every preemption the way a
    scheduler would (fresh :class:`FaultPlan` per restart = fresh
    process semantics). Used by ``bench.py``'s ``APEX_TPU_FAULT_PLAN``
    knob; returns a summary dict whose counters also land in the
    registry (→ BENCH_METRICS.jsonl).
    """
    import jax
    import jax.numpy as jnp

    from apex_tpu.resilience.retry import Policy

    faults_mod.FaultPlan.parse(spec)  # validate before any work
    key = jax.random.PRNGKey(seed)
    template = {"w": jnp.ones((16, 16), jnp.float32)}

    def step_fn(state, step):
        g = jax.random.normal(jax.random.fold_in(key, step), (16, 16))
        w = state["w"] - 0.01 * (g + 0.1 * state["w"])
        # loss stays a device scalar: the health check reads it either
        # way, and keeping the step traceable lets the ISSUE 9 NaN
        # probe replay its jaxpr when a chaos fault poisons the state
        return {"w": w}, {"loss": jnp.mean(w * w)}

    restarts = 0
    completed = False
    final = None
    for _ in range(max_restarts + 1):
        loop = ResilientTrainLoop(
            step_fn, directory=directory, save_every=save_every,
            fault_plan=faults_mod.FaultPlan.parse(spec),
            retry_policy=Policy(max_attempts=3, initial_backoff=0.001,
                                retry_on=(OSError,
                                          faults_mod.FaultInjected),
                                sleep=lambda s: None, seed=seed,
                                name="chaos_probe", registry=registry),
            registry=registry)
        try:
            final = loop.run(template, steps)
            completed = True
            break
        except Preempted:
            restarts += 1
    reg = registry
    if reg is None:
        from apex_tpu.observability import get_registry
        reg = get_registry()
    summary = {"completed": completed, "restarts": restarts,
               "steps": steps, "plan": spec}
    for m in reg.metrics():
        if m.kind == "counter" and m.name.startswith("resilience/"):
            label = ",".join(f"{k}={v}" for k, v in
                             sorted(m.labels.items()))
            summary[m.name + (f"{{{label}}}" if label else "")] = m.value
    if final is not None:
        summary["final_param_sum"] = float(jnp.sum(final["w"]))
    reg.event("chaos_probe", **{k: v for k, v in summary.items()
                                if isinstance(v, (int, float, str, bool))})
    return summary
