"""Measured-vs-modeled HBM calibration (ISSUE 15 tentpole piece 3).

The sharding-flow estimator (PR 4) prices every registered target's
per-device peak HBM, and the auto-sharding planner (PR 8) *prunes
candidate layouts* on that number — yet it had never been checked
against what XLA actually allocates. This module closes the loop:

- re-run a registered sharding-flow target with the
  :func:`~apex_tpu.analysis.sharding_checks.capture_traces` hook
  armed, so the exact ``(fn, example_args)`` the estimator modeled is
  in hand;
- AOT-compile the same program
  (:meth:`CompiledMemoryCapture.capture`) and read XLA's
  ``memory_analysis()`` total (argument + output + temp − alias);
- publish ``memory/hbm_calibration_ratio{target=}`` = measured /
  modeled, plus the raw modeled/measured byte gauges.

The ratio is not expected to be 1.0 — the liveness model and XLA's
buffer assignment count different things (donation timing, fusion
temps, layout padding) — but it IS expected to be *stable*: a drifting
ratio means the cost model and the compiler disagree in a new way, and
every planner pruning decision inherits that error.
``tools/metrics_report.py --compare`` gates exactly that drift, which
turns silent planner mis-pruning into a failing diff. Run on a TPU the
same pass gives the cost model on-silicon ground truth.

Per-target compile failures degrade to a ``memory_calibration_skipped``
event (not every analyzable program compiles on every backend) —
callers assert on how many ratios LANDED, not on zero skips.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["DEFAULT_CALIBRATION_TARGETS", "calibrate_targets"]

# Sharding-flow targets that both trace AND compile on the CPU backend
# — the calibration set bench.py runs per-invocation.
# Deliberately spans the families the estimator's error modes differ
# over: a collective-only step, a shard_map'd kernel, donated optimizer
# state, and the dp-sharded ZeRO path.
DEFAULT_CALIBRATION_TARGETS = (
    "ddp_bucket_allreduce_step",
    "tp_fused_softmax_sharded",
    "fused_adam_master_sharded_step",
    "moe_dispatch",
    "zero1_fused_adam_step",
)


def calibrate_targets(names=None, registry=None,
                      capture=None) -> dict:
    """Run measured-vs-modeled HBM calibration over ``names`` (default
    :data:`DEFAULT_CALIBRATION_TARGETS`; must be registered
    sharding-flow targets). Returns ``{target: row}`` where a
    successful row carries ``modeled_bytes`` / ``measured_bytes`` /
    ``ratio`` / the per-executable ``breakdown``, and a skipped one
    carries ``error``.

    ``capture``: an optional
    :class:`~apex_tpu.observability.memory.compiled
    .CompiledMemoryCapture` to record the compiled stats into (default:
    the installed process capture, or a detached throwaway).
    """
    from apex_tpu.analysis import sharding_checks, targets as targets_mod
    from apex_tpu.observability.memory import compiled as compiled_mod
    from apex_tpu.observability.registry import get_registry

    reg = registry if registry is not None else get_registry()
    cap = capture
    if cap is None:
        cap = compiled_mod.current_capture()
    if cap is None:
        cap = compiled_mod.CompiledMemoryCapture(registry=reg)

    names = tuple(names) if names is not None \
        else DEFAULT_CALIBRATION_TARGETS
    # validated against the SHARDING target set specifically: only a
    # target that calls analyze_sharding can be trace-captured, so a
    # precision/spmd target name is as unknown here as a typo
    unknown = [n for n in names
               if n not in targets_mod.SHARDING_TARGETS]
    if unknown:
        raise ValueError(
            f"unknown sharding-flow target(s) {sorted(unknown)}; "
            f"registered: {sorted(targets_mod.SHARDING_TARGETS)}")

    results: dict = {}
    for name in names:
        row = _calibrate_one(name, targets_mod, sharding_checks, cap,
                             reg)
        results[name] = row
        if "ratio" in row:
            reg.gauge("memory/hbm_calibration_ratio", target=name).set(
                row["ratio"])
            reg.gauge("memory/hbm_modeled_bytes", target=name).set(
                row["modeled_bytes"])
            reg.gauge("memory/hbm_measured_bytes", target=name).set(
                row["measured_bytes"])
            reg.event("memory_calibration", target=name,
                      modeled_bytes=row["modeled_bytes"],
                      measured_bytes=row["measured_bytes"],
                      ratio=row["ratio"])
        else:
            reg.counter("memory/calibration_skipped").inc()
            reg.event("memory_calibration_skipped", target=name,
                      error=row["error"])
    return results


def _calibrate_one(name, targets_mod, sharding_checks, cap, reg) -> dict:
    """One target's calibration row; failures land as {"error": ...}
    (a target that cannot compile on this backend is a skip, not a
    crash of the whole calibration pass)."""
    captured: dict = {}
    try:
        with sharding_checks.capture_traces(captured):
            targets_mod.TARGETS[name]()
    except Exception as e:  # noqa: BLE001 — the target itself failed
        return {"error": f"target failed: {e!r:.200}"}
    trace = captured.get(name)
    if trace is None:
        return {"error": "target ran no analyze_sharding trace under "
                         "this name (jaxpr-level entry?)"}
    modeled = _modeled_peak(name, targets_mod)
    if modeled is None:
        return {"error": "no peak_hbm_bytes estimate in SHARDING_STATS"}
    try:
        _compiled, fields = cap.capture(
            trace["fn"], *trace["example_args"],
            name=f"calibrate/{name}",
            donate_argnums=trace["donate_argnums"] or ())
    except Exception as e:  # noqa: BLE001 — not every analyzable
        # program compiles on every backend; the caller counts skips
        return {"error": f"compile failed: {e!r:.200}"}
    if fields is None:
        return {"error": "backend reported no memory_analysis"}
    measured = fields["total_bytes"]
    if modeled <= 0:
        return {"error": f"modeled peak is {modeled} bytes — nothing "
                         f"to calibrate against"}
    return {
        "modeled_bytes": int(modeled),
        "measured_bytes": int(measured),
        "ratio": round(measured / modeled, 4),
        "breakdown": fields,
    }


def _modeled_peak(name, targets_mod) -> Optional[int]:
    stats = targets_mod.SHARDING_STATS.get(name) or {}
    peak = stats.get("peak_hbm_bytes")
    return int(peak) if isinstance(peak, (int, float)) else None
