"""On-device tensor statistics for whole pytrees (ISSUE 9 tentpole
piece 1).

One jit of :func:`tensor_stats` computes amax / l2-norm /
underflow-fraction / zero-fraction / finite-flag for EVERY inexact leaf
of a tree as one fused program: per-leaf scalars stacked into five
small vectors, so the device does one pass over the data and the host
does ONE fetch for the whole tree. The anti-pattern this replaces — a
Python loop of ``bool(jnp.isnan(leaf).any())`` host pulls per tensor —
serializes the step pipeline on device round-trips and is now linted
(``host-isnan-in-step-loop``).

:class:`StatsCollector` is the decimated driver: stats are computed
AND pulled only every ``every`` steps; the host fetch of the stacked
result vectors is the only wait — one fetch per pull, never per
tensor.

The stacked ``amax`` vector is the substrate ROADMAP item 5's fp8
delayed scaling feeds on — :mod:`.history` rings it.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

__all__ = [
    "TENSOR_STAT_FIELDS", "TreeStats", "tree_paths", "leaf_paths",
    "tensor_stats", "host_tensor_stats", "nonfinite_paths",
    "summarize_stats", "StatsCollector",
]

#: per-tensor statistics every stats pass computes, in stack order.
TENSOR_STAT_FIELDS = ("amax", "l2", "underflow_frac", "zero_frac",
                      "finite")


class TreeStats(NamedTuple):
    """Stacked per-leaf statistics (one entry per inexact leaf, in
    ``leaf_paths`` order). All five live on device until one host
    fetch pulls the whole tuple."""

    amax: object            # f32[n]  max |x|
    l2: object              # f32[n]  sqrt(sum x^2)
    underflow_frac: object  # f32[n]  fraction with 0 < |x| < tiny
    zero_frac: object       # f32[n]  fraction exactly zero
    finite: object          # bool[n] all-finite flag


def _key_str(key) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(key, attr):
            return str(getattr(key, attr))
    return str(key)


def _path_leaves(tree):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(_key_str(k) for k in path) or "<root>", leaf)
            for path, leaf in flat]


def _is_inexact(leaf) -> bool:
    import jax.numpy as jnp
    return hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.inexact)


def tree_paths(tree) -> tuple:
    """Slash-joined key path of EVERY leaf, in flatten order."""
    return tuple(p for p, _leaf in _path_leaves(tree))


def leaf_paths(tree) -> tuple:
    """Key paths of the inexact leaves only — the tensors a stats pass
    covers, aligned with the :class:`TreeStats` vectors."""
    return tuple(p for p, leaf in _path_leaves(tree)
                 if _is_inexact(leaf))


def tensor_stats(tree) -> TreeStats:
    """Per-tensor stats for every inexact leaf, on device, jit-safe.

    Call it inside a jitted step (free fusion with the step program) or
    through :class:`StatsCollector` (which jits it standalone). The
    underflow threshold is each leaf's own dtype's smallest normal, so
    a bf16 tensor reports bf16 underflow even though the reduction runs
    in f32.
    """
    import jax.numpy as jnp

    leaves = [leaf for _p, leaf in _path_leaves(tree)
              if _is_inexact(leaf)]
    if not leaves:
        z = jnp.zeros((0,), jnp.float32)
        return TreeStats(z, z, z, z, jnp.zeros((0,), jnp.bool_))
    amax, l2, under, zero, finite = [], [], [], [], []
    for leaf in leaves:
        tiny = float(jnp.finfo(leaf.dtype).tiny)
        x = leaf.astype(jnp.float32)
        ax = jnp.abs(x)
        amax.append(jnp.max(ax))
        l2.append(jnp.sqrt(jnp.sum(x * x)))
        under.append(jnp.mean(((ax > 0) & (ax < tiny)).astype(
            jnp.float32)))
        zero.append(jnp.mean((x == 0).astype(jnp.float32)))
        finite.append(jnp.all(jnp.isfinite(x)))
    return TreeStats(jnp.stack(amax), jnp.stack(l2), jnp.stack(under),
                     jnp.stack(zero), jnp.stack(finite))


def host_tensor_stats(tree, stats: Optional[TreeStats] = None) -> dict:
    """{path: {field: float/bool}} for every inexact leaf — ONE host
    fetch of the stacked vectors. Pass a
    precomputed ``stats`` to fetch results a jitted step already
    produced."""
    import jax

    paths = leaf_paths(tree)
    if stats is None:
        stats = _jitted_stats()(tree)
    host = jax.device_get(stats)
    out = {}
    for i, path in enumerate(paths):
        out[path] = {
            "amax": float(host.amax[i]),
            "l2": float(host.l2[i]),
            "underflow_frac": float(host.underflow_frac[i]),
            "zero_frac": float(host.zero_frac[i]),
            "finite": bool(host.finite[i]),
        }
    return out


def nonfinite_paths(tree, stats: Optional[TreeStats] = None) -> tuple:
    """Key paths of the leaves containing NaN/Inf (one device
    reduction + one fetch for the whole tree)."""
    per_tensor = host_tensor_stats(tree, stats)
    return tuple(p for p, s in per_tensor.items() if not s["finite"])


def summarize_stats(per_tensor: dict, top_k: int = 3) -> dict:
    """Fold a ``host_tensor_stats`` dict into the compact summary a
    step record / JSON line carries: all-finite flag, the non-finite
    paths, and the top-k tensors by amax."""
    import math

    def rank(s):  # non-finite tensors are the most broken: rank first
        return math.inf if not math.isfinite(s["amax"]) else s["amax"]

    worst = sorted(per_tensor.items(), key=lambda kv: -rank(kv[1]))
    return {
        "tensors": len(per_tensor),
        "finite": all(s["finite"] for s in per_tensor.values()),
        "nonfinite_paths": [p for p, s in per_tensor.items()
                            if not s["finite"]],
        # max over FINITE amaxes only — one NaN tensor must not turn
        # the whole summary (and every gauge built on it) into NaN;
        # the finite flag + nonfinite_paths already carry that fact
        "amax_max": max((s["amax"] for s in per_tensor.values()
                         if math.isfinite(s["amax"])), default=0.0),
        "worst_amax": [[p, round(s["amax"], 6)]
                       for p, s in worst[:top_k]],
        "underflow_frac_max": max(
            (s["underflow_frac"] for s in per_tensor.values()),
            default=0.0),
        "zero_frac_max": max((s["zero_frac"]
                              for s in per_tensor.values()),
                             default=0.0),
    }


_STATS_JIT = None


def _jitted_stats():
    global _STATS_JIT
    if _STATS_JIT is None:
        import jax
        _STATS_JIT = jax.jit(tensor_stats)
    return _STATS_JIT


class StatsCollector:
    """Decimated stats driver: ``observe(tree, step)`` runs the fused
    stats pass + the single host pull every ``every`` steps and
    publishes the ``numerics/*`` family to the registry; off-cadence
    steps cost nothing (not even a dispatch).

    Publishes per pull (all labeled ``source=<name>``):

    - gauge ``numerics/finite`` — 1.0/0.0 whole-tree finite flag (the
      ``--compare`` gate fails a run where this flips 1 → 0);
    - gauges ``numerics/amax_max``, ``numerics/underflow_frac_max``,
      ``numerics/zero_frac_max``;
    - timer ``numerics/stats_pass`` — the pass's own cost (compute +
      the one host fetch), so the <2% overhead budget is measured, not
      assumed;
    - counter ``numerics/stats_pulls``; event ``numerics_stats`` with
      the summary (non-finite paths, top-k amax tensors).

    ``last`` keeps the most recent summary — the ``numerics`` block
    ``StepReporter.step(..., numerics=collector.last)`` attaches.
    """

    def __init__(self, name: str = "numerics", every: int = 16,
                 registry=None, top_k: int = 3):
        self.name = name
        self.every = max(int(every), 1)
        self.top_k = top_k
        self._registry = registry
        self.last: Optional[dict] = None

    def _reg(self):
        if self._registry is not None:
            return self._registry
        from apex_tpu.observability.registry import get_registry
        return get_registry()

    def observe(self, tree, step: int) -> Optional[dict]:
        """Run the pass when ``step`` is on cadence; returns the
        summary dict (also kept as ``last``), or None off-cadence."""
        if step % self.every:
            return None
        reg = self._reg()
        timer = reg.timer("numerics/stats_pass", source=self.name)
        timer.start()
        try:
            per_tensor = host_tensor_stats(tree)
        except BaseException:
            timer.cancel()
            raise
        elapsed = timer.stop()  # the device_get above was the sync
        summary = summarize_stats(per_tensor, top_k=self.top_k)
        summary["step"] = int(step)
        summary["stats_pass_ms"] = round(elapsed * 1e3, 3)
        reg.counter("numerics/stats_pulls", source=self.name).inc()
        reg.gauge("numerics/finite", source=self.name).set(
            1.0 if summary["finite"] else 0.0)
        reg.gauge("numerics/amax_max", source=self.name).set(
            summary["amax_max"])
        reg.gauge("numerics/underflow_frac_max", source=self.name).set(
            summary["underflow_frac_max"])
        reg.gauge("numerics/zero_frac_max", source=self.name).set(
            summary["zero_frac_max"])
        reg.event("numerics_stats", source=self.name, **{
            k: v for k, v in summary.items() if k != "tensors"})
        if not summary["finite"]:
            reg.counter("numerics/nonfinite_pulls",
                        source=self.name).inc()
        self.last = summary
        return summary
