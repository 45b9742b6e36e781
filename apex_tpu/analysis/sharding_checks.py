"""Sharding-flow checks — client analyses over :mod:`.sharding_flow`
(ISSUE 4 tentpole).

Apex's parallelism pitch was that the collectives were *pre-audited*:
Megatron TP/PP and DDP buckets shipped with their communication pattern
already reasoned about. These checks machine-check the same properties
over the traced programs, where the failure modes are silent — a
mis-sharded boundary compiles fine and only shows up as a slow or
OOMing step on silicon:

- ``implicit-reshard``   the propagated sharding disagrees with a
  ``with_sharding_constraint``/out-sharding boundary in a way GSPMD can
  only satisfy by *moving* data (an axis hops dims ⇒ all-to-all, or a
  dim re-shards onto a different axis), or two differently-sharded
  operands meet in one elementwise op (one side gets resharded).
  An explicit constraint that simply *drops* an axis is not flagged:
  constraining to replicated is the documented GSPMD way to ASK for an
  all-gather (``gather_output``, sequence-parallel boundaries) — the
  hidden reshards are the ones nobody wrote down.
- ``replicated-large``   a large input (params, optimizer state) whose
  spec is fully replicated although some mesh axis divides one of its
  dims — TP master weights living whole on every device.
- ``psum-scatter``       a ``psum`` whose result is immediately sliced
  to this rank's chunk along the reduced mesh axis: half the bytes of
  the allreduce are thrown away; ``lax.psum_scatter`` moves ~half as
  much.
- ``dead-collective``    a collective whose operand cannot differ
  across the mesh axis it rides (``distinct`` lattice): the bytes move
  (or a tree reduction runs) to reproduce what every chip already has.
  The classic is ``psum(jnp.ones(()))`` as an axis-size probe — that is
  ``lax.axis_size``, a compile-time constant.
- ``hbm-budget``         live-range peak-HBM estimate (per-device
  local bytes under the propagated shardings, donation credit from the
  PR 1 donation wiring) against a configurable per-device budget
  (:func:`apex_tpu.ops.pallas_config.device_hbm_bytes`).

Entry point: :func:`analyze_sharding` (mirrors
``precision_checks.analyze_precision``); the registered customers live
in :mod:`.targets`. Every run also produces the per-target comms-bytes
and peak-HBM estimates bench.py ships in its JSON line and the metrics
JSONL (``analysis/sharding_*`` family).
"""

from __future__ import annotations

import contextlib

from apex_tpu.analysis.findings import Finding
from apex_tpu.analysis.sharding_flow import (
    COLLECTIVE_PRIMS,
    ShardVal,
    collective_bytes,
    estimate_hbm_and_comms,
    interpret_sharding,
    live_mesh_axis_sizes,
    local_bytes,
    normalize_spec,
)

SHARDING_CHECKS = (
    "implicit-reshard", "replicated-large", "psum-scatter",
    "dead-collective", "hbm-budget",
)

# Inputs below this size are never worth sharding (replicated-large).
DEFAULT_REPLICATED_THRESHOLD = 1 << 20  # 1 MiB

# When armed (a dict), analyze_sharding records each traced target's
# (fn, example_args, donate_argnums, closed jaxpr) under its name — the
# hook the memory-calibration tier (ISSUE 15) uses to AOT-compile the
# exact program the HBM estimator priced. Arm via capture_traces().
_TRACE_CAPTURE = None


@contextlib.contextmanager
def capture_traces(sink: dict):
    """Arm the per-target trace capture for the duration of the block;
    ``sink`` receives one entry per analyze_sharding call (keyed by
    target name). Re-entrant: the previous sink is restored on exit."""
    global _TRACE_CAPTURE
    prev, _TRACE_CAPTURE = _TRACE_CAPTURE, sink
    try:
        yield sink
    finally:
        _TRACE_CAPTURE = prev


def _fmt_spec(spec):
    if spec is None:
        return "?"
    return "P(" + ", ".join(
        ("None" if not e else "+".join(e) if len(e) > 1 else e[0])
        for e in spec) + ")"


def _fmt_bytes(n):
    if n >= 1 << 30:
        return f"{n / (1 << 30):.2f} GiB"
    if n >= 1 << 20:
        return f"{n / (1 << 20):.1f} MiB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f} KiB"
    return f"{n} B"


# Binary/ternary ops whose operands GSPMD must co-locate elementwise —
# the only place the join-conflict flavor of implicit-reshard applies.
_ELEMENTWISE_JOIN_PRIMS = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "atan2",
    "and", "or", "xor", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "nextafter", "complex", "add_any",
    "eq", "ne", "lt", "le", "gt", "ge", "select_n",
})


class _Ctx:
    def __init__(self, name, path):
        self.name = name
        self.path = path
        self.findings = []
        self.seen = set()

    def add(self, check, severity, message, dedup_key=None):
        if dedup_key is not None:
            key = (check,) + tuple(dedup_key)
            if key in self.seen:
                return
            self.seen.add(key)
        self.findings.append(Finding(
            check, severity, self.path, 0, self.name, message))


# ------------------------------------------------------------- checks

def _visit_implicit_reshard(ctx, eqn, ins, outs, mctx):
    prim = eqn.primitive.name
    if prim == "sharding_constraint":
        src = ins[0] if ins else None
        if src is None or src.spec is None:
            return
        sharding = eqn.params.get("sharding")
        want = normalize_spec(getattr(sharding, "spec", None),
                              len(src.spec))
        have = src.spec
        if have == want:
            return
        have_dims = {a: d for d, e in enumerate(have) for a in e}
        want_dims = {a: d for d, e in enumerate(want) for a in e}
        moved = {a: (have_dims[a], want_dims[a]) for a in have_dims
                 if a in want_dims and have_dims[a] != want_dims[a]}
        aval = eqn.invars[0].aval
        if moved:
            nb = local_bytes(aval, src, mctx)
            axes = sorted(moved)
            moves = ", ".join(f"'{a}' dim {moved[a][0]}→{moved[a][1]}"
                              for a in axes)
            ctx.add(
                "implicit-reshard", "error",
                f"sharding constraint moves mesh axis "
                f"{moves}: propagated {_fmt_spec(have)} vs constrained "
                f"{_fmt_spec(want)} forces a hidden all-to-all of "
                f"~{_fmt_bytes(nb)} per device — reshard explicitly "
                f"(or fix the upstream with_sharding_constraint) so "
                f"the transfer is visible and schedulable",
                dedup_key=("moved", have, want))
            return
        for d, (h, w) in enumerate(zip(have, want)):
            if h and w and h != w:
                nb = local_bytes(aval, src, mctx)
                ctx.add(
                    "implicit-reshard", "error",
                    f"dim {d} arrives sharded over {'+'.join(h)} but "
                    f"the constraint wants {'+'.join(w)}: GSPMD "
                    f"inserts a hidden reshard (~{_fmt_bytes(nb)} per "
                    f"device) — align the producer's sharding with "
                    f"this boundary",
                    dedup_key=("axis", d, h, w))
        return

    # elementwise join of incompatibly-sharded operands: one side gets
    # an implicit all-gather/reshard nobody wrote down. Only genuinely
    # elementwise prims — a gather/pjit/concatenate legitimately mixes
    # operands whose shardings differ (e.g. an embedding lookup where
    # the table shards over a different dim than the indices).
    if prim not in _ELEMENTWISE_JOIN_PRIMS or len(eqn.invars) < 2:
        return
    known = [(v, iv) for v, iv in zip(ins, eqn.invars)
             if v is not None and v.spec is not None]
    if len(known) < 2:
        return
    ndims = {len(v.spec) for v, _ in known}
    if len(ndims) != 1:
        return
    base = known[0][0].spec
    base_dims = {a: d for d, e in enumerate(base) for a in e}
    for v, iv in known[1:]:
        for d, (a, b) in enumerate(zip(base, v.spec)):
            if a and b and a != b:
                nb = local_bytes(iv.aval, v, mctx)
                ctx.add(
                    "implicit-reshard", "error",
                    f"'{prim}' joins operands sharded differently on "
                    f"dim {d} ({'+'.join(a)} vs {'+'.join(b)}): XLA "
                    f"must reshard one side (~{_fmt_bytes(nb)} per "
                    f"device) on every step — add the missing "
                    f"with_sharding_constraint so both sides agree",
                    dedup_key=("join", prim, d, a, b))
        other_dims = {a: d for d, e in enumerate(v.spec) for a in e}
        for axis, d0 in sorted(base_dims.items()):
            d1 = other_dims.get(axis)
            if d1 is not None and d1 != d0:
                nb = local_bytes(iv.aval, v, mctx)
                ctx.add(
                    "implicit-reshard", "error",
                    f"'{prim}' joins operands carrying mesh axis "
                    f"'{axis}' on different dims ({d0} vs {d1}): XLA "
                    f"must all-to-all one side (~{_fmt_bytes(nb)} per "
                    f"device) on every step — add the missing "
                    f"with_sharding_constraint so both sides agree",
                    dedup_key=("join-move", prim, axis, d0, d1))


def _visit_psum_scatter(ctx, eqn, ins, outs, mctx):
    if eqn.primitive.name != "dynamic_slice":
        return
    op = ins[0] if ins else None
    if op is None or not op.psum_axes:
        return
    rank_axes = frozenset()
    for v in ins[1:]:
        if v is not None:
            rank_axes |= v.from_axis_index
    hit = op.psum_axes & rank_axes
    if not hit:
        return
    axis = sorted(hit)[0]
    n = mctx.size(axis)
    try:
        nb = local_bytes(eqn.invars[0].aval, op, mctx)
    except Exception:
        nb = 0
    ctx.add(
        "psum-scatter", "warning",
        f"psum over '{axis}' immediately sliced to this rank's chunk "
        f"(slice start derives from axis_index('{axis}')): the "
        f"allreduce moves ~{_fmt_bytes(collective_bytes('psum', nb, [n]))} "
        f"per device and {max(n - 1, 1)}/{n} of the result is thrown "
        f"away — lax.psum_scatter moves ~half the bytes and skips the "
        f"slice",
        dedup_key=(axis,))


def _visit_dead_collective(ctx, eqn, ins, outs, mctx):
    prim = eqn.primitive.name
    param = COLLECTIVE_PRIMS.get(prim)
    if param is None or prim in ("psum_scatter", "reduce_scatter"):
        # psum_scatter of replicated data still produces per-rank
        # chunks — not a pure no-op, so it stays out of this check
        return
    axes = [a for a in _axes_of(eqn.params.get(param))]
    if not axes:
        return
    # a fused tree psum carries several operands: the collective is
    # alive if ANY of them can differ (Literal/None operands are
    # definitionally identical everywhere)
    distinct = frozenset().union(
        *(v.distinct for v in ins if v is not None)) \
        if any(v is not None for v in ins) else frozenset()
    if distinct & frozenset(axes):
        return
    # unknown-provenance guard: a value varying over an axis we failed
    # to model would be distinct-empty too; only fire when the operand
    # world is one the lattice fully models (inside shard_map, where
    # every distinct source is in_names / axis_index / collectives)
    if not mctx.manual_axes.issuperset(axes):
        return
    ctx.add(
        "dead-collective", "warning",
        f"'{prim}' over {axes} moves data that cannot differ across "
        f"{'that axis' if len(axes) == 1 else 'those axes'}: every "
        f"device already holds the result"
        + (" — psum of a constant is just a scaled copy; use "
           "jax.lax.axis_size for size probes"
           if prim in ("psum", "psum_invariant") else "")
        + ", drop the collective or compute it locally",
        dedup_key=(prim, tuple(axes)))


def _axes_of(value):
    if value is None:
        return ()
    if isinstance(value, (tuple, list, frozenset, set)):
        out = []
        for v in value:
            out.extend(_axes_of(v))
        return tuple(out)
    return (str(value),)


_VISITORS = {
    "implicit-reshard": _visit_implicit_reshard,
    "psum-scatter": _visit_psum_scatter,
    "dead-collective": _visit_dead_collective,
}


def _check_replicated_large(ctx, closed, in_vals, axis_sizes,
                            threshold):
    import numpy as np
    for i, var in enumerate(closed.jaxpr.invars):
        val = in_vals[i] if i < len(in_vals) else None
        if val is None or val.spec is None or val.axes_used():
            continue
        aval = var.aval
        shape = tuple(getattr(aval, "shape", ()) or ())
        nbytes = int(np.prod(shape or (1,)) *
                     np.dtype(str(aval.dtype)).itemsize)
        if nbytes < threshold:
            continue
        shardable = [
            (axis, size) for axis, size in sorted(axis_sizes.items())
            if size > 1 and any(d >= size and d % size == 0
                                for d in shape)]
        if not shardable:
            continue
        axis, size = shardable[0]
        ctx.add(
            "replicated-large", "warning",
            f"input {i} ({str(aval.dtype)}{list(shape)}, "
            f"{_fmt_bytes(nbytes)}) is fully replicated although mesh "
            f"axis '{axis}' (size {size}) divides one of its dims: "
            f"every device holds the whole array — shard it (master "
            f"weights/optimizer state shard over tp like the params "
            f"they mirror)",
            dedup_key=("input", i))


# -------------------------------------------------------------- entry

def _flatten_specs(example_args, in_specs):
    """Per-arg specs -> one PartitionSpec-or-None per flat leaf."""
    import jax
    from jax.sharding import PartitionSpec

    def is_spec(x):
        return x is None or isinstance(x, PartitionSpec)

    flat = []
    for argnum, arg in enumerate(example_args):
        leaves = jax.tree_util.tree_leaves(arg)
        entry = None
        if in_specs is not None and argnum < len(in_specs):
            entry = in_specs[argnum]
        if is_spec(entry):
            flat.extend([entry] * len(leaves))
            continue
        spec_leaves = jax.tree_util.tree_leaves(entry, is_leaf=is_spec)
        if len(spec_leaves) != len(leaves):
            raise ValueError(
                f"in_specs[{argnum}] has {len(spec_leaves)} spec "
                f"leaves for {len(leaves)} argument leaves")
        flat.extend(spec_leaves)
    return flat


def analyze_sharding(fn, *example_args, name=None, in_specs=None,
                     donate_argnums=(), axis_sizes=None, checks=None,
                     hbm_budget_bytes=None,
                     replicated_threshold_bytes=None, stats_out=None):
    """Trace ``fn`` and run the sharding-flow checks over its jaxpr.

    ``in_specs``: one entry per positional arg — a ``PartitionSpec``
    (or None) applied to every leaf, or a matching pytree of specs.
    ``donate_argnums`` mirrors ``jax.jit``'s and feeds the hbm-budget
    liveness credit. ``axis_sizes`` is the mesh universe (default: the
    live ``parallel_state`` mesh). ``hbm_budget_bytes`` defaults to
    :func:`apex_tpu.ops.pallas_config.device_hbm_bytes`.
    ``stats_out``: optional dict that receives the per-device
    ``comms_bytes`` / ``peak_hbm_bytes`` estimates even when no check
    fires — the numbers bench.py reports. Returns a list of
    :class:`Finding`.
    """
    import jax

    name = name or getattr(fn, "__name__", "fn")
    _validate_checks(checks)

    closed = jax.make_jaxpr(fn)(*example_args)

    if _TRACE_CAPTURE is not None:
        # ISSUE 15: the memory-calibration tier re-compiles the SAME
        # (fn, args) triple the estimator modeled, so measured-vs-
        # modeled compares like for like. Captured before the specs are
        # flattened so the sink owns everything a jit needs.
        _TRACE_CAPTURE[name] = {
            "fn": fn, "example_args": example_args,
            "donate_argnums": donate_argnums, "closed": closed,
        }

    flat_specs = _flatten_specs(example_args, in_specs)
    in_vals = []
    for i, var in enumerate(closed.jaxpr.invars):
        spec = flat_specs[i] if i < len(flat_specs) else None
        ndim = len(getattr(var.aval, "shape", ()) or ())
        # None means UNKNOWN (the engine stays quiet about this input);
        # an explicit P() asserts full replication and is checked
        in_vals.append(ShardVal(spec=None) if spec is None
                       else ShardVal(spec=normalize_spec(spec, ndim)))

    donated = set()
    if donate_argnums:
        import jax as _jax
        donate = {donate_argnums} if isinstance(donate_argnums, int) \
            else set(donate_argnums)
        idx = 0
        for argnum, arg in enumerate(example_args):
            n = len(_jax.tree_util.tree_leaves(arg))
            if argnum in donate:
                donated.update(range(idx, idx + n))
            idx += n

    return analyze_sharding_jaxpr(
        closed, in_vals, name=name, donated=donated,
        axis_sizes=axis_sizes, checks=checks,
        hbm_budget_bytes=hbm_budget_bytes,
        replicated_threshold_bytes=replicated_threshold_bytes,
        stats_out=stats_out)


def _validate_checks(checks):
    """The requested check-id set, validated loudly (and BEFORE any
    expensive trace a caller is about to pay for)."""
    run = set(checks or SHARDING_CHECKS)
    unknown = run - set(SHARDING_CHECKS)
    if unknown:
        raise ValueError(
            f"unknown sharding check(s) {sorted(unknown)}; valid: "
            f"{list(SHARDING_CHECKS)}")
    return run


def analyze_sharding_jaxpr(closed, in_vals, *, name, donated=frozenset(),
                           axis_sizes=None, checks=None,
                           hbm_budget_bytes=None,
                           replicated_threshold_bytes=None,
                           stats_out=None):
    """Jaxpr-level entry: run the sharding-flow checks over an
    already-traced ``ClosedJaxpr`` with explicit per-invar
    :class:`ShardVal` inputs and flat donated indices.

    This is :func:`analyze_sharding` minus the tracing — the hook the
    auto-sharding planner (:mod:`.planner`) uses to re-check every
    candidate layout against one trace, so the plan it emits is vetted
    by exactly the analyses that gate the repo."""
    path = f"<jaxpr:{name}>"
    run = _validate_checks(checks)
    if axis_sizes is None:
        axis_sizes = live_mesh_axis_sizes()
    if replicated_threshold_bytes is None:
        replicated_threshold_bytes = DEFAULT_REPLICATED_THRESHOLD

    ctx = _Ctx(name, path)
    visitors = [_VISITORS[c] for c in SHARDING_CHECKS
                if c in run and c in _VISITORS]

    def visit(eqn, ins, outs, mctx):
        for v in visitors:
            v(ctx, eqn, ins, outs, mctx)

    interpret_sharding(closed, in_vals, axis_sizes=axis_sizes,
                       visit=visit if visitors else None)

    if "replicated-large" in run:
        _check_replicated_large(ctx, closed, in_vals, axis_sizes,
                                replicated_threshold_bytes)

    stats = estimate_hbm_and_comms(closed, in_vals, donated=donated,
                                   axis_sizes=axis_sizes)
    if stats_out is not None:
        stats_out.update(stats)

    if "hbm-budget" in run:
        if hbm_budget_bytes is None:
            from apex_tpu.ops.pallas_config import device_hbm_bytes
            hbm_budget_bytes = device_hbm_bytes()
        peak = stats["peak_hbm_bytes"]
        if peak > hbm_budget_bytes:
            ctx.add(
                "hbm-budget", "error",
                f"estimated peak live HBM {_fmt_bytes(peak)} per "
                f"device (step {stats['peak_step']} of the linearized "
                f"program, donation credit applied) exceeds the "
                f"{_fmt_bytes(hbm_budget_bytes)} budget — shard or "
                f"donate the big buffers, or raise the budget "
                f"(APEX_TPU_HBM_BYTES / device_hbm_bytes) if the "
                f"target really has more HBM")

    return ctx.findings


def report_to_registry(results, registry=None):
    """Publish sharding findings + per-target estimates as the
    ``analysis/sharding_*`` metric family.

    ``results``: {target name: (findings list, stats dict)}. Counters:
    ``analysis/sharding_findings{check=}``; gauges:
    ``analysis/sharding_findings_total``,
    ``analysis/sharding_comms_bytes{target=}``,
    ``analysis/sharding_peak_hbm_bytes{target=}``. Returns
    {check id: count}.
    """
    from apex_tpu.observability import get_registry

    reg = registry if registry is not None else get_registry()
    counts = {c: 0 for c in SHARDING_CHECKS}
    for target, (findings, stats) in sorted(results.items()):
        for f in findings:
            if f.check in counts:
                counts[f.check] += 1
        if stats:
            reg.gauge("analysis/sharding_comms_bytes",
                      target=target).set(stats.get("comms_bytes", 0))
            reg.gauge("analysis/sharding_peak_hbm_bytes",
                      target=target).set(stats.get("peak_hbm_bytes", 0))
    for check, n in counts.items():
        if n:
            reg.counter("analysis/sharding_findings", check=check).inc(n)
    reg.gauge("analysis/sharding_findings_total").set(
        sum(counts.values()))
    return counts
