"""Unified multi-lattice forward abstract interpretation over closed
jaxprs — the one traversal under the precision AND sharding engines
(ISSUE 8 prerequisite refactor).

:mod:`.dataflow` (dtype/taint lattice, precision checks) and
:mod:`.sharding_flow` (PartitionSpec/distinctness lattice, sharding
checks) used to carry two near-identical interpreters: the same env
bookkeeping, the same ``pjit``/``remat``/``scan``/``while``/``cond``/
``shard_map`` structural walk, duplicated and drifting independently.
This module owns that walk ONCE; each engine plugs in as a
:class:`Lattice` — a bundle of value semantics (initial values, the
per-equation transfer function, branch/carry joins, call-boundary
coercions, the shard_map world rule). Several lattices ride the same
traversal: one pass over the jaxpr computes every engine's values and
fires every engine's visitors, which is what makes the auto-sharding
planner's inner loop (many spec candidates x one jaxpr) and the
combined lint gate cheap.

Structural semantics are lattice-selectable where the engines
legitimately differ:

- ``warm_carry_join``   scan/while bodies run once silently first and
  the output carries are joined into the input carries (the sharding
  engine's steady-state fixpoint); lattices that opt out (precision —
  every check there fires on iteration 1) keep their original inputs,
  so a mixed run changes neither engine's verdicts. The silent warm
  pass is skipped entirely when no participating lattice wants it.
- ``shard_map_enter/exit``  the sharding engine treats shard_map as a
  world boundary (specs stripped to distinctness, outer spec rebuilt
  from out_names); the precision engine enters it like any call. Both
  are expressed as lattice hooks over the same single body traversal.

Entry point: :func:`interpret_lattices`. The single-engine entry
points (``dataflow.interpret``, ``sharding_flow.interpret_sharding``)
are thin wrappers that pass exactly one lattice.

ISSUE 9 adds a third domain: :class:`NonFiniteLattice`, the
non-finite taint lattice under
:mod:`apex_tpu.observability.numerics.nan_probe`. Unlike the precision
and sharding lattices it carries CONCRETE values when it can (the
probe replays the failing step's jaxpr with the actual tensors), so
"did this primitive produce the first NaN?" is answered by evaluating
the primitive, not by approximating it — with a pure taint fallback
(any non-finite input taints every output) wherever concrete replay is
impossible (pallas kernels, shape-changing structural re-entries).
"""

from __future__ import annotations

__all__ = ["Lattice", "LatticeRun", "MeshCtx", "NFVal",
           "NonFiniteLattice", "interpret_lattices",
           "run_lattice_silent"]

# Call-like primitives whose bodies run in the caller's value world.
CALL_PRIMS = frozenset({
    "jit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "remat", "checkpoint",
})

_SUB_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def is_var(v):
    import jax.extend.core as core
    return isinstance(v, core.Var)


def closed_jaxprs_in(value):
    import jax.extend.core as core
    out = []
    if isinstance(value, (core.ClosedJaxpr, core.Jaxpr)):
        out.append(value)
    elif isinstance(value, (tuple, list)):
        for v in value:
            out.extend(closed_jaxprs_in(v))
    return out


def jaxpr_of(obj):
    import jax.extend.core as core
    return obj.jaxpr if isinstance(obj, core.ClosedJaxpr) else obj


def consts_of(obj):
    import jax.extend.core as core
    return obj.consts if isinstance(obj, core.ClosedJaxpr) else ()


class MeshCtx:
    """Axis universe the interpretation runs under: name -> size, plus
    the manual (shard_map-consumed) axes at the current depth.

    ``control`` is the divergent-control stack (ISSUE 14): one
    ``(prim, axes)`` entry per enclosing ``cond``/``while`` whose
    predicate some participating lattice declared rank-divergent
    (:meth:`Lattice.divergent_axes`). A visitor that sees a collective
    while the stack carries a non-empty entry knows the collective's
    issue is conditional on a value that differs across those mesh
    axes — the deadlock/desync shape the rank-consistency checks
    exist for."""

    def __init__(self, axis_sizes=None, manual_axes=frozenset(),
                 control=()):
        self.axis_sizes = dict(axis_sizes or {})
        self.manual_axes = frozenset(manual_axes)
        self.control = tuple(control)

    def size(self, axis, default=1) -> int:
        return int(self.axis_sizes.get(axis, default))

    def child(self, extra_sizes=None, extra_manual=()):
        sizes = dict(self.axis_sizes)
        if extra_sizes:
            sizes.update({str(k): int(v) for k, v in extra_sizes.items()})
        return MeshCtx(sizes, self.manual_axes | frozenset(extra_manual),
                       self.control)

    def control_child(self, prim, axes):
        """The context for a ``cond``/``while`` body whose predicate
        can differ across ``axes``."""
        return MeshCtx(self.axis_sizes, self.manual_axes,
                       self.control + ((str(prim), frozenset(axes)),))

    def divergent_axes(self) -> frozenset:
        """Union of the control stack's divergent axes."""
        out = frozenset()
        for _prim, axes in self.control:
            out |= axes
        return out


def shard_map_names(eqn, which: str) -> tuple:
    """A shard_map equation's ``in_specs`` / ``out_specs`` (``which`` =
    "in" / "out") as one ``{dim: (axis, ...)}`` dict per operand — the
    form every lattice here consumes."""
    out = []
    for spec in eqn.params.get(f"{which}_specs", ()):
        names = {}
        for dim, entry in enumerate(spec):
            if entry is not None:
                names[dim] = entry if isinstance(entry, tuple) else (entry,)
        out.append(names)
    return tuple(out)


def shard_map_axis_sizes(eqn) -> dict:
    """The mesh axis sizes a shard_map equation introduces."""
    shape = getattr(eqn.params.get("mesh"), "shape", None)
    return {str(k): int(v) for k, v in dict(shape).items()} \
        if shape else {}


class Lattice:
    """Value semantics for one analysis domain (see module docstring).

    Subclasses must implement :meth:`for_aval` and :meth:`transfer`;
    everything else has call-transparent defaults matching the
    precision engine's behavior."""

    name = "lattice"
    # scan/while: run the body once silently and join the output
    # carries into the input carries before the visited pass.
    warm_carry_join = False

    # ---- values ------------------------------------------------------

    def for_aval(self, aval):
        raise NotImplementedError

    def for_const(self, var, const):
        return self.for_aval(getattr(var, "aval", None))

    def transfer(self, eqn, ins, out_avals, ctx):
        raise NotImplementedError

    # ---- call boundaries ---------------------------------------------

    def bind_sub(self, aval, val):
        """Coerce a caller value onto a sub-jaxpr invar (None = derive
        from the aval)."""
        return self.for_aval(aval) if val is None else val

    def fix_out(self, aval, val, restack=False):
        """Coerce a sub-jaxpr output onto the caller's out aval.
        ``restack`` marks stacked scan ys (which grow a leading dim)."""
        return self.for_aval(aval) if val is None else val

    # ---- joins -------------------------------------------------------

    def join_branch(self, a, b):
        """Join the same output slot across cond branches."""
        return a if a is not None else b

    def cond_branch_index(self, ins):
        """Index of the branch this lattice KNOWS will run (from its
        abstract view of the cond's index operand), or None to walk
        and join every branch. The walk honors it only when every
        participating lattice names the same branch — the abstract
        engines (precision/sharding) return None by design: their
        verdicts must cover all paths."""
        return None

    def join_carry(self, orig, warm):
        """Join a warm-pass output carry into the input carry; the
        default keeps the original (no fixpoint)."""
        return orig

    def divergent_axes(self, eqn, ins, ctx) -> frozenset:
        """Mesh axes across which this ``cond``/``while`` equation's
        predicate can DIFFER between ranks, in this lattice's view —
        the walk pushes the union onto :attr:`MeshCtx.control` for the
        body traversal. The default (every abstract engine that does
        not model rank distinctness) declares none."""
        return frozenset()

    # ---- scan / shard_map structure ----------------------------------

    def map_scan_xs(self, val):
        """Map an xs value across the scan boundary (the body sees it
        without the leading scan dim)."""
        return val

    def shard_map_enter(self, eqn, ins, sub, ctx):
        """Values bound to the shard_map body invars; the default enters
        like a call."""
        n = len(sub.invars)
        bound = list(ins[:n]) + [None] * max(0, n - len(ins))
        return [self.bind_sub(var.aval, val)
                for var, val in zip(sub.invars, bound)]

    def shard_map_exit(self, eqn, inner_outs, ctx):
        """Caller-world values for the shard_map outputs; the default
        exits like a call."""
        outs = []
        for i, var in enumerate(eqn.outvars):
            o = inner_outs[i] if i < len(inner_outs) else None
            outs.append(self.fix_out(var.aval, o))
        return outs


class LatticeRun:
    """One lattice's participation in a traversal: the lattice, its
    per-invar input values, and an optional
    ``visit(eqn, ins, outs, mesh_ctx)`` callback."""

    def __init__(self, lattice, in_vals=(), visit=None):
        self.lattice = lattice
        self.in_vals = list(in_vals or ())
        self.visit = visit


class _Walk:
    def __init__(self, lattices, visits):
        self.lattices = lattices
        self.visits = visits

    def _silent(self):
        return _Walk(self.lattices, [None] * len(self.lattices))

    def run(self, jaxpr, consts, in_cols, ctx):
        lats = self.lattices
        n_lat = len(lats)
        env: dict = {}

        def write(var, vals):
            if is_var(var):
                env[var] = vals

        consts = list(consts or ())
        for i, var in enumerate(jaxpr.constvars):
            if i < len(consts):
                write(var, [lat.for_const(var, consts[i])
                            for lat in lats])
            else:
                write(var, [lat.for_aval(var.aval) for lat in lats])
        for j, var in enumerate(jaxpr.invars):
            vals = []
            for k, lat in enumerate(lats):
                v = in_cols[k][j] if j < len(in_cols[k]) else None
                vals.append(v if v is not None else lat.for_aval(var.aval))
            write(var, vals)

        for eqn in jaxpr.eqns:
            rows = [env.get(v) if is_var(v) else None for v in eqn.invars]
            ins_cols = [tuple(row[k] if row is not None else None
                              for row in rows) for k in range(n_lat)]
            outs_cols = self._structured(eqn, ins_cols, ctx)
            if outs_cols is None:
                out_avals = tuple(v.aval for v in eqn.outvars)
                outs_cols = [lats[k].transfer(eqn, ins_cols[k],
                                              out_avals, ctx)
                             for k in range(n_lat)]
            for k, visit in enumerate(self.visits):
                if visit is not None:
                    visit(eqn, ins_cols[k], outs_cols[k], ctx)
            for j, var in enumerate(eqn.outvars):
                write(var, [outs_cols[k][j] for k in range(n_lat)])

        results = []
        for k, lat in enumerate(lats):
            out = []
            for v in jaxpr.outvars:
                row = env.get(v) if is_var(v) else None
                out.append(row[k] if row is not None
                           else lat.for_aval(getattr(v, "aval", None)))
            results.append(tuple(out))
        return results

    # ---- structured primitives ---------------------------------------

    def _structured(self, eqn, ins_cols, ctx):
        prim = eqn.primitive.name
        params = eqn.params

        if prim in CALL_PRIMS:
            for key in _SUB_JAXPR_KEYS:
                if key in params:
                    subs = closed_jaxprs_in(params[key])
                    if subs:
                        return self._run_sub(subs[0], ins_cols, eqn, ctx)
            return None

        if prim == "scan":
            subs = closed_jaxprs_in(params.get("jaxpr"))
            if not subs:
                return None
            n_consts = params.get("num_consts", 0)
            n_carry = params.get("num_carry", 0)
            mapped_cols = []
            for k, lat in enumerate(self.lattices):
                col = list(ins_cols[k])
                for i in range(n_consts + n_carry, len(col)):
                    if col[i] is not None:
                        col[i] = lat.map_scan_xs(col[i])
                mapped_cols.append(col)
            self._warm_carries(subs[0], mapped_cols, eqn, ctx,
                               carry_at=n_consts, n_carry=n_carry,
                               restack_from=n_carry)
            return self._run_sub(subs[0], mapped_cols, eqn, ctx,
                                 restack_from=n_carry)

        if prim == "while":
            subs = closed_jaxprs_in(params.get("body_jaxpr"))
            if not subs:
                return None
            n_cond = params.get("cond_nconsts", 0)
            body_cols = [list(col[n_cond:]) for col in ins_cols]
            n_body = params.get("body_nconsts", 0)
            self._warm_carries(subs[0], body_cols, eqn, ctx,
                               carry_at=n_body, n_carry=None)
            # divergence must be judged on the WARMED carries: a
            # predicate that only becomes rank-divergent through the
            # loop carry (per-rank early exit) is invisible on the
            # initial values. The warm pass itself is silent, so no
            # visitor misses the control context.
            warmed_ins = [
                list(ins_cols[k][:n_cond + n_body])
                + list(body_cols[k][n_body:])
                for k in range(len(self.lattices))]
            sub_ctx = self._control_ctx(eqn, warmed_ins, ctx)
            return self._run_sub(subs[0], body_cols, eqn, sub_ctx)

        if prim == "cond":
            branches = closed_jaxprs_in(params.get("branches", ()))
            if not branches:
                return None
            ctx = self._control_ctx(eqn, ins_cols, ctx)
            pred_less = [col[1:] for col in ins_cols]
            # concrete-replay lattices can name the branch that will
            # actually run; walking (and joining) the untaken branch
            # would blame its primitives for values that never existed
            picks = {lat.cond_branch_index(ins_cols[k])
                     for k, lat in enumerate(self.lattices)}
            if len(picks) == 1:
                pick = picks.pop()
                if pick is not None and 0 <= pick < len(branches):
                    return self._run_sub(branches[pick], pred_less,
                                         eqn, ctx)
            outs_cols = None
            for br in branches:
                br_cols = self._run_sub(br, pred_less, eqn, ctx)
                if outs_cols is None:
                    outs_cols = [list(c) for c in br_cols]
                else:
                    for k, lat in enumerate(self.lattices):
                        outs_cols[k] = [
                            lat.join_branch(a, b)
                            for a, b in zip(outs_cols[k], br_cols[k])]
            return [tuple(c) for c in outs_cols]

        if prim == "shard_map":
            subs = closed_jaxprs_in(params.get("jaxpr", ()))
            if not subs:
                return None
            sizes = shard_map_axis_sizes(eqn)
            inner_ctx = ctx.child(sizes, sizes.keys())
            sub = jaxpr_of(subs[0])
            inner_cols = [lat.shard_map_enter(eqn, ins_cols[k], sub, ctx)
                          for k, lat in enumerate(self.lattices)]
            inner_outs = _Walk(self.lattices, self.visits).run(
                sub, consts_of(subs[0]), inner_cols, inner_ctx)
            return [tuple(lat.shard_map_exit(eqn, inner_outs[k], ctx))
                    for k, lat in enumerate(self.lattices)]

        return None

    def _control_ctx(self, eqn, ins_cols, ctx):
        """Push a divergent-control entry for a cond/while body when any
        participating lattice declares the predicate rank-divergent
        (no-op context otherwise — the common case costs one call)."""
        axes = frozenset()
        for k, lat in enumerate(self.lattices):
            axes |= lat.divergent_axes(eqn, ins_cols[k], ctx)
        if not axes:
            return ctx
        return ctx.control_child(eqn.primitive.name, axes)

    def _warm_carries(self, sub, cols, eqn, ctx, carry_at, n_carry,
                      restack_from=None):
        """Silent warm pass + per-lattice carry join (in place) for the
        lattices that want the fixpoint. No-op when none do."""
        if not any(lat.warm_carry_join for lat in self.lattices):
            return
        warm_cols = self._silent()._run_sub(sub, cols, eqn, ctx,
                                            restack_from=restack_from)
        for k, lat in enumerate(self.lattices):
            if not lat.warm_carry_join:
                continue
            warm = warm_cols[k]
            stop = len(warm) if n_carry is None else min(n_carry,
                                                         len(warm))
            for c in range(stop):
                i = carry_at + c
                if i < len(cols[k]):
                    cols[k][i] = lat.join_carry(cols[k][i], warm[c])

    def _run_sub(self, closed_or_jaxpr, ins_cols, eqn, ctx,
                 restack_from=None):
        jaxpr = jaxpr_of(closed_or_jaxpr)
        consts = consts_of(closed_or_jaxpr)
        n = len(jaxpr.invars)
        mapped_cols = []
        for k, lat in enumerate(self.lattices):
            col = list(ins_cols[k][:n]) + [None] * max(
                0, n - len(ins_cols[k]))
            mapped_cols.append([lat.bind_sub(var.aval, val)
                                for var, val in zip(jaxpr.invars, col)])
        outs_cols = self.run(jaxpr, consts, mapped_cols, ctx)
        out_avals = tuple(v.aval for v in eqn.outvars)
        fixed_cols = []
        for k, lat in enumerate(self.lattices):
            outs = outs_cols[k]
            fixed = []
            for i, aval in enumerate(out_avals):
                o = outs[i] if i < len(outs) else None
                restack = restack_from is not None and i >= restack_from
                fixed.append(lat.fix_out(aval, o, restack=restack))
            fixed_cols.append(tuple(fixed))
        return fixed_cols


def run_lattice_silent(lattice, closed_or_jaxpr, in_vals, ctx):
    """Run ONE lattice over a (closed) jaxpr with no visitors and
    return its abstract outputs — the hook a lattice's own
    :meth:`Lattice.divergent_axes` uses to evaluate a while-loop's
    ``cond_jaxpr`` (which the main walk never enters: only the body
    carries values forward)."""
    jaxpr = jaxpr_of(closed_or_jaxpr)
    cols = [list(in_vals[:len(jaxpr.invars)])]
    cols[0] += [None] * (len(jaxpr.invars) - len(cols[0]))
    (outs,) = _Walk([lattice], [None]).run(
        jaxpr, consts_of(closed_or_jaxpr), cols, ctx)
    return outs


def interpret_lattices(closed, runs, axis_sizes=None):
    """Run every :class:`LatticeRun` in ``runs`` over ``closed`` (a
    ``ClosedJaxpr``) in ONE traversal.

    Each run's ``in_vals`` holds one abstract value (or None for
    "derive from the aval") per flat invar; its ``visit`` fires for
    every equation at every depth with that lattice's values. Returns
    one tuple of abstract output values per run, in order."""
    ctx = MeshCtx(axis_sizes or {})
    jaxpr = closed.jaxpr
    in_cols = []
    for run in runs:
        col = list(run.in_vals) + [None] * max(
            0, len(jaxpr.invars) - len(run.in_vals))
        in_cols.append(col)
    walk = _Walk([run.lattice for run in runs],
                 [run.visit for run in runs])
    return walk.run(jaxpr, closed.consts, in_cols, ctx)


# ------------------------------------------------- non-finite taint


class NFVal:
    """One point of the non-finite lattice: ``finite`` is True (proven
    finite), False (contains NaN/Inf), or None (unknown); ``val`` is
    the concrete array when the replay still has one."""

    __slots__ = ("finite", "val")

    def __init__(self, finite=None, val=None):
        self.finite = finite
        self.val = val

    @classmethod
    def known(cls, val):
        return cls(finite=_finite_of(val), val=val)

    def __repr__(self):
        return (f"NFVal(finite={self.finite}, "
                f"concrete={self.val is not None})")


def _finite_of(val):
    """True/False for arrays whose finiteness is checkable, None
    otherwise (opaque objects, exotic dtypes). Integer/bool values are
    finite by construction."""
    import numpy as np
    try:
        arr = np.asarray(val)
    except Exception:  # noqa: BLE001 — not an array-like
        return None
    if arr.dtype.kind in ("i", "u", "b"):
        return True
    if arr.dtype.kind not in ("f", "c"):
        return None
    try:
        if arr.dtype.itemsize < 4:  # bf16/f16/fp8: widen for the ufunc
            arr = arr.astype(np.float32)
        return bool(np.isfinite(arr).all())
    except Exception:  # noqa: BLE001 — ml_dtypes gap etc.
        return None


# Primitives concrete replay must not execute: kernels (a replay is a
# host-side post-mortem — running a device kernel eagerly from it can
# itself fail or hang) and effectful I/O.
_NO_EVAL_PRIMS = frozenset({
    "pallas_call", "infeed", "outfeed", "io_callback", "pure_callback",
    "custom_partitioning",
})


class NonFiniteLattice(Lattice):
    """Concrete-replay non-finite taint (see module docstring).

    ``transfer`` evaluates the equation with the concrete input values
    when every input is available (``prim.bind`` outside any trace =
    eager evaluation) and derives each output's finite flag from the
    result. When replay is impossible — an opaque kernel, a value
    already degraded to taint, a bind error from a structural
    approximation upstream — it falls back to the taint join: any
    known-non-finite input marks every output non-finite ("the taint
    reached this op"), all-finite inputs mark outputs finite only for
    NaN-incapable output dtypes, else unknown.
    """

    name = "nonfinite"

    def for_aval(self, aval):
        return NFVal()

    def for_const(self, var, const):
        return NFVal.known(const)

    def _literal_vals(self, eqn, ins):
        """Concrete input list, pulling Literal values straight off the
        equation (the walk hands None for non-Var inputs)."""
        vals = []
        for i, var in enumerate(eqn.invars):
            nf = ins[i] if i < len(ins) else None
            if nf is not None and nf.val is not None:
                vals.append(nf.val)
            elif nf is None and hasattr(var, "val"):
                vals.append(var.val)
            else:
                return None
        return vals

    def _taint_join(self, eqn, ins, out_avals):
        import numpy as np
        flags = []
        for i, var in enumerate(eqn.invars):
            nf = ins[i] if i < len(ins) else None
            if nf is not None:
                flags.append(nf.finite)
            elif hasattr(var, "val"):
                flags.append(_finite_of(var.val))
            else:
                flags.append(None)
        if any(f is False for f in flags):
            out = False
        elif all(f is True for f in flags):
            out = True
        else:
            out = None
        res = []
        for aval in out_avals:
            kind = np.dtype(getattr(aval, "dtype", np.float32)).kind \
                if hasattr(aval, "dtype") else "f"
            if kind in ("i", "u", "b"):
                res.append(NFVal(finite=True))
            else:
                res.append(NFVal(finite=out))
        return tuple(res)

    def transfer(self, eqn, ins, out_avals, ctx):
        prim = eqn.primitive
        if prim.name in _NO_EVAL_PRIMS:
            return self._taint_join(eqn, ins, out_avals)
        vals = self._literal_vals(eqn, ins)
        if vals is None:
            return self._taint_join(eqn, ins, out_avals)
        try:
            out = prim.bind(*vals, **eqn.params)
        except Exception:  # noqa: BLE001 — replay is best-effort; a
            # bind error (shape drift from a structural approximation,
            # an unsupported eager prim) degrades to taint, never kills
            # the probe
            return self._taint_join(eqn, ins, out_avals)
        outs = list(out) if prim.multiple_results else [out]
        if len(outs) != len(out_avals):
            return self._taint_join(eqn, ins, out_avals)
        return tuple(NFVal.known(o) for o in outs)

    # structural coercions: concrete values whose shape no longer
    # matches the target aval drop to flag-only (the finite verdict
    # still flows; downstream binds fall back to taint)

    def _coerce(self, aval, nf):
        if nf is None:
            return NFVal()
        if nf.val is not None and hasattr(aval, "shape") and \
                tuple(getattr(nf.val, "shape", ())) != tuple(aval.shape):
            return NFVal(finite=nf.finite)
        return nf

    def bind_sub(self, aval, val):
        return self._coerce(aval, val)

    def fix_out(self, aval, val, restack=False):
        if restack:
            return NFVal(finite=None if val is None else val.finite)
        return self._coerce(aval, val)

    def map_scan_xs(self, val):
        """The body sees one slice of the xs. A whole-array non-finite
        flag must survive the slicing: element 0 can be clean while
        the poison sits in a later row, and replaying the body with
        the clean slice would launder the taint — drop to flag-only so
        the body's first consuming primitive is still named."""
        if val is None or val.val is None:
            return val
        if val.finite is False:
            return NFVal(finite=False)
        try:
            return NFVal.known(val.val[0])
        except Exception:  # noqa: BLE001 — 0-d or exotic container
            return NFVal(finite=val.finite)

    def cond_branch_index(self, ins):
        """The cond's index operand (invar 0, an i32 after jax's
        bool→index conversion) is usually concrete in a replay: name
        the branch that actually runs so join_branch never blames the
        untaken one."""
        nf = ins[0] if ins else None
        if nf is None or nf.val is None:
            return None
        try:
            import numpy as np
            idx = np.asarray(nf.val)
            if idx.ndim != 0:
                return None
            return int(idx)
        except Exception:  # noqa: BLE001 — exotic index value
            return None

    def join_branch(self, a, b):
        if a is None or b is None:
            return a if b is None else b
        if a.finite is False or b.finite is False:
            return NFVal(finite=False)
        if a.finite is True and b.finite is True:
            return NFVal(finite=True)
        return NFVal()
