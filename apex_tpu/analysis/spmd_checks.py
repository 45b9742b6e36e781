"""SPMD rank-consistency checks — the static counterpart of the PR 11
fleet desync/straggler detectors (ISSUE 14 tentpole).

A multi-host step is one program run by every rank; the bug class that
kills fleets is the program *disagreeing with itself across ranks*: a
collective issued under a rank-divergent branch (some ranks enter, the
rest never arrive — deadlock, or a silent partial reduction), a
rank-derived value stored into state the out_specs claim is replicated
(the fingerprint desync PR 11 can only observe at runtime), RNG streams
that are coordinated when they must differ (or differ when they must
not), and host effects whose order the runtime never pinned. The fleet
observability tier makes these failures *visible*; this module makes
them *un-committable*, the same way the precision/sharding sanitizers
gate their bug classes at lint time.

The engine is :class:`RankConsistencyLattice`, a third value domain
plugged into the unified multi-lattice walk (:mod:`.interp`). Per jaxpr
``Var`` it tracks:

- ``distinct``      mesh axes across which the value can DIFFER between
  ranks — seeded by shard_map ``in_names`` (per-shard data),
  ``lax.axis_index`` (rank identity), and scatter-type collectives;
  cleared by reducing/gathering collectives (``psum``/``pmax``/
  ``all_gather`` make the value identical along their axes). This is
  the :mod:`.sharding_flow` ``distinct`` notion, re-derived here so the
  lattice also flows it through rank-indexed ``dynamic_slice``s and
  RNG, where the placement engine deliberately resets provenance.
- ``rank_origin``   the subset of ``distinct`` whose divergence traces
  to ``axis_index``/``process_index`` specifically — "this value IS a
  function of the rank id", the signature of the chaos one-rank-desync
  pattern (``where(rank == k, poisoned, x)``) as opposed to ordinary
  data parallelism.
- ``rng``           the value derives from a PRNG primitive
  (``threefry2x32``/``random_bits``/``random_fold_in``/...). Combined
  with ``distinct`` it distinguishes the two RNG failure modes below.
- ``leaked``        set only on shard_map OUTPUTS: the mesh axes the
  inner value was still distinct over although this output's
  ``out_names`` never mentions them — the out_spec claims replication
  the program does not establish.

Four checks ride the lattice (:data:`SPMD_CHECKS`; the fifth member of
the family, ``nondeterministic-collective-order``, is an AST check in
:mod:`.ast_checks` — collective ISSUE order is decided by host Python,
not by the jaxpr):

- ``collective-in-divergent-control``  a collective inside a ``cond``/
  ``while`` whose predicate is rank-distinct over an axis the
  collective rides: ranks disagree about whether (or how many times)
  the collective executes — the canonical SPMD deadlock. The interp
  walk carries the divergent-control stack (:attr:`MeshCtx.control`);
  this lattice pushes entries via :meth:`Lattice.divergent_axes`
  (while predicates are evaluated by running the ``cond_jaxpr`` under
  the same lattice).
- ``rank-divergent-update``  a shard_map output whose ``out_names``
  claim replication over an axis the value is still distinct on — no
  reducing collective intervened between the rank-divergent value and
  the store. Fired at the shard_map boundary (where the program itself
  declares the replication contract), plus optionally on declared
  ``replicated_outs`` slots for un-shard_mapped steps.
- ``uncoordinated-rng``  (a) a rank-distinct RNG-derived value reaching
  a replicated store — per-rank noise applied to supposedly-replicated
  state desyncs the fleet exactly like the update check, but the fix
  is different (fold the key identically everywhere, or reduce the
  noise); (b) a rank-INVARIANT random float (same stream on every
  rank) meeting rank-distinct data elementwise inside shard_map —
  every rank applies the same dropout/noise mask to different data,
  silently correlating what should be independent samples. Integer
  joins are exempt: ``fold_in(key, axis_index)`` — an integer op — IS
  the coordination idiom, not the bug.
- ``unordered-host-effect``  an ``io_callback(ordered=False)`` /
  ``debug_callback`` positioned between two collectives on the same
  axis with NO data dependency anchoring it to either (result unused
  by any collective operand, inputs not derived from any collective
  result): the runtime may interleave the host effect differently per
  rank, so cross-rank logs/telemetry disagree about which collective
  the effect preceded. The fleet probe's own call sites pass by
  construction — its enter token is barrier-tied INTO the collective
  operand and its exit callback is FED the collective's result.

Entry point: :func:`analyze_spmd` (mirrors ``analyze_sharding``); the
registered schedules live in :mod:`.targets` (``SPMD_TARGETS``) and the
per-run counts land in the ``analysis/spmd_*`` metric family.
"""

from __future__ import annotations

import dataclasses

from apex_tpu.analysis import interp
from apex_tpu.analysis.findings import Finding
from apex_tpu.analysis.sharding_flow import (
    COLLECTIVE_PRIMS,
    _axis_names_of as _axes_of,
)

SPMD_CHECKS = (
    "collective-in-divergent-control", "rank-divergent-update",
    "uncoordinated-rng", "unordered-host-effect",
)

#: collectives that make their output IDENTICAL across the ridden axes
#: (every rank holds the same reduced/gathered result)
_REDUCING_COLLECTIVES = frozenset({
    "psum", "psum_invariant", "pmin", "pmax", "all_gather",
    "all_gather_invariant",
})

#: collectives whose output remains (or becomes) per-rank distinct
_SCATTER_COLLECTIVES = frozenset({"psum_scatter", "reduce_scatter"})

#: PRNG primitives (raw threefry keys AND new-style typed keys)
_RNG_PRIMS = frozenset({
    "threefry2x32", "random_bits", "random_seed", "random_wrap",
    "random_unwrap", "random_fold_in", "random_split", "random_gamma",
})

#: unordered host-effect primitives the ordering check governs
_HOST_EFFECT_PRIMS = frozenset({"io_callback", "debug_callback"})

#: re-typing prims that move no bytes: a pbroadcast/pvary never makes a
#: value distinct, and never launders distinctness away either
_IDENTITY_PRIMS = frozenset({"pbroadcast", "pvary", "stop_gradient",
                             "copy", "optimization_barrier"})

#: genuinely elementwise joins — the only place the shared-stream RNG
#: pattern (b) applies (a gather/concatenate legitimately mixes worlds)
_ELEMENTWISE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "atan2",
    "nextafter", "add_any", "select_n",
})


@dataclasses.dataclass(frozen=True)
class RankVal:
    """One point of the rank-consistency lattice (module docstring)."""

    distinct: frozenset = frozenset()
    rank_origin: frozenset = frozenset()
    rng: bool = False
    leaked: frozenset = frozenset()
    leaked_origin: frozenset = frozenset()  # leaked ∩ rank-id-derived

    def with_(self, **kw) -> "RankVal":
        return dataclasses.replace(self, **kw)


_EMPTY = RankVal()


def _join(ins) -> RankVal:
    present = [v for v in ins if v is not None]
    if not present:
        return _EMPTY
    return RankVal(
        distinct=frozenset().union(*(v.distinct for v in present)),
        rank_origin=frozenset().union(
            *(v.rank_origin for v in present)),
        rng=any(v.rng for v in present))


class RankConsistencyLattice(interp.Lattice):
    """Rank-distinctness semantics over the unified walk. Scan/while
    carries run the warm fixpoint (a carry fed by a ppermute or a
    rank-indexed slice picks up distinctness on iteration 1);
    ``shard_map`` seeds distinctness from ``in_names`` on entry and
    audits the replication claim of ``out_names`` on exit (the
    ``leaked`` field the rank-divergent-update check reads)."""

    name = "rank"
    warm_carry_join = True

    def for_aval(self, aval):
        return _EMPTY

    def transfer(self, eqn, ins, out_avals, ctx):
        prim = eqn.primitive.name
        n_out = len(out_avals)

        if prim == "axis_index":
            axis = str(eqn.params.get("axis_name"))
            # a size-1 axis has exactly one rank: its index is the
            # constant 0 everywhere, never a divergence source (and
            # the default ctx size for an unknown axis is 1, so an
            # un-modeled mesh stays conservative-quiet, matching the
            # sharding engine's unknown-spec discipline)
            if ctx.size(axis) <= 1:
                return tuple(_EMPTY for _ in range(n_out))
            v = RankVal(distinct=frozenset({axis}),
                        rank_origin=frozenset({axis}))
            return tuple(v for _ in range(n_out))

        if prim in _IDENTITY_PRIMS:
            base = _join(ins)
            if prim == "optimization_barrier":
                # elementwise over the tuple: each output mirrors its
                # own operand, not the join (the probe token must not
                # taint the bucket it orders)
                return tuple(
                    (ins[i] if i < len(ins) and ins[i] is not None
                     else _EMPTY) for i in range(n_out))
            return tuple(base for _ in range(n_out))

        if prim in _REDUCING_COLLECTIVES:
            axes = frozenset(_axes_of(
                eqn.params.get(COLLECTIVE_PRIMS.get(prim, "axes"))))
            base = _join(ins)
            out = base.with_(distinct=base.distinct - axes,
                             rank_origin=base.rank_origin - axes)
            return tuple(out for _ in range(n_out))

        if prim in _SCATTER_COLLECTIVES:
            axes = frozenset(
                a for a in _axes_of(eqn.params.get(
                    COLLECTIVE_PRIMS.get(prim, "axis_name")))
                if ctx.size(a) > 1)  # a 1-rank scatter is the identity
            base = _join(ins)
            out = base.with_(distinct=base.distinct | axes)
            return tuple(out for _ in range(n_out))

        if prim in ("ppermute", "all_to_all"):
            # data moved between ranks is still per-rank data
            base = _join(ins)
            return tuple(base for _ in range(n_out))

        if prim in _RNG_PRIMS:
            base = _join(ins)
            out = base.with_(rng=True)
            return tuple(out for _ in range(n_out))

        # default: distinctness is contagious through every compute op
        # (incl. dynamic_slice with a rank-derived start: the slice
        # CONTENT differs per rank even when the operand is replicated)
        base = _join(ins)
        return tuple(base for _ in range(n_out))

    # ---- joins / structure ------------------------------------------

    def join_branch(self, a, b):
        if a is None:
            return b
        if b is None:
            return a
        return RankVal(distinct=a.distinct | b.distinct,
                       rank_origin=a.rank_origin | b.rank_origin,
                       rng=a.rng or b.rng)

    join_carry = join_branch

    def divergent_axes(self, eqn, ins, ctx) -> frozenset:
        prim = eqn.primitive.name
        if prim == "cond":
            pred = ins[0] if ins else None
            return pred.distinct if pred is not None else frozenset()
        if prim == "while":
            # the main walk only enters the BODY; the predicate lives in
            # cond_jaxpr(cond_consts ++ carry) — run it under this
            # lattice to see which axes it can differ over
            subs = interp.closed_jaxprs_in(
                eqn.params.get("cond_jaxpr"))
            if not subs:
                return frozenset()
            n_cond = eqn.params.get("cond_nconsts", 0)
            n_body = eqn.params.get("body_nconsts", 0)
            cond_ins = list(ins[:n_cond]) + list(ins[n_cond + n_body:])
            try:
                outs = interp.run_lattice_silent(
                    self, subs[0], cond_ins, ctx)
            except Exception:  # noqa: BLE001 — a malformed cond_jaxpr
                # must degrade to "not provably divergent", never kill
                # the whole analysis run
                return frozenset()
            axes = frozenset()
            for o in outs:
                if o is not None:
                    axes |= o.distinct
            return axes
        return frozenset()

    # ---- shard_map boundary -----------------------------------------

    def shard_map_enter(self, eqn, ins, sub, ctx):
        in_names = interp.shard_map_names(eqn, "in")
        sizes = interp.shard_map_axis_sizes(eqn)
        mapped = []
        for i, _var in enumerate(sub.invars):
            names = in_names[i] if i < len(in_names) else {}
            # in_names consumption of a size-1 axis cannot make
            # per-shard data differ (there is one shard) — leaving it
            # out keeps findings independent of the host device count
            # a degenerate mesh was built over
            consumed = frozenset(
                str(a) for axes in dict(names or {}).values()
                for a in axes if sizes.get(str(a), 1) > 1)
            outer = ins[i] if i < len(ins) else None
            base = outer if outer is not None else _EMPTY
            mapped.append(base.with_(
                distinct=base.distinct | consumed, leaked=frozenset()))
        return mapped

    def shard_map_exit(self, eqn, inner_outs, ctx):
        out_names = interp.shard_map_names(eqn, "out")
        mesh_axes = frozenset(interp.shard_map_axis_sizes(eqn))
        outs = []
        for i, _var in enumerate(eqn.outvars):
            names = out_names[i] if i < len(out_names) else {}
            declared = frozenset(
                str(a) for axes in dict(names or {}).values()
                for a in axes)
            inner = inner_outs[i] if i < len(inner_outs) else None
            if inner is None:
                outs.append(_EMPTY)
                continue
            # the replication claim: every mesh axis this shard_map
            # binds that out_names does NOT lay the value out over
            leaked = (inner.distinct & mesh_axes) - declared
            outs.append(RankVal(
                distinct=inner.distinct - mesh_axes,
                rank_origin=inner.rank_origin - mesh_axes,
                rng=inner.rng, leaked=leaked,
                leaked_origin=inner.rank_origin & leaked))
        return outs


RANK_LATTICE = RankConsistencyLattice()


# ------------------------------------------------------------- findings


def _fmt_axes(axes):
    return "/".join(f"'{a}'" for a in sorted(axes))


class _Ctx:
    def __init__(self, name, path, checks=frozenset(SPMD_CHECKS)):
        self.name = name
        self.path = path
        self.checks = frozenset(checks)
        self.findings = []
        self.seen = set()
        self.collectives = 0
        self.host_effects = 0

    def add(self, check, severity, message, dedup_key=None):
        if check not in self.checks:
            return
        if dedup_key is not None:
            key = (check,) + tuple(dedup_key)
            if key in self.seen:
                return
            self.seen.add(key)
        self.findings.append(Finding(
            check, severity, self.path, 0, self.name, message))


def _visit_divergent_control(ctx, eqn, ins, outs, mctx):
    prim = eqn.primitive.name
    if prim not in COLLECTIVE_PRIMS:
        return
    axes = frozenset(_axes_of(eqn.params.get(COLLECTIVE_PRIMS[prim])))
    for control_prim, div_axes in mctx.control:
        hit = axes & div_axes
        if hit:
            ctx.add(
                "collective-in-divergent-control", "error",
                f"'{prim}' over {_fmt_axes(axes)} is issued inside a "
                f"'{control_prim}' whose predicate can differ across "
                f"{_fmt_axes(hit)}: ranks disagree about whether (or "
                f"how many times) this collective executes — some "
                f"arrive, the rest never do, and the fleet deadlocks "
                f"(or silently reduces a partial group). Hoist the "
                f"collective out of the branch, or make the predicate "
                f"rank-invariant (reduce it first: "
                f"psum/pmax the flag over {_fmt_axes(hit)})",
                dedup_key=(prim, tuple(sorted(axes)), control_prim))


def _visit_shard_map_exit(ctx, eqn, ins, outs, mctx):
    """The replication-claim audit: emits ``rank-divergent-update``,
    or ``uncoordinated-rng`` for the RNG-derived form when that check
    is enabled (a disabled specific check degrades to the generic one
    — the divergence is real either way; ``_Ctx.add`` drops whatever
    the caller's ``checks=`` excluded)."""
    if eqn.primitive.name != "shard_map":
        return
    for i, out in enumerate(outs):
        if out is None or not out.leaked:
            continue
        if out.rng and "uncoordinated-rng" in ctx.checks:
            ctx.add(
                "uncoordinated-rng", "error",
                f"shard_map output {i} carries RNG-derived data that "
                f"can differ across {_fmt_axes(out.leaked)} although "
                f"its out_specs claim replication over "
                f"{'that axis' if len(out.leaked) == 1 else 'those axes'}"
                f": every rank applies its own random stream to state "
                f"the program treats as replicated — the fleet desyncs "
                f"on the first step. Derive the key identically on "
                f"every rank (fold with the step, not axis_index), or "
                f"reduce the randomized update before storing",
                dedup_key=("rng-out", i, tuple(sorted(out.leaked))))
            continue
        origin = out.leaked_origin
        how = (f"derives from lax.axis_index over "
               f"{_fmt_axes(origin)} (the one-rank-desync shape: a "
               f"rank-conditional write)" if origin else
               f"is per-rank data (sharded input reached this store "
               f"with no reducing collective on the path)")
        ctx.add(
            "rank-divergent-update", "error",
            f"shard_map output {i} can differ across "
            f"{_fmt_axes(out.leaked)} although its out_specs claim "
            f"replication: the value {how}. Stored into params/"
            f"optimizer state this is the PR 11 fingerprint desync, "
            f"made static — insert the missing psum/pmean over "
            f"{_fmt_axes(out.leaked)} before the store, or declare the "
            f"output sharded if per-rank state is intended",
            dedup_key=("out", i, tuple(sorted(out.leaked))))


def _visit_uncoordinated_rng(ctx, eqn, ins, outs, mctx):
    """Pattern (b): a rank-invariant random FLOAT meets rank-distinct
    data elementwise inside the manual (shard_map) world."""
    prim = eqn.primitive.name
    if prim not in _ELEMENTWISE_PRIMS or not mctx.manual_axes:
        return
    present = [(v, iv) for v, iv in zip(ins, eqn.invars)
               if v is not None]
    if len(present) < 2:
        return
    import numpy as np

    def _is_float(var):
        try:
            return np.dtype(str(var.aval.dtype)).kind == "f"
        except Exception:  # noqa: BLE001 — exotic dtype: not a sample
            return False

    shared_rng = [
        (v, iv) for v, iv in present
        if v.rng and not (v.distinct & mctx.manual_axes)
        and _is_float(iv)]
    distinct_data = [
        v for v, _ in present if (v.distinct & mctx.manual_axes)]
    if shared_rng and distinct_data:
        axes = frozenset().union(*(v.distinct for v in distinct_data)) \
            & mctx.manual_axes
        ctx.add(
            "uncoordinated-rng", "warning",
            f"'{prim}' applies a rank-INVARIANT random sample to data "
            f"that differs across {_fmt_axes(axes)}: every rank draws "
            f"the identical stream (same dropout/noise mask against "
            f"different shards), silently correlating what should be "
            f"independent samples — fold the PRNG key with "
            f"lax.axis_index({_fmt_axes(axes)}) so each rank gets its "
            f"own stream",
            dedup_key=("shared-stream", prim, tuple(sorted(axes))))


def _visitors_for(run):
    """The eqn visitors an analyze run needs. The shard_map-exit audit
    serves BOTH update/rng check ids (emission is gated per id inside
    `_Ctx.add`), so requesting either installs it."""
    visitors = []
    if "collective-in-divergent-control" in run:
        visitors.append(_visit_divergent_control)
    if {"rank-divergent-update", "uncoordinated-rng"} & run:
        visitors.append(_visit_shard_map_exit)
    if "uncoordinated-rng" in run:
        visitors.append(_visit_uncoordinated_rng)
    return visitors


# --------------------------------------- unordered host effects (walk)


def _flatten_body(jaxpr, env, steps):
    """Call prims inlined (caller-world var identity), everything else
    one step — the same-body linear order the interleaving check
    reasons over. Control-flow/shard_map bodies are collected as
    separate bodies by the caller."""
    def canon(v):
        while v in env:
            v = env[v]
        return v

    for eqn in jaxpr.eqns:
        sub = None
        if eqn.primitive.name in interp.CALL_PRIMS:
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                if key in eqn.params:
                    subs = interp.closed_jaxprs_in(eqn.params[key])
                    if subs:
                        sub = interp.jaxpr_of(subs[0])
                        break
        if sub is not None and len(sub.invars) == len(eqn.invars):
            for iv, ov in zip(sub.invars, eqn.invars):
                if interp.is_var(ov):
                    env[iv] = canon(ov)
            _flatten_body(sub, env, steps)
            for inner_ov, outer_ov in zip(sub.outvars, eqn.outvars):
                if interp.is_var(inner_ov):
                    env[outer_ov] = canon(inner_ov)
            continue
        reads = [canon(v) if interp.is_var(v) else None
                 for v in eqn.invars]
        steps.append((eqn, reads))


def _iter_bodies(jaxpr):
    """Yield every distinct body (flattened step list) in the program:
    the top level, and each control-flow / shard_map sub-body."""
    env: dict = {}
    steps: list = []
    _flatten_body(jaxpr, env, steps)
    yield steps
    for eqn, _reads in steps:
        if eqn.primitive.name in interp.CALL_PRIMS:
            continue
        for value in eqn.params.values():
            for sub in interp.closed_jaxprs_in(value):
                yield from _iter_bodies(interp.jaxpr_of(sub))


def _is_unordered_effect(eqn) -> bool:
    prim = eqn.primitive.name
    if prim not in _HOST_EFFECT_PRIMS:
        return False
    if prim == "io_callback":
        return not bool(eqn.params.get("ordered", False))
    return True  # debug_callback carries no ordering guarantee


def _check_unordered_effects(ctx, closed):
    """Per body: unanchored unordered host effects positioned between
    two collectives on the same axis."""
    for steps in _iter_bodies(closed.jaxpr):
        collectives = []   # (pos, axes, eqn)
        effects = []       # (pos, eqn, reads)
        for pos, (eqn, reads) in enumerate(steps):
            prim = eqn.primitive.name
            if prim in COLLECTIVE_PRIMS:
                axes = frozenset(_axes_of(
                    eqn.params.get(COLLECTIVE_PRIMS[prim])))
                collectives.append((pos, axes, eqn))
            elif _is_unordered_effect(eqn):
                effects.append((pos, eqn, reads))
        ctx.collectives += len(collectives)
        ctx.host_effects += len(effects)
        if not effects or len(collectives) < 2:
            continue

        # forward: vars (transitively) derived from a collective result
        derived = set()
        # reverse: vars that (transitively) feed a collective operand
        feeds = set()
        for eqn, reads in steps:
            if any(r is not None and r in derived for r in reads) or \
                    eqn.primitive.name in COLLECTIVE_PRIMS:
                derived.update(v for v in eqn.outvars
                               if interp.is_var(v))
        for eqn, reads in reversed(steps):
            if eqn.primitive.name in COLLECTIVE_PRIMS or \
                    any(v in feeds for v in eqn.outvars
                        if interp.is_var(v)):
                feeds.update(r for r in reads if r is not None)

        for pos, eqn, reads in effects:
            anchored = any(r is not None and r in derived
                           for r in reads) or \
                any(interp.is_var(v) and v in feeds
                    for v in eqn.outvars)
            if anchored:
                continue
            between = sorted(
                axes_hit
                for (p0, a0, _e0) in collectives
                for (p1, a1, _e1) in collectives
                for axes_hit in (a0 & a1,)
                if p0 < pos < p1 and axes_hit)
            if not between:
                continue
            axes = between[0]
            ctx.add(
                "unordered-host-effect", "warning",
                f"'{eqn.primitive.name}' with no ordering guarantee "
                f"(ordered=False) sits between collectives over "
                f"{_fmt_axes(axes)} with no data dependency tying it "
                f"to either: the runtime may interleave the host "
                f"effect differently on each rank, so cross-rank "
                f"logs/telemetry disagree about which collective it "
                f"preceded — anchor it like the fleet probe does "
                f"(barrier-tie its token into the collective operand, "
                f"or feed it the collective's result), or pass "
                f"ordered=True",
                dedup_key=(eqn.primitive.name, pos))


# --------------------------------------------------------------- entry


def analyze_spmd(fn, *example_args, name=None, in_distinct=None,
                 replicated_outs=None, axis_sizes=None, checks=None,
                 stats_out=None):
    """Trace ``fn`` and run the rank-consistency checks over its jaxpr.

    ``in_distinct``: {argnum: iterable of mesh axes} marking positional
    arguments whose leaves already differ per rank when the traced fn
    is NOT a shard_map (inside one, ``in_names`` seed distinctness
    automatically). ``replicated_outs``: flat output slots that must be
    rank-invariant — a sequence of indices (no divergence allowed), or
    {index: allowed-axes} (divergence over the allowed axes is the
    declared sharding; anything else fires). shard_map outputs are
    audited against their own ``out_names`` regardless. ``stats_out``:
    optional dict receiving ``collectives`` / ``host_effects`` counts
    (UNORDERED host effects — the population the ordering check
    governs; the ``analysis/spmd_*`` gauges). Returns a list of
    :class:`Finding`.
    """
    import jax

    name = name or getattr(fn, "__name__", "fn")
    run = _validate_checks(checks)
    path = f"<jaxpr:{name}>"

    closed = jax.make_jaxpr(fn)(*example_args)

    in_vals = []
    flat_distinct = {}
    if in_distinct:
        idx = 0
        for argnum, arg in enumerate(example_args):
            n = len(jax.tree_util.tree_leaves(arg))
            if argnum in in_distinct:
                axes = frozenset(str(a) for a in in_distinct[argnum])
                for j in range(idx, idx + n):
                    flat_distinct[j] = axes
            idx += n
    for i, _var in enumerate(closed.jaxpr.invars):
        axes = flat_distinct.get(i)
        in_vals.append(RankVal(distinct=axes) if axes else None)

    ctx = _Ctx(name, path, checks=run)
    visitors = _visitors_for(run)

    def visit(eqn, ins, outs, mctx):
        for v in visitors:
            v(ctx, eqn, ins, outs, mctx)

    if axis_sizes is None:
        from apex_tpu.analysis.sharding_flow import live_mesh_axis_sizes
        axis_sizes = live_mesh_axis_sizes()
    (out_vals,) = interp.interpret_lattices(
        closed, [interp.LatticeRun(RANK_LATTICE, in_vals,
                                   visit if visitors else None)],
        axis_sizes=axis_sizes)

    if replicated_outs and ("rank-divergent-update" in run
                            or "uncoordinated-rng" in run):
        declared = (replicated_outs if isinstance(replicated_outs, dict)
                    else {i: () for i in replicated_outs})
        for i, allowed in sorted(declared.items()):
            if i >= len(out_vals) or out_vals[i] is None:
                continue
            bad = out_vals[i].distinct - frozenset(
                str(a) for a in allowed)
            if not bad:
                continue
            if out_vals[i].rng and "uncoordinated-rng" in run:
                ctx.add(
                    "uncoordinated-rng", "error",
                    f"output {i} is declared replicated but carries "
                    f"RNG-derived data that can differ across "
                    f"{_fmt_axes(bad)} — per-rank randomness reaching "
                    f"replicated state desyncs the fleet; coordinate "
                    f"the key or reduce before storing",
                    dedup_key=("declared-rng", i))
            elif "rank-divergent-update" in run:
                origin = out_vals[i].rank_origin & bad
                ctx.add(
                    "rank-divergent-update", "error",
                    f"output {i} is declared replicated but can differ "
                    f"across {_fmt_axes(bad)}"
                    + (f" (derives from lax.axis_index over "
                       f"{_fmt_axes(origin)})" if origin else "")
                    + " — insert the missing reducing collective "
                      "before the store",
                    dedup_key=("declared", i))

    if "unordered-host-effect" in run:
        _check_unordered_effects(ctx, closed)
    else:
        # stats stay populated either way (the gauges feed bench) —
        # counting the SAME predicate as the check path, so the
        # host_effects number never depends on which checks ran
        for steps in _iter_bodies(closed.jaxpr):
            for eqn, _reads in steps:
                if eqn.primitive.name in COLLECTIVE_PRIMS:
                    ctx.collectives += 1
                elif _is_unordered_effect(eqn):
                    ctx.host_effects += 1

    if stats_out is not None:
        stats_out.update({"collectives": ctx.collectives,
                          "host_effects": ctx.host_effects})
    return ctx.findings


def _validate_checks(checks):
    run = set(checks or SPMD_CHECKS)
    unknown = run - set(SPMD_CHECKS)
    if unknown:
        raise ValueError(
            f"unknown spmd check(s) {sorted(unknown)}; valid: "
            f"{list(SPMD_CHECKS)}")
    return run


def report_to_registry(results, registry=None):
    """Publish spmd findings + per-target collective counts as the
    ``analysis/spmd_*`` metric family.

    ``results``: {target name: (findings list, stats dict)}. Counters:
    ``analysis/spmd_findings{check=}``; gauges:
    ``analysis/spmd_findings_total``,
    ``analysis/spmd_collectives{target=}``,
    ``analysis/spmd_host_effects{target=}``. Returns {check id: count}.
    """
    from apex_tpu.observability import get_registry

    reg = registry if registry is not None else get_registry()
    counts = {c: 0 for c in SPMD_CHECKS}
    for target, (findings, stats) in sorted(results.items()):
        for f in findings:
            if f.check in counts:
                counts[f.check] += 1
        if stats:
            reg.gauge("analysis/spmd_collectives",
                      target=target).set(stats.get("collectives", 0))
            reg.gauge("analysis/spmd_host_effects",
                      target=target).set(stats.get("host_effects", 0))
    for check, n in counts.items():
        if n:
            reg.counter("analysis/spmd_findings", check=check).inc(n)
    reg.gauge("analysis/spmd_findings_total").set(sum(counts.values()))
    return counts
