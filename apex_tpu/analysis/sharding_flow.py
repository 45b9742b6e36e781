"""Forward sharding propagation over closed jaxprs — the flow engine
under the sharding checks (ISSUE 4 tentpole).

:mod:`.dataflow` answers dtype-flow questions; this module answers the
*placement* questions that decide whether a distributed step is fast or
silently all-gathers itself to death: where does every value live on
the mesh, which collectives actually move data, and how much HBM does
the live set peak at. The lattice tracks, per jaxpr ``Var``,

- ``spec``            the GSPMD-world partitioning: one entry per array
  dim, each a tuple of mesh axis names (or ``()`` for replicated);
  ``None`` means unknown (the analysis stays quiet rather than guess);
- ``pending``         mesh axes holding *unreduced partial sums* — a
  ``dot_general`` whose contracting dim was sharded produces per-shard
  partials that some later psum / sharding boundary must combine;
- ``distinct``        shard_map-world truth: the mesh axes across which
  the per-shard data can actually *differ*. ``pbroadcast``/``pvary``
  re-type a value without changing its bytes, so they do NOT add axes
  here — which is exactly how a psum of replicated data is caught as a
  dead collective;
- ``from_axis_index`` axes this (integer) value derives from
  ``lax.axis_index`` over — the signal that a dynamic_slice start is
  "my rank's chunk";
- ``psum_axes``       set while the value is (a preserve-chain of) a
  fresh ``psum`` result over those axes — the psum→slice
  reduce-scatter pattern detector's memory.

Sub-jaxprs are entered like :mod:`.dataflow` (``pjit``/``remat``/
``custom_vjp``/``scan``/``while``/``cond`` one-pass). ``shard_map`` is
the world boundary: entering strips the manual axes into ``distinct``;
leaving rebuilds the outer ``spec`` from ``out_names``. ``pallas_call``
stays opaque via in/out avals.

On top of the interpreter, :func:`estimate_hbm_and_comms` runs the
liveness walk: per-value local bytes (global aval bytes over the
product of the sharded axis sizes), last-use liveness with donation
credit (a donated input's buffer dies at its last read; a non-donated
input is caller-owned for the whole step), plus a per-collective
comms-bytes model. Clients subscribe with visitor callbacks;
:mod:`.sharding_checks` builds the five shipped analyses on top. The
engine itself never emits a Finding.
"""

from __future__ import annotations

import dataclasses
import math
import weakref

import numpy as np

from apex_tpu.analysis import interp
from apex_tpu.analysis.interp import MeshCtx

__all__ = [
    "ShardVal", "MeshCtx", "COLLECTIVE_PRIMS", "interpret_sharding",
    "ShardingLattice", "SHARDING_LATTICE",
    "shard_val_for_aval", "spec_from_partition_spec", "local_bytes",
    "collective_bytes", "estimate_hbm_and_comms", "normalize_spec",
    "Liveness", "compute_liveness", "prior_ratio_of",
]

# Call-like primitives whose bodies run in the caller's value world.
_CALL_PRIMS = interp.CALL_PRIMS

# Ops that preserve the value's identity: psum_axes / from_axis_index
# flow through (a reshaped psum result is still "the psum result").
_PRESERVE_PRIMS = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims",
    "stop_gradient", "copy", "convert_element_type", "neg",
    "pbroadcast", "pvary",
})

# Collectives with an axis-name param and (per-device, per-byte) comms
# cost factors as a function of the axis size n. psum is a ring
# allreduce (reduce-scatter + all-gather): 2(n-1)/n. all_gather
# receives the other n-1 shards. ppermute moves the whole block once.
COLLECTIVE_PRIMS = {
    "psum": "axes", "psum_invariant": "axes", "pmin": "axes",
    "pmax": "axes",
    "all_gather": "axis_name", "all_gather_invariant": "axis_name",
    "all_to_all": "axis_name", "reduce_scatter": "axis_name",
    "psum_scatter": "axis_name", "ppermute": "axis_name",
}

_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin",
})


def _axis_names_of(value):
    if value is None:
        return ()
    if isinstance(value, (tuple, list, frozenset, set)):
        out = []
        for v in value:
            out.extend(_axis_names_of(v))
        return tuple(out)
    return (str(value),)


def normalize_spec(partition_spec, ndim):
    """A PartitionSpec (or None) -> canonical per-dim tuple of
    axis-name tuples, padded to ``ndim``."""
    if partition_spec is None:
        return tuple(() for _ in range(ndim))
    entries = []
    for entry in tuple(partition_spec):
        entries.append(_axis_names_of(entry))
    while len(entries) < ndim:
        entries.append(())
    return tuple(entries[:ndim])


spec_from_partition_spec = normalize_spec


@dataclasses.dataclass(frozen=True)
class ShardVal:
    """One point of the sharding lattice (see module docstring)."""

    spec: tuple = None  # per-dim tuples of axis names; None = unknown
    pending: frozenset = frozenset()
    distinct: frozenset = frozenset()
    from_axis_index: frozenset = frozenset()
    psum_axes: frozenset = frozenset()

    def with_(self, **kw) -> "ShardVal":
        return dataclasses.replace(self, **kw)

    def axes_used(self) -> frozenset:
        if self.spec is None:
            return frozenset()
        return frozenset(a for entry in self.spec for a in entry)


def shard_val_for_aval(aval, partition_spec=None,
                       distinct=frozenset()) -> ShardVal:
    ndim = len(getattr(aval, "shape", ()) or ())
    return ShardVal(spec=normalize_spec(partition_spec, ndim),
                    distinct=frozenset(distinct))


def _aval_bytes(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()) or ())
    dtype = getattr(aval, "dtype", "float32")
    try:
        itemsize = np.dtype(str(dtype)).itemsize
    except TypeError:
        # exotic dtypes numpy cannot parse by name — jax's float0
        # tangent (zero bytes) being the one AD actually produces (an
        # int-input value_and_grad trace carries it); trust the dtype's
        # own itemsize when it has one
        itemsize = getattr(dtype, "itemsize", 0) or 0
    return math.prod(shape or (1,)) * itemsize


def local_bytes(aval, val, ctx: MeshCtx) -> int:
    """Per-device bytes of ``aval`` under ``val``'s sharding (global
    bytes over the product of the sharded axis sizes; unknown specs
    count as replicated — the conservative direction for HBM)."""
    nbytes = _aval_bytes(aval)
    if val is None or val.spec is None:
        return nbytes
    denom = 1
    for entry in val.spec:
        for axis in entry:
            denom *= ctx.size(axis)
    return max(1, nbytes // max(1, denom))


def collective_bytes(prim: str, nbytes: int, axis_sizes) -> int:
    """Per-device bytes moved by collective ``prim`` over a per-shard
    operand of ``nbytes`` riding axes of the given sizes."""
    n = 1
    for s in axis_sizes:
        n *= max(1, int(s))
    if n <= 1:
        return 0
    if prim in ("psum", "psum_invariant", "pmin", "pmax"):
        return int(2 * nbytes * (n - 1) / n)
    if prim in ("all_gather", "all_gather_invariant"):
        return nbytes * (n - 1)
    if prim in ("reduce_scatter", "psum_scatter", "all_to_all"):
        return int(nbytes * (n - 1) / n)
    if prim == "ppermute":
        return nbytes
    return nbytes


# ----------------------------------------------------------- transfer

def _merge_specs(specs):
    """Elementwise join of same-rank specs. Returns (spec, conflicts)
    where conflicts is a list of (dim, entry_a, entry_b) that disagree
    (both sharded, differently) — GSPMD has to reshard one side."""
    known = [s for s in specs if s is not None]
    if not known:
        return None, []
    rank = max(len(s) for s in known)
    out, conflicts = [], []
    for d in range(rank):
        entries = [s[d] for s in known if len(s) == rank and s[d]]
        if not entries:
            out.append(())
            continue
        first = entries[0]
        for other in entries[1:]:
            if other != first:
                conflicts.append((d, first, other))
        out.append(first)
    # one mesh axis cannot shard two dims: keep the first occurrence
    seen = set()
    cleaned = []
    for entry in out:
        kept = tuple(a for a in entry if a not in seen)
        seen.update(kept)
        cleaned.append(kept)
    return tuple(cleaned), conflicts


def _join(ins, out_aval):
    present = [v for v in ins if v is not None]
    ndim = len(getattr(out_aval, "shape", ()) or ())
    same_rank = [v.spec for v in present
                 if v.spec is not None and len(v.spec) == ndim]
    spec, _ = _merge_specs(same_rank) if same_rank else (None, [])
    if spec is None and ndim == 0:
        spec = ()
    return ShardVal(
        spec=spec,
        pending=frozenset().union(*(v.pending for v in present))
        if present else frozenset(),
        distinct=frozenset().union(*(v.distinct for v in present))
        if present else frozenset(),
        from_axis_index=frozenset().union(
            *(v.from_axis_index for v in present))
        if present else frozenset(),
    )


def _reshape_spec(spec, in_shape, out_shape):
    """Map a spec across reshape. Dims whose sizes match positionally
    from the front/back keep their entries; anything in the mixed
    middle goes unknown-replicated (the quiet, no-false-positive
    choice)."""
    if spec is None:
        return None
    out = [()] * len(out_shape)
    i = 0
    while (i < len(in_shape) and i < len(out_shape)
           and in_shape[i] == out_shape[i]):
        out[i] = spec[i]
        i += 1
    j = 0
    while (j < len(in_shape) - i and j < len(out_shape) - i
           and in_shape[-1 - j] == out_shape[-1 - j]):
        out[len(out_shape) - 1 - j] = spec[len(in_shape) - 1 - j]
        j += 1
    # an axis must not survive twice after the positional match
    seen = set()
    cleaned = []
    for entry in out:
        kept = tuple(a for a in entry if a not in seen)
        seen.update(kept)
        cleaned.append(kept)
    return tuple(cleaned)


def _dot_general_transfer(eqn, ins, out_aval):
    lhs, rhs = (ins + (None, None))[:2]
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    lhs_spec = lhs.spec if lhs is not None else None
    rhs_spec = rhs.spec if rhs is not None else None
    base = _join(ins, out_aval)
    pending = set(base.pending)
    for spec, cdims in ((lhs_spec, lc), (rhs_spec, rc)):
        if spec is None:
            continue
        for d in cdims:
            if d < len(spec):
                pending.update(spec[d])
    out_spec = None
    if lhs_spec is not None and rhs_spec is not None:
        entries = [lhs_spec[d] for d in lb]
        entries += [lhs_spec[d] for d in range(len(lhs_spec))
                    if d not in lc and d not in lb]
        entries += [rhs_spec[d] for d in range(len(rhs_spec))
                    if d not in rc and d not in rb]
        seen = set()
        cleaned = []
        for entry in entries:
            kept = tuple(a for a in entry if a not in seen)
            seen.update(kept)
            cleaned.append(kept)
        ndim = len(getattr(out_aval, "shape", ()) or ())
        while len(cleaned) < ndim:
            cleaned.append(())
        out_spec = tuple(cleaned[:ndim])
    return base.with_(spec=out_spec, pending=frozenset(pending),
                      from_axis_index=frozenset())


def _transfer(eqn, ins, out_avals, ctx: MeshCtx):
    prim = eqn.primitive.name
    src = next((v for v in ins if v is not None), None)

    if prim in _PRESERVE_PRIMS:
        outs = []
        for aval in out_avals:
            ndim = len(getattr(aval, "shape", ()) or ())
            if src is None:
                outs.append(shard_val_for_aval(aval))
                continue
            if prim == "reshape":
                spec = _reshape_spec(src.spec,
                                     tuple(eqn.invars[0].aval.shape),
                                     tuple(aval.shape))
            elif prim == "broadcast_in_dim":
                spec = [()] * ndim
                bdims = eqn.params.get("broadcast_dimensions", ())
                if src.spec is not None:
                    for sdim, odim in enumerate(bdims):
                        if sdim < len(src.spec) and odim < ndim:
                            spec[odim] = src.spec[sdim]
                spec = tuple(spec)
            elif src.spec is not None and len(src.spec) == ndim:
                spec = src.spec
            else:
                spec = normalize_spec(None, ndim)
            outs.append(src.with_(spec=spec))
        return tuple(outs)

    if prim == "transpose":
        perm = eqn.params.get("permutation", ())
        spec = None
        if src is not None and src.spec is not None:
            spec = tuple(src.spec[p] if p < len(src.spec) else ()
                         for p in perm)
        base = src if src is not None else ShardVal()
        return tuple(base.with_(spec=spec) for _ in out_avals)

    if prim == "dot_general":
        return tuple(_dot_general_transfer(eqn, tuple(ins), a)
                     for a in out_avals)

    if prim in _REDUCE_PRIMS or prim in ("reduce_window_sum",):
        dims = set(eqn.params.get("axes", ()) or ())
        base = _join(ins, out_avals[0])
        pending = set(base.pending)
        spec = None
        if src is not None and src.spec is not None:
            spec = []
            for d, entry in enumerate(src.spec):
                if d in dims:
                    pending.update(entry)
                else:
                    spec.append(entry)
            spec = tuple(spec)
        return tuple(base.with_(spec=spec, pending=frozenset(pending),
                                from_axis_index=frozenset())
                     for _ in out_avals)

    if prim == "axis_index":
        axis = str(eqn.params.get("axis_name"))
        return tuple(ShardVal(spec=normalize_spec(None, 0),
                              distinct=frozenset({axis}),
                              from_axis_index=frozenset({axis}))
                     for _ in out_avals)

    if prim in ("psum", "psum_invariant", "pmin", "pmax"):
        axes = frozenset(_axis_names_of(eqn.params.get("axes")))
        base = _join(ins, out_avals[0])
        return tuple(base.with_(
            pending=base.pending - axes,
            distinct=base.distinct - axes,
            psum_axes=axes if prim in ("psum", "psum_invariant") else frozenset(),
            from_axis_index=frozenset(),
        ) for _ in out_avals)

    if prim in ("all_gather", "all_gather_invariant"):
        axes = frozenset(_axis_names_of(eqn.params.get("axis_name")))
        base = _join(ins, out_avals[0])
        ndim = len(getattr(out_avals[0], "shape", ()) or ())
        return tuple(base.with_(spec=normalize_spec(None, ndim),
                                distinct=base.distinct - axes,
                                psum_axes=frozenset(),
                                from_axis_index=frozenset())
                     for _ in out_avals)

    if prim in ("psum_scatter", "reduce_scatter"):
        axes = frozenset(_axis_names_of(eqn.params.get("axis_name")))
        base = _join(ins, out_avals[0])
        return tuple(base.with_(distinct=base.distinct | axes,
                                pending=base.pending - axes,
                                psum_axes=frozenset(),
                                from_axis_index=frozenset())
                     for _ in out_avals)

    if prim in ("ppermute", "all_to_all"):
        base = _join(ins, out_avals[0])
        return tuple(base.with_(psum_axes=frozenset(),
                                from_axis_index=frozenset())
                     for _ in out_avals)

    if prim == "sharding_constraint":
        sharding = eqn.params.get("sharding")
        pspec = getattr(sharding, "spec", None)
        base = src if src is not None else ShardVal()
        outs = []
        for aval in out_avals:
            ndim = len(getattr(aval, "shape", ()) or ())
            outs.append(base.with_(spec=normalize_spec(pspec, ndim),
                                   pending=frozenset()))
        return tuple(outs)

    if prim in ("slice", "dynamic_slice", "rev", "squeeze", "gather",
                "dynamic_update_slice", "scatter", "scatter-add",
                "select_n", "pad", "concatenate", "iota"):
        base = _join(ins, out_avals[0])
        if prim == "dynamic_slice" and ins and ins[0] is not None:
            # the slice keeps the operand's provenance so a following
            # check can see "this is a chunk of a psum result"
            base = base.with_(psum_axes=ins[0].psum_axes)
        outs = []
        for aval in out_avals:
            ndim = len(getattr(aval, "shape", ()) or ())
            spec = base.spec
            if spec is not None and len(spec) != ndim:
                spec = normalize_spec(None, ndim)
            elif spec is not None and prim in ("slice", "dynamic_slice",
                                               "dynamic_update_slice"):
                in_shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
                out_shape = tuple(getattr(aval, "shape", ()) or ())
                if len(in_shape) == ndim:
                    spec = tuple(
                        entry if in_shape[d] == out_shape[d] else ()
                        for d, entry in enumerate(spec))
            outs.append(base.with_(spec=spec))
        return tuple(outs)

    if prim == "pallas_call":
        present = [v for v in ins if v is not None]
        distinct = frozenset().union(*(v.distinct for v in present)) \
            if present else frozenset()
        return tuple(shard_val_for_aval(a, distinct=distinct)
                     for a in out_avals)

    base = _join(ins, out_avals[0])
    outs = []
    for aval in out_avals:
        ndim = len(getattr(aval, "shape", ()) or ())
        spec = base.spec
        if spec is not None and len(spec) != ndim:
            spec = None if ndim else ()
        outs.append(base.with_(spec=spec))
    return tuple(outs)


# ----------------------------------------------------------- interp

_is_var = interp.is_var
_closed_jaxprs_in = interp.closed_jaxprs_in
_jaxpr_of = interp.jaxpr_of
_consts_of = interp.consts_of


def _names_to_spec(names, ndim):
    """shard_map in_names/out_names entry ({dim: (axes,)}) -> spec."""
    spec = [()] * ndim
    for dim, axes in dict(names or {}).items():
        if int(dim) < ndim:
            spec[int(dim)] = tuple(str(a) for a in axes)
    return tuple(spec)


class ShardingLattice(interp.Lattice):
    """The placement value semantics, plugged into the unified walk
    (:mod:`.interp`). Scan/while carries run the two-pass fixpoint (a
    loop-carried value picks up distinctness on iteration 1 — e.g. a
    pipeline carry init'd to zeros but fed by a ppermute — so the body
    runs once silently and the output carries join into the input
    carries before the visited pass). ``shard_map`` is the world
    boundary: entering strips the manual axes into ``distinct``;
    leaving rebuilds the outer ``spec`` from ``out_names``."""

    name = "sharding"
    warm_carry_join = True

    def for_aval(self, aval):
        return shard_val_for_aval(aval)

    def transfer(self, eqn, ins, out_avals, ctx):
        return _transfer(eqn, ins, out_avals, ctx)

    def bind_sub(self, aval, val):
        ndim = len(getattr(aval, "shape", ()) or ())
        if val is None:
            return shard_val_for_aval(aval)
        if val.spec is not None and len(val.spec) != ndim:
            return val.with_(spec=normalize_spec(None, ndim))
        return val

    def fix_out(self, aval, val, restack=False):
        ndim = len(getattr(aval, "shape", ()) or ())
        if val is None:
            return shard_val_for_aval(aval)
        if val.spec is not None and len(val.spec) != ndim:
            if restack and len(val.spec) == ndim - 1:
                # stacked scan ys grow a leading (replicated) dim
                return val.with_(spec=((),) + val.spec)
            return val.with_(spec=normalize_spec(None, ndim))
        return val

    def join_branch(self, a, b):
        if a is None:
            return b
        if b is None:
            return a
        spec, _ = _merge_specs([a.spec, b.spec]) \
            if a.spec is not None and b.spec is not None \
            and len(a.spec) == len(b.spec) else (None, [])
        return a.with_(
            spec=spec if spec is not None else a.spec,
            pending=a.pending | b.pending,
            distinct=a.distinct | b.distinct,
            from_axis_index=a.from_axis_index | b.from_axis_index,
            psum_axes=a.psum_axes & b.psum_axes,
        )

    join_carry = join_branch

    def map_scan_xs(self, val):
        # xs lose their leading (scan) dim inside the body
        if val.spec:
            return val.with_(spec=val.spec[1:])
        return val

    def shard_map_enter(self, eqn, ins, sub, ctx):
        in_names = interp.shard_map_names(eqn, "in")
        mapped = []
        for i, var in enumerate(sub.invars):
            ndim = len(getattr(var.aval, "shape", ()) or ())
            names = in_names[i] if i < len(in_names) else {}
            consumed = frozenset(
                str(a) for axes in dict(names or {}).values()
                for a in axes)
            outer = ins[i] if i < len(ins) else None
            distinct = consumed | (outer.distinct if outer else
                                   frozenset())
            mapped.append(ShardVal(spec=normalize_spec(None, ndim),
                                   distinct=distinct))
        return mapped

    def shard_map_exit(self, eqn, inner_outs, ctx):
        out_names = interp.shard_map_names(eqn, "out")
        outs = []
        for i, var in enumerate(eqn.outvars):
            ndim = len(getattr(var.aval, "shape", ()) or ())
            names = out_names[i] if i < len(out_names) else {}
            inner = inner_outs[i] if i < len(inner_outs) else None
            pending = inner.pending if inner else frozenset()
            outs.append(ShardVal(spec=_names_to_spec(names, ndim),
                                 pending=pending,
                                 distinct=ctx.manual_axes & (
                                     inner.distinct if inner
                                     else frozenset())))
        return outs


SHARDING_LATTICE = ShardingLattice()


def interpret_sharding(closed, in_vals, axis_sizes=None, visit=None):
    """Run the forward sharding propagation over ``closed`` (a
    ``ClosedJaxpr``).

    ``in_vals``: one :class:`ShardVal` (or None) per flat invar.
    ``axis_sizes``: the mesh axis universe (name -> size); defaults to
    the live ``parallel_state`` mesh when initialized.
    ``visit(eqn, in_vals, out_vals, mesh_ctx)`` runs for every equation
    at every depth. Returns the abstract output values.
    """
    if axis_sizes is None:
        axis_sizes = live_mesh_axis_sizes()
    (outs,) = interp.interpret_lattices(
        closed, [interp.LatticeRun(SHARDING_LATTICE, in_vals, visit)],
        axis_sizes=axis_sizes)
    return outs


def live_mesh_axis_sizes() -> dict:
    """Axis sizes of the live ``parallel_state`` mesh, {} when none."""
    try:
        from apex_tpu.transformer import parallel_state
        if parallel_state.model_parallel_is_initialized():
            return {str(k): int(v) for k, v in
                    dict(parallel_state.get_mesh().shape).items()}
    except Exception:
        pass
    return {}


# ----------------------------------------------- liveness / HBM walk

def _linearize(jaxpr, env, steps):
    """Flatten call-like primitives into one step list (var identity
    mapped into the caller world, as in jaxpr_checks._linearize);
    control flow / shard_map / pallas stay opaque single steps."""
    def canon(v):
        while v in env:
            v = env[v]
        return v

    for eqn in jaxpr.eqns:
        sub = None
        if eqn.primitive.name in _CALL_PRIMS:
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                if key in eqn.params:
                    subs = _closed_jaxprs_in(eqn.params[key])
                    if subs:
                        sub = _jaxpr_of(subs[0])
                        break
        if sub is not None and len(sub.invars) == len(eqn.invars):
            for iv, ov in zip(sub.invars, eqn.invars):
                if _is_var(ov):
                    env[iv] = canon(ov)
            _linearize(sub, env, steps)
            for inner_ov, outer_ov in zip(sub.outvars, eqn.outvars):
                if _is_var(inner_ov):
                    env[outer_ov] = canon(inner_ov)
            continue
        reads = [canon(v) if _is_var(v) else None for v in eqn.invars]
        steps.append((eqn, reads))


# Linearization depends only on the jaxpr structure, never on in_vals
# or the mesh — memoize it so the planner's inner loop (many spec
# candidates x one jaxpr) pays the flattening walk once. Weak keys: the
# cache must not keep a traced program alive after its caller drops it.
_LINEARIZE_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _linearized(jaxpr):
    try:
        hit = _LINEARIZE_CACHE.get(jaxpr)
    except TypeError:  # unhashable/unweakrefable jaxpr: just rebuild
        hit = None
    if hit is None:
        env: dict = {}
        steps: list = []
        _linearize(jaxpr, env, steps)
        hit = (env, steps)
        try:
            _LINEARIZE_CACHE[jaxpr] = hit
        except TypeError:
            pass
    return hit


@dataclasses.dataclass
class Liveness:
    """Per-value live-interval record of one linearized walk — the ONE
    truth under both :func:`estimate_hbm_and_comms` and the
    memory-liveness checks (:mod:`.memory_checks`, ISSUE 19). Every
    field is in the canonical (caller-world) var namespace of
    :func:`_linearize`; steps index into the linearized program.

    ``births[cv]``/``deaths[cv]``: the half-open live interval (a var
    is live at step ``s`` iff ``births[cv] <= s < deaths[cv]``).
    Donation credit shows up as an early death: a donated invar that is
    not returned dies at ``last_use + 1`` instead of surviving the
    whole step."""

    ctx: MeshCtx
    env: dict
    steps: list
    vals: dict
    births: dict
    deaths: dict
    first_use: dict
    last_use: dict
    producer: dict          # canonical var -> (step idx, eqn)
    out_vars: frozenset
    donated_vars: frozenset
    invar_canon: tuple      # canonical var per flat invar index
    n_steps: int
    peak_hbm_bytes: int
    peak_step: int
    comms_bytes: int
    input_bytes: int
    output_bytes: int

    def canon(self, v):
        while v in self.env:
            v = self.env[v]
        return v

    def var_bytes(self, cv) -> int:
        return local_bytes(cv.aval, self.vals.get(cv), self.ctx)

    def live_at(self, step):
        """Canonical vars live at ``step`` (birth <= step < death)."""
        return [cv for cv, b in self.births.items()
                if b <= step < self.deaths[cv]]

    def live_at_peak(self):
        """The peak-composition record: ``(cv, bytes)`` pairs live at
        the modeled peak, largest first."""
        pairs = [(cv, self.var_bytes(cv))
                 for cv in self.live_at(self.peak_step)]
        pairs.sort(key=lambda p: (-p[1], str(p[0])))
        return pairs

    def steady_bytes(self) -> int:
        """Bytes still live when the step returns (outputs plus every
        caller-owned input/const) — the post-peak watermark the
        peak-spike check compares the transient peak against."""
        return sum(self.var_bytes(cv) for cv, d in self.deaths.items()
                   if d > self.n_steps)

    def donation_credit(self):
        """Per flat invar index: True when the input's buffer was
        donated AND actually credited (it dies before the step ends)."""
        out = {}
        for i, cv in enumerate(self.invar_canon):
            out[i] = cv in self.donated_vars and \
                self.deaths.get(cv, self.n_steps + 1) <= self.n_steps
        return out


def compute_liveness(closed, in_vals, donated=frozenset(),
                     axis_sizes=None) -> Liveness:
    """The liveness walk over the linearized program: propagate
    ShardVals, account comms, and assign every canonical var its
    birth/death interval with donation credit. Both the HBM estimator
    and the memory-liveness engine consume this record, so the two can
    never disagree on what is live when.
    """
    if axis_sizes is None:
        axis_sizes = live_mesh_axis_sizes()
    ctx = MeshCtx(axis_sizes)
    jaxpr = closed.jaxpr

    env, steps = _linearized(jaxpr)

    def canon(v):
        while v in env:
            v = env[v]
        return v

    # forward-propagate ShardVals over the linearized steps so every
    # var (at any inlined depth) has a sharding for its byte estimate
    vals: dict = {}
    comms = 0
    for i, var in enumerate(jaxpr.invars):
        v = in_vals[i] if i < len(in_vals) else None
        vals[var] = v if v is not None else shard_val_for_aval(var.aval)
    for var in jaxpr.constvars:
        vals[var] = shard_val_for_aval(var.aval)

    manual = ctx.manual_axes
    for eqn, reads in steps:
        prim = eqn.primitive.name
        ins = tuple(vals.get(r) if r is not None else None
                    for r in reads)
        if prim == "shard_map":
            out_names = interp.shard_map_names(eqn, "out")
            outs = []
            for k, ov in enumerate(eqn.outvars):
                ndim = len(getattr(ov.aval, "shape", ()) or ())
                names = out_names[k] if k < len(out_names) else {}
                outs.append(ShardVal(spec=_names_to_spec(names, ndim)))
            # collectives inside the opaque body still cost comms
            # (trip-count aware: a psum in a scanned body runs once
            # per iteration)
            comms += _control_flow_comms(eqn, ctx)
        elif prim in ("scan", "while", "cond"):
            outs = _transfer(eqn, ins,
                             tuple(v.aval for v in eqn.outvars), ctx)
            comms += _control_flow_comms(eqn, ctx)
        else:
            outs = _transfer(eqn, ins,
                             tuple(v.aval for v in eqn.outvars), ctx)
            if prim in _CALL_PRIMS:
                # a call prim _linearize could not inline (arity
                # mismatch): still sweep its body for collectives
                comms += _control_flow_comms(eqn, ctx)
            param = COLLECTIVE_PRIMS.get(prim)
            if param is not None:
                axes = _axis_names_of(eqn.params.get(param))
                # sum over ALL array operands: a tree psum moves every
                # leaf, not just the first
                nbytes = sum(
                    local_bytes(v.aval, ins[k] if k < len(ins) else
                                None, ctx)
                    for k, v in enumerate(eqn.invars) if _is_var(v))
                comms += collective_bytes(
                    prim, nbytes, [ctx.size(a) for a in axes])
            if prim == "sharding_constraint" and ins and \
                    ins[0] is not None:
                before = ins[0]
                after = outs[0]
                if before.pending:
                    # the boundary resolves partial sums: GSPMD inserts
                    # the allreduce the row-parallel pattern relies on
                    nb = local_bytes(eqn.invars[0].aval, after, ctx)
                    comms += collective_bytes(
                        "psum", nb, [ctx.size(a) for a in before.pending])
                if before.spec is not None and \
                        before.spec != after.spec:
                    gone = before.axes_used() - after.axes_used()
                    if gone:  # all-gather'd axes move the other shards
                        nb = local_bytes(eqn.invars[0].aval, before, ctx)
                        n = 1
                        for a in gone:
                            n *= ctx.size(a)
                        comms += nb * (n - 1)
        for var, val in zip(eqn.outvars, outs):
            vals[var] = val

    # liveness: birth/death step per canonical var
    first_use: dict = {}
    last_use: dict = {}
    for idx, (eqn, reads) in enumerate(steps):
        for r in reads:
            if r is not None:
                first_use.setdefault(r, idx)
                last_use[r] = idx
    out_vars = frozenset(canon(v) for v in jaxpr.outvars if _is_var(v))
    donated_vars = frozenset(canon(jaxpr.invars[i]) for i in donated
                             if i < len(jaxpr.invars))
    n_steps = len(steps)

    def var_bytes(v):
        return local_bytes(v.aval, vals.get(v), ctx)

    births: dict = {}
    deaths: dict = {}
    producer: dict = {}
    for i, var in enumerate(jaxpr.invars):
        cv = canon(var)
        births[cv] = 0
        if cv in donated_vars and cv not in out_vars:
            deaths[cv] = last_use.get(cv, 0) + 1
        else:
            deaths[cv] = n_steps + 1
    for var in jaxpr.constvars:
        cv = canon(var)
        births[cv] = 0
        deaths[cv] = n_steps + 1
    for idx, (eqn, _reads) in enumerate(steps):
        for var in eqn.outvars:
            cv = canon(var)
            if cv in births:
                continue
            births[cv] = idx
            producer[cv] = (idx, eqn)
            if cv in out_vars:
                deaths[cv] = n_steps + 1
            else:
                deaths[cv] = last_use.get(cv, idx) + 1

    events: dict = {}
    for cv, b in births.items():
        nb = var_bytes(cv)
        events[b] = events.get(b, 0) + nb
        events[deaths[cv]] = events.get(deaths[cv], 0) - nb
    peak, cur, peak_step = 0, 0, 0
    for step in sorted(events):
        cur += events[step]
        if cur > peak:
            peak, peak_step = cur, step

    input_bytes = sum(var_bytes(canon(v)) for v in jaxpr.invars)
    output_bytes = sum(var_bytes(canon(v)) for v in jaxpr.outvars
                       if _is_var(v))
    # partial sums still pending at an output: GSPMD resolves them to
    # the (replicated) out sharding with an allreduce at the boundary
    for v in jaxpr.outvars:
        if not _is_var(v):
            continue
        val = vals.get(canon(v))
        if val is not None and val.pending:
            comms += collective_bytes(
                "psum", var_bytes(canon(v)),
                [ctx.size(a) for a in val.pending])
    return Liveness(
        ctx=ctx, env=env, steps=steps, vals=vals, births=births,
        deaths=deaths, first_use=first_use, last_use=last_use,
        producer=producer, out_vars=out_vars,
        donated_vars=donated_vars,
        invar_canon=tuple(canon(v) for v in jaxpr.invars),
        n_steps=n_steps, peak_hbm_bytes=int(peak),
        peak_step=int(peak_step), comms_bytes=int(comms),
        input_bytes=int(input_bytes), output_bytes=int(output_bytes))


def prior_ratio_of(priors):
    """Normalize a prior to a positive finite float ratio. Accepts a
    bare number or a priors-file row (``{"ratio": ...}``); loud on
    anything else — a drifted priors file must never silently price
    the planner's pruning."""
    ratio = priors.get("ratio") if isinstance(priors, dict) else priors
    try:
        ratio = float(ratio)
    except (TypeError, ValueError):
        raise ValueError(
            f"HBM prior must be a number or a {{'ratio': ...}} row, "
            f"got {priors!r}")
    if not math.isfinite(ratio) or ratio <= 0:
        raise ValueError(
            f"HBM prior ratio must be positive and finite, got "
            f"{ratio!r} (from {priors!r})")
    return ratio


def estimate_hbm_and_comms(closed, in_vals, donated=frozenset(),
                           axis_sizes=None, priors=None):
    """Liveness walk over the linearized program (a thin view over
    :func:`compute_liveness` — the memory-liveness engine shares the
    same record).

    ``donated``: flat invar indices whose buffers die at their last
    read (jit donation); everything else is caller-owned for the whole
    step. ``priors``: an optional measured/modeled calibration ratio
    (a number, or an ``analysis/hbm_priors.json`` row) — when given,
    the result additionally carries ``prior_ratio`` and
    ``calibrated_peak_hbm_bytes`` (modeled peak x prior), the bytes
    calibrated consumers (planner pruning, hbm-budget) should price
    on. Returns ``{"peak_hbm_bytes", "input_bytes", "output_bytes",
    "comms_bytes", "peak_step"}`` — all per-device estimates under the
    propagated shardings.
    """
    live = compute_liveness(closed, in_vals, donated=donated,
                            axis_sizes=axis_sizes)
    out = {
        "peak_hbm_bytes": live.peak_hbm_bytes,
        "input_bytes": live.input_bytes,
        "output_bytes": live.output_bytes,
        "comms_bytes": live.comms_bytes,
        "peak_step": live.peak_step,
    }
    if priors is not None:
        ratio = prior_ratio_of(priors)
        out["prior_ratio"] = ratio
        out["calibrated_peak_hbm_bytes"] = int(
            round(live.peak_hbm_bytes * ratio))
    return out


def _jaxpr_comms(jaxpr, ctx: MeshCtx, mult: int) -> int:
    """Per-device comms bytes of the collectives in ``jaxpr``, each
    weighted by ``mult`` executions."""
    total = 0
    for eqn in jaxpr.eqns:
        param = COLLECTIVE_PRIMS.get(eqn.primitive.name)
        if param is not None:
            axes = _axis_names_of(eqn.params.get(param))
            nbytes = sum(_aval_bytes(v.aval)
                         for v in eqn.invars if _is_var(v))
            total += mult * collective_bytes(
                eqn.primitive.name, nbytes,
                [ctx.size(a) for a in axes])
        else:
            total += _control_flow_comms(eqn, ctx, mult)
    return total


def _control_flow_comms(eqn, ctx: MeshCtx, mult: int = 1) -> int:
    """Comms bytes from collectives nested anywhere inside ``eqn``.
    Scan bodies are weighted by their trip count, cond counts its most
    expensive branch (not the sum), while-loop bodies count one
    iteration (an unknowable trip count — a documented floor)."""
    prim = eqn.primitive.name
    params = eqn.params

    if prim == "shard_map":
        shape = getattr(params.get("mesh"), "shape", None)
        sizes = {str(k): int(v) for k, v in dict(shape).items()} \
            if shape else {}
        inner = ctx.child(sizes, sizes.keys())
        return sum(_jaxpr_comms(_jaxpr_of(s), inner, mult)
                   for s in _closed_jaxprs_in(params.get("jaxpr", ())))

    if prim == "scan":
        length = params.get("length") or 1
        return sum(
            _jaxpr_comms(_jaxpr_of(s), ctx, mult * int(length))
            for s in _closed_jaxprs_in(params.get("jaxpr", ())))

    if prim == "cond":
        branches = _closed_jaxprs_in(params.get("branches", ()))
        return max((_jaxpr_comms(_jaxpr_of(b), ctx, mult)
                    for b in branches), default=0)

    total = 0
    for value in params.values():
        for sub in _closed_jaxprs_in(value):
            total += _jaxpr_comms(_jaxpr_of(sub), ctx, mult)
    return total
