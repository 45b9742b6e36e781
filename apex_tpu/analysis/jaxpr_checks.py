"""Engine 1: jaxpr-level TPU lint.

Traces a function with abstract avals (``jax.make_jaxpr`` — no backend,
no compile; runs fine under ``JAX_PLATFORMS=cpu``) and walks the closed
jaxpr for the bug classes round 5's VERDICT showed slip past review:

- ``donation``        donated input aliased into an output by a
                      ``pallas_call`` and read again afterwards (in-place
                      clobber / defeated donation), or donated with no
                      aval-matching output (wasted donation).
- ``recompile``       retrace-per-step hazards: weak-typed Python-scalar
                      arguments and large closed-over concrete arrays
                      baked into the trace.
- ``collective-axis`` ``psum``/``ppermute``/``all_gather``/... axis names
                      checked against the live mesh axes (default: the
                      ``transformer.parallel_state`` mesh), plus
                      ``ppermute`` permutation validation — the
                      mismatches that deadlock multichip runs.
- ``pallas-block``    every ``pl.pallas_call`` BlockSpec checked for
                      (sublane, 128) tiling alignment by dtype and a
                      double-buffered VMEM residency estimate against
                      ``ops.pallas_config.device_vmem_bytes()``.

Entry point: :func:`analyze_fn`.
"""

from __future__ import annotations

import math

import numpy as np

from apex_tpu.analysis.findings import Finding

JAXPR_CHECKS = ("donation", "recompile", "collective-axis", "pallas-block")

# Call-like primitives inlined for the donation liveness walk: their
# bodies execute in the caller's buffer world, so reads inside them are
# reads of the caller's (possibly donated) buffers.
_INLINE_PRIMS = {"jit", "closed_call", "core_call", "custom_jvp_call",
                 "custom_vjp_call", "custom_vjp_call_jaxpr", "remat",
                 "checkpoint"}

# Collective primitives and the param carrying their axis name(s).
_COLLECTIVE_AXIS_PARAMS = {
    "psum": "axes", "psum_invariant": "axes", "pmin": "axes",
    "pmax": "axes",
    "ppermute": "axis_name", "pbroadcast": "axes",
    "all_gather": "axis_name", "all_gather_invariant": "axis_name",
    "all_to_all": "axis_name", "reduce_scatter": "axis_name",
    "psum_scatter": "axis_name", "axis_index": "axis_name",
}

# Sublane multiple (second-minor tile dim) by dtype itemsize; the lane
# (minor) dim is always 128 (pallas_guide.md tiling table).
_SUBLANE_BY_ITEMSIZE = {8: 8, 4: 8, 2: 16, 1: 32}
_LANE = 128


def _closed_jaxprs_in(value):
    """Jaxpr-like objects inside an eqn param value."""
    import jax.extend.core as core
    out = []
    if isinstance(value, core.ClosedJaxpr):
        out.append(value.jaxpr)
    elif isinstance(value, core.Jaxpr):
        out.append(value)
    elif isinstance(value, (tuple, list)):
        for v in value:
            out.extend(_closed_jaxprs_in(v))
    return out


def _canon(env, v):
    while v in env:
        v = env[v]
    return v


def _is_var(v):
    import jax.extend.core as core
    return isinstance(v, core.Var)


def _linearize(jaxpr, env, steps):
    """Flatten call-like primitives into one eqn sequence, mapping inner
    vars onto their caller operands so a read inside a pjit body counts
    as a read of the caller's (donated) buffer."""
    for eqn in jaxpr.eqns:
        sub = None
        if eqn.primitive.name in _INLINE_PRIMS:
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                if key in eqn.params:
                    subs = _closed_jaxprs_in(eqn.params[key])
                    if subs:
                        sub = subs[0]
                        break
        if sub is not None and len(sub.invars) == len(eqn.invars):
            for iv, ov in zip(sub.invars, eqn.invars):
                if _is_var(ov):
                    env[iv] = _canon(env, ov)
            _linearize(sub, env, steps)
            for inner_ov, outer_ov in zip(sub.outvars, eqn.outvars):
                if _is_var(inner_ov):
                    env[outer_ov] = _canon(env, inner_ov)
            continue
        # keep Literal slots as None so positional lookups (pallas_call
        # input_output_aliases operand indices) stay aligned
        reads = [_canon(env, v) if _is_var(v) else None
                 for v in eqn.invars]
        steps.append((eqn, reads))


def _walk_all(jaxpr, axis_sizes, out):
    """Yield (eqn, axis_sizes-at-that-depth) for every eqn at any depth,
    tracking axis sizes bound by enclosing shard_map meshes."""
    for eqn in jaxpr.eqns:
        out.append((eqn, axis_sizes))
        inner_sizes = axis_sizes
        if eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            shape = getattr(mesh, "shape", None)
            if shape:
                inner_sizes = dict(axis_sizes)
                inner_sizes.update({str(k): int(v)
                                    for k, v in dict(shape).items()})
        for value in eqn.params.values():
            for sub in _closed_jaxprs_in(value):
                _walk_all(sub, inner_sizes, out)


# ----------------------------------------------------------- the checks

def _donated_invar_indices(example_args, donate_argnums):
    """Map top-level donate_argnums onto flat invar index ranges."""
    import jax
    donate = {donate_argnums} if isinstance(donate_argnums, int) \
        else set(donate_argnums)
    idx, out = 0, {}
    for argnum, arg in enumerate(example_args):
        n = len(jax.tree_util.tree_leaves(arg))
        if argnum in donate:
            for k in range(n):
                out[idx + k] = (argnum, k)
        idx += n
    return out


def check_donation(closed, donated, name, path):
    """donated: {flat invar index: (argnum, leaf)} from the caller."""
    findings = []
    jaxpr = closed.jaxpr
    env: dict = {}
    steps: list = []
    _linearize(jaxpr, env, steps)
    out_avals = [(tuple(v.aval.shape), str(v.aval.dtype))
                 for v in jaxpr.outvars if _is_var(v)]
    outvars = {_canon(env, v) for v in jaxpr.outvars if _is_var(v)}

    for flat_idx, (argnum, leaf) in sorted(donated.items()):
        if flat_idx >= len(jaxpr.invars):
            continue
        var = jaxpr.invars[flat_idx]
        sig = (tuple(var.aval.shape), str(var.aval.dtype))
        where = f"arg {argnum} leaf {leaf} {sig[1]}{list(sig[0])}"

        if var not in outvars and sig not in out_avals:
            findings.append(Finding(
                "donation", "warning", path, 0, name,
                f"donated {where} matches no output shape/dtype: XLA "
                f"cannot reuse the buffer, so donation is wasted and the "
                f"caller still loses the array"))
            continue

        alias_step = None
        for i, (eqn, reads) in enumerate(steps):
            if eqn.primitive.name != "pallas_call" or var not in reads:
                continue
            gm = eqn.params.get("grid_mapping")
            n_index = getattr(gm, "num_index_operands", 0)
            for in_idx, _out_idx in eqn.params.get(
                    "input_output_aliases", ()):
                pos = n_index + in_idx
                if pos < len(reads) and reads[pos] is var:
                    alias_step = i
                    break
            if alias_step is not None:
                break
        if alias_step is None:
            continue
        kernel = str(eqn.params.get("name_and_src_info", "pallas kernel"))
        read_after = None
        for j in range(alias_step + 1, len(steps)):
            later_eqn, later_reads = steps[j]
            if var in later_reads:
                read_after = f"'{later_eqn.primitive.name}'"
                break
        if read_after is None and var in outvars:
            # the pre-alias value is returned directly: same clobber,
            # just read by the caller instead of a later eqn
            read_after = "the caller (it is returned as an output)"
        if read_after is not None:
            findings.append(Finding(
                "donation", "error", path, 0, name,
                f"donated {where} is aliased into an output by "
                f"pallas_call [{kernel}] and read again by "
                f"{read_after} afterwards — the kernel's in-place "
                f"write clobbers the later read (or forces a "
                f"defensive copy that defeats donation)"))
    return findings


_CONST_CAPTURE_MIN_ELEMS = 256


def check_recompile(closed, name, path, example_args=()):
    findings = []
    jaxpr = closed.jaxpr

    import jax
    arg_of_invar = {}
    idx = 0
    for argnum, arg in enumerate(example_args):
        for _ in jax.tree_util.tree_leaves(arg):
            arg_of_invar[idx] = argnum
            idx += 1

    for i, var in enumerate(jaxpr.invars):
        aval = var.aval
        if getattr(aval, "weak_type", False) and \
                getattr(aval, "ndim", None) == 0:
            argnum = arg_of_invar.get(i, i)
            findings.append(Finding(
                "recompile", "warning", path, 0, name,
                f"argument {argnum} is a weak-typed Python scalar "
                f"({aval.dtype}): weak promotion can flip downstream "
                f"dtypes between call sites, and a scalar hyperparameter "
                f"fed this way is one refactor away from a per-value "
                f"retrace — pass jnp.asarray(x, dtype) instead"))

    for const in closed.consts:
        size = int(np.size(const))
        if size >= _CONST_CAPTURE_MIN_ELEMS:
            shape = tuple(np.shape(const))
            dtype = getattr(const, "dtype", type(const).__name__)
            findings.append(Finding(
                "recompile", "warning", path, 0, name,
                f"trace closes over a concrete {dtype}{list(shape)} "
                f"array ({size} elements) baked in as a constant: every "
                f"retrace re-stages it, it bloats the executable, and it "
                f"can neither be donated nor resharded — thread it "
                f"through as an argument"))
    return findings


def _axis_names(value):
    if value is None:
        return []
    if isinstance(value, (tuple, list, frozenset, set)):
        out = []
        for v in value:
            out.extend(_axis_names(v))
        return out
    return [str(value)]


def check_collectives(closed, name, path, mesh_axes=None):
    """``mesh_axes``: the axis universe collectives must live in — a
    dict name->size, an iterable of names, or a Mesh. Default: the live
    ``parallel_state`` mesh when one is initialized, else the axes bound
    by enclosing shard_maps in the trace itself."""
    declared_sizes = {}
    declared = None
    if mesh_axes is None:
        try:
            from apex_tpu.transformer import parallel_state
            if parallel_state.model_parallel_is_initialized():
                mesh_axes = parallel_state.get_mesh()
        except Exception:
            mesh_axes = None
    if mesh_axes is not None:
        shape = getattr(mesh_axes, "shape", None)
        if isinstance(mesh_axes, dict):
            declared_sizes = {str(k): int(v) for k, v in mesh_axes.items()}
            declared = set(declared_sizes)
        elif shape:
            declared_sizes = {str(k): int(v) for k, v in dict(shape).items()}
            declared = set(declared_sizes)
        else:
            declared = {str(a) for a in mesh_axes}

    findings = []
    eqns: list = []
    _walk_all(closed.jaxpr, {}, eqns)
    for eqn, bound_sizes in eqns:
        prim = eqn.primitive.name
        param = _COLLECTIVE_AXIS_PARAMS.get(prim)
        if param is None:
            continue
        axes = _axis_names(eqn.params.get(param))
        valid = declared if declared is not None else set(bound_sizes)
        for ax in axes:
            if valid and ax not in valid:
                findings.append(Finding(
                    "collective-axis", "error", path, 0, name,
                    f"'{prim}' rides axis '{ax}' which is not in the "
                    f"live mesh axes {sorted(valid)} — on a multichip "
                    f"run this deadlocks (some chips enter the "
                    f"collective, the rest never will)"))
        if prim == "ppermute":
            perm = eqn.params.get("perm") or ()
            ax = axes[0] if axes else None
            size = bound_sizes.get(ax) or declared_sizes.get(ax)
            srcs = [s for s, _ in perm]
            dsts = [d for _, d in perm]
            if size is not None:
                bad = [p for p in perm
                       if not (0 <= p[0] < size and 0 <= p[1] < size)]
                if bad:
                    findings.append(Finding(
                        "collective-axis", "error", path, 0, name,
                        f"ppermute over axis '{ax}' (size {size}) names "
                        f"out-of-range ranks {bad[:4]} — the transfer "
                        f"never completes"))
            if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
                findings.append(Finding(
                    "collective-axis", "error", path, 0, name,
                    f"ppermute permutation over axis '{ax}' repeats a "
                    f"source or destination rank: {list(perm)[:6]} — "
                    f"ppermute requires a partial permutation (each rank "
                    f"sends/receives at most once)"))
    return findings


def check_pallas_blocks(closed, name, path, vmem_bytes=None):
    from apex_tpu.ops import pallas_config

    if vmem_bytes is None:
        vmem_bytes = pallas_config.device_vmem_bytes()
    findings = []
    eqns: list = []
    _walk_all(closed.jaxpr, {}, eqns)
    for eqn, _ in eqns:
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params.get("grid_mapping")
        if gm is None:
            continue
        kernel = str(eqn.params.get("name_and_src_info", "pallas kernel"))
        resident = 0
        for bm in gm.block_mappings:
            sd = bm.array_aval
            dtype = np.dtype(sd.dtype)
            # block dims are Blocked(block_size=n) entries; squeezed
            # dims carry no size and take no room in the block
            idims = [d.block_size for d in bm.block_shape
                     if hasattr(d, "block_size")]
            resident += math.prod(idims or [1]) * dtype.itemsize
            if len(idims) < 2:
                continue  # scalar/1D blocks: no (sublane, lane) tiling
            minor, second = idims[-1], idims[-2]
            a_shape = tuple(sd.shape)
            a_minor = a_shape[-1] if a_shape else minor
            a_second = a_shape[-2] if len(a_shape) >= 2 else second
            sublane = _SUBLANE_BY_ITEMSIZE.get(dtype.itemsize, 8)
            if minor % _LANE and minor != a_minor:
                findings.append(Finding(
                    "pallas-block", "warning", path, 0, name,
                    f"[{kernel}] {bm.origin}: block minor dim {minor} is "
                    f"neither a multiple of the {_LANE}-lane width nor "
                    f"the full array dim ({a_minor}) — Mosaic pads every "
                    f"block, wasting VMEM and bandwidth"))
            if second % sublane and second != a_second:
                findings.append(Finding(
                    "pallas-block", "warning", path, 0, name,
                    f"[{kernel}] {bm.origin}: block sublane dim {second} "
                    f"is neither a multiple of {sublane} (dtype "
                    f"{dtype.name}) nor the full array dim ({a_second}) "
                    f"— Mosaic pads every block"))
        est = 2 * resident  # double-buffered pipeline
        if est > vmem_bytes:
            findings.append(Finding(
                "pallas-block", "error", path, 0, name,
                f"[{kernel}] estimated VMEM residency "
                f"{est / 2**20:.1f} MiB (double-buffered block set) "
                f"exceeds the ~{vmem_bytes / 2**20:.0f} MiB per-core "
                f"budget — the kernel will fail to compile or thrash "
                f"HBM; shrink the BlockSpecs"))
    return findings


# -------------------------------------------------------------- entry

def analyze_fn(fn, *example_args, donate_argnums=(), mesh_axes=None,
               name=None, checks=None, vmem_bytes=None):
    """Trace ``fn`` with ``example_args`` and run the jaxpr checks.

    ``donate_argnums`` mirrors ``jax.jit``'s (top-level positional args).
    ``checks`` restricts to a subset of :data:`JAXPR_CHECKS`. Returns a
    list of :class:`Finding`.
    """
    import jax

    name = name or getattr(fn, "__name__", "fn")
    path = f"<jaxpr:{name}>"
    run = set(checks or JAXPR_CHECKS)
    unknown = run - set(JAXPR_CHECKS)
    if unknown:
        raise ValueError(f"unknown jaxpr check(s) {sorted(unknown)}; "
                         f"valid: {list(JAXPR_CHECKS)}")

    closed = jax.make_jaxpr(fn)(*example_args)

    findings = []
    if "donation" in run:
        donated = _donated_invar_indices(example_args, donate_argnums)
        if donated:
            findings += check_donation(closed, donated, name, path)
    if "recompile" in run:
        findings += check_recompile(closed, name, path, example_args)
    if "collective-axis" in run:
        findings += check_collectives(closed, name, path, mesh_axes)
    if "pallas-block" in run:
        findings += check_pallas_blocks(closed, name, path, vmem_bytes)
    return findings
