"""Loss scaling — TPU re-design of ``apex.amp.scaler.LossScaler``.

Ref: apex/amp/scaler.py (+ apex/fp16_utils/loss_scaler.py).

The CUDA scaler syncs an overflow flag to the host every step
(``overflow = scale_check.item()``) and skips ``optimizer.step()`` in Python.
Here the whole protocol is in-graph: the overflow check is a fused
``isfinite`` reduction, the skip is a ``lax.cond``/``where``, and the
dynamic-scale automaton (halve on overflow, double every ``scale_window``
clean steps) updates as traced arithmetic — zero host syncs per step.

bf16 training on TPU usually needs no loss scaling (bf16 has fp32's
exponent range); the scaler exists for fp16 parity and for gradient-range
safety nets. ``LossScaler(enabled=False)`` compiles to nothing.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from apex_tpu.observability import scope
from apex_tpu.ops.vma import to_varying


class LossScaleState(NamedTuple):
    """Functional scaler state (carried through the jitted train step).

    ``steps``/``last_overflow_step``/``skip_streak`` are the ISSUE 9
    readout fields: the health detectors need *when* the last overflow
    hit and *how many in a row*, not just the cumulative count — a
    scaler stuck skipping every step looks identical to a healthy one
    through ``overflows`` alone until the loss curve dies.
    """

    loss_scale: jax.Array      # f32 scalar
    unskipped: jax.Array       # i32: clean steps since last rescale (ref scaler.py:_unskipped)
    overflows: jax.Array       # i32: total overflow count (diagnostics)
    steps: jax.Array           # i32: total update() calls
    last_overflow_step: jax.Array  # i32: step index of newest overflow (-1 = never)
    skip_streak: jax.Array     # i32: consecutive overflow-skipped steps


class LossScaler:
    """Static + dynamic loss scaling with in-graph overflow skip.

    ``dynamic=True`` mirrors apex's default dynamic scaler
    (init 2**16, x2 growth every 2000 unskipped steps, /2 on overflow).
    """

    def __init__(self, loss_scale="dynamic", init_scale=2.0 ** 16,
                 scale_factor=2.0, scale_window=2000,
                 min_loss_scale=None, max_loss_scale=2.0 ** 24, enabled=True,
                 backoff_factor=None):
        self.dynamic = loss_scale == "dynamic"
        self._static_scale = 1.0 if self.dynamic else float(loss_scale)
        self.init_scale = init_scale if self.dynamic else self._static_scale
        self.scale_factor = scale_factor
        # apex default: backoff is symmetric (1/growth); torch-GradScaler
        # style asymmetric backoff is supported via an explicit factor
        self.backoff_factor = (1.0 / scale_factor if backoff_factor is None
                               else backoff_factor)
        self.scale_window = scale_window
        self.min_loss_scale = min_loss_scale
        self.max_loss_scale = max_loss_scale
        self.enabled = enabled

    def init(self) -> LossScaleState:
        return LossScaleState(
            loss_scale=jnp.asarray(self.init_scale if self.enabled else 1.0, jnp.float32),
            unskipped=jnp.zeros([], jnp.int32),
            overflows=jnp.zeros([], jnp.int32),
            steps=jnp.zeros([], jnp.int32),
            last_overflow_step=jnp.full([], -1, jnp.int32),
            skip_streak=jnp.zeros([], jnp.int32),
        )

    # ---- in-graph protocol -------------------------------------------------

    def scale_loss(self, loss, state: LossScaleState):
        """Ref apex/amp/handle.py:scale_loss — multiply before backward."""
        if not self.enabled:
            return loss
        return loss * state.loss_scale.astype(loss.dtype)

    def unscale(self, grads, state: LossScaleState):
        """Unscale grads and detect inf/nan in one fused pass.

        Returns ``(unscaled_grads, overflow)``; overflow is a traced bool
        (ref apex/amp/scaler.py:unscale + axpby_check_overflow).
        """
        if not self.enabled:
            return grads, jnp.zeros([], jnp.bool_)
        inv = 1.0 / state.loss_scale
        unscaled = jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype), grads)
        leaves = jax.tree_util.tree_leaves(unscaled)
        finite = jnp.array(True)
        for l in leaves:
            finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(l)))
        return unscaled, jnp.logical_not(finite)

    def update(self, state: LossScaleState, overflow) -> LossScaleState:
        """Dynamic-scale automaton (ref apex/amp/scaler.py:update_scale).

        The diagnostics fields (overflow count/step/streak) advance for
        ANY enabled scaler — a static scale still skips steps on
        overflow via ``scaled_update``'s cond, and those skips must be
        observable; only the scale value itself is dynamic-gated.
        """
        if not self.enabled:
            return state
        overflow = jnp.asarray(overflow)
        ovf_i = overflow.astype(jnp.int32)
        # this update closes step index `state.steps` (0-based)
        diag = dict(
            overflows=state.overflows + ovf_i,
            steps=state.steps + 1,
            last_overflow_step=jnp.where(
                overflow, state.steps,
                state.last_overflow_step).astype(jnp.int32),
            skip_streak=jnp.where(overflow, state.skip_streak + 1,
                                  0).astype(jnp.int32),
        )
        if not self.dynamic:
            return state._replace(**diag)
        halved = state.loss_scale * self.backoff_factor
        if self.min_loss_scale is not None:  # ref default: no floor
            halved = jnp.maximum(halved, self.min_loss_scale)
        new_scale = jnp.where(
            overflow,
            halved,
            jnp.where(
                state.unskipped + 1 >= self.scale_window,
                jnp.minimum(state.loss_scale * self.scale_factor, self.max_loss_scale),
                state.loss_scale,
            ),
        )
        new_unskipped = jnp.where(
            overflow | (state.unskipped + 1 >= self.scale_window),
            0, state.unskipped + 1).astype(jnp.int32)
        return state._replace(
            loss_scale=new_scale,
            unskipped=new_unskipped,
            **diag,
        )

    def loss_scale(self, state: LossScaleState):
        return state.loss_scale

    # ---- host-side diagnostics (ISSUE 2 satellite) ------------------------

    def overflow_count(self, state: LossScaleState) -> int:
        """Cumulative overflow/skip count as a host int.

        The in-graph automaton tracks ``state.overflows`` as a traced
        i32 (zero host syncs per step); this is the sanctioned read-out
        for logging cadence — one device fetch per CALL, so poll it at
        report intervals, not per step. Until now the count was only
        provable via multichip dryrun logs; this makes it first-class.
        """
        return int(jax.device_get(state.overflows))

    def report(self, state: LossScaleState, registry=None,
               prefix: str = "amp", grads=None, top_k: int = 3) -> dict:
        """Publish scaler health to a metrics registry (default: the
        process registry): gauges ``<prefix>/loss_scale``,
        ``<prefix>/overflow_count``, ``<prefix>/unskipped_steps``,
        plus (ISSUE 9) ``<prefix>/last_overflow_step`` and
        ``<prefix>/skip_streak`` — the fields the numerics
        ``HealthMonitor``'s overflow-streak detector consumes.
        Returns the values as a dict. One host sync per call.

        ``grads``: pass the (scaled) grads pytree when the last update
        overflowed and the readout should say WHICH tensors blew up —
        one fused stats pass names the top-``top_k`` tensors by amax
        (+ any outright non-finite paths) in an ``amp_overflow`` event
        and a ``top_offenders`` key. Skipped on clean steps, so the
        stats pass costs nothing in the steady state.
        """
        from apex_tpu.observability import get_registry

        host = jax.device_get(state)
        values = {
            "loss_scale": float(host.loss_scale),
            "overflow_count": int(host.overflows),
            "unskipped_steps": int(host.unskipped),
            "last_overflow_step": int(host.last_overflow_step),
            "skip_streak": int(host.skip_streak),
        }
        reg = registry if registry is not None else get_registry()
        for name, v in values.items():
            reg.gauge(f"{prefix}/{name}").set(v)
        if grads is not None and values["skip_streak"] > 0:
            from apex_tpu.observability import numerics

            per_tensor = numerics.host_tensor_stats(grads)
            summary = numerics.summarize_stats(per_tensor, top_k=top_k)
            values["top_offenders"] = summary["worst_amax"]
            reg.event("amp_overflow", prefix=prefix,
                      step=values["last_overflow_step"],
                      skip_streak=values["skip_streak"],
                      loss_scale=values["loss_scale"],
                      top_offenders=summary["worst_amax"],
                      nonfinite_paths=summary["nonfinite_paths"])
        return values

    # ---- checkpointing (ref apex/amp/frontend.py:state_dict) --------------

    def state_dict(self, state: LossScaleState) -> dict:
        host = jax.device_get(state)
        return {
            "loss_scale": host.loss_scale.item(),
            "unskipped": host.unskipped.item(),
            "overflows": host.overflows.item(),
            "steps": host.steps.item(),
            "last_overflow_step": host.last_overflow_step.item(),
            "skip_streak": host.skip_streak.item(),
        }

    def load_state_dict(self, d: dict) -> LossScaleState:
        # Compat contract (ISSUE 13 satellite, explicit tests in
        # tests/run_amp/test_fp8.py): every field except loss_scale
        # defaults, so legacy (pre-ISSUE-9 / pre-fp8) dicts load with
        # the "never overflowed yet" readout — and unknown EXTRA keys
        # (e.g. the O4 handle's "fp8" block read by an older build) are
        # simply ignored, never fatal.
        return LossScaleState(
            loss_scale=jnp.asarray(d["loss_scale"], jnp.float32),
            unskipped=jnp.asarray(d.get("unskipped", 0), jnp.int32),
            overflows=jnp.asarray(d.get("overflows", 0), jnp.int32),
            steps=jnp.asarray(d.get("steps", 0), jnp.int32),
            last_overflow_step=jnp.asarray(
                d.get("last_overflow_step", -1), jnp.int32),
            skip_streak=jnp.asarray(d.get("skip_streak", 0), jnp.int32),
        )


# --------------------------------------------------------------- fp8 (O4)
# Delayed-scaling automaton on top of the ISSUE 9 AmaxHistory rings
# (observability/numerics/history.py): each registered matmul site owns
# three ring rows — its two forward operands (E4M3) and its grad
# cotangent (E5M2). Scales are computed from the ring max (previous
# steps' amaxes — one step of staleness buys an on-device scale), the
# per-step update is a single column write per ring, and the whole
# state is a plain pytree that rides checkpoint.py's atomic manifest
# bit-identically (proved under the PR 5 chaos harness in
# tests/run_resilience/test_fp8_roundtrip.py).
#
# The *mechanism* is trace-time: a step enters `scaler.step(state)` and
# every `ops.precision.matmul_amp` call site inside the context turns
# into a scaled fp8 matmul, recording its amax observations into the
# context (plain Python at trace time, so the whole protocol jits).
# Sites are identified by (name, trace-order ordinal) — deterministic
# for a fixed step function; sites the scaler was not built with fall
# back to the fp32-accum path (which is what keeps decoder matmuls
# inside lax.scan/vmap safe: a collected tracer may never escape a
# transform, so only top-level sites are ever registered).


class Fp8ScalingState(NamedTuple):
    """Functional delayed-scaling state — carry it through the jitted
    train step and checkpoint it with the rest of the train state."""

    fwd: Any     # AmaxHistoryState over <site>/a, <site>/b rows (E4M3)
    grad: Any    # AmaxHistoryState over <site>/g rows (E5M2)
    steps: Any   # i32: update() calls applied


_FP8_STACK: list = []


def current_fp8():
    """The innermost active fp8 context (``Fp8DelayedScaler.step`` /
    ``record_fp8_sites``), or None when the fp8 tier is off — the hook
    ``ops.precision.matmul_amp`` consults at every routed call site."""
    return _FP8_STACK[-1] if _FP8_STACK else None


class _Fp8ContextBase:
    def __enter__(self):
        _FP8_STACK.append(self)
        return self

    def __exit__(self, *exc):
        if _FP8_STACK and _FP8_STACK[-1] is self:
            _FP8_STACK.pop()
        return False

    def _site(self, name: str) -> str:
        k = self._counts.get(name, 0)
        self._counts[name] = k + 1
        return f"{name}#{k}"


def _fp32acc_fallback(a, b, out_dtype):
    """Non-fp8 path for context matmuls: the accumulator stays fp32 all
    the way to ``out_dtype`` — a ``keep_acc`` caller asking for the
    fp32 result must NOT see the product round-trip through the
    storage dtype first (that would push the epilogue's backward
    reductions into bf16, exactly what matmul_fp32acc's keep_acc
    exists to avoid)."""
    from apex_tpu.ops.precision import matmul_fp32acc

    y = matmul_fp32acc(a, b, keep_acc=True)
    return y.astype(jnp.result_type(a, b) if out_dtype is None
                    else out_dtype)


class Fp8SiteRecorder(_Fp8ContextBase):
    """Discovery context: records every fp8-eligible call site's name in
    trace order (``with Fp8SiteRecorder() as rec: jax.eval_shape(fn,
    ...)``) while computing through the fp32-accum path. Feed
    ``rec.sites`` to :class:`Fp8DelayedScaler`."""

    def __init__(self):
        self.sites = []
        self._counts = {}

    def matmul(self, a, b, name="matmul", out_dtype=None):
        self._site(name)
        self.sites.append(name)
        return _fp32acc_fallback(a, b, out_dtype)


class _Fp8Apply(_Fp8ContextBase):
    """The live O4 context one traced step enters: resolves each site's
    delayed scales from the carried state, rewrites the matmul through
    ``ops.precision.matmul_fp8``, and collects this step's amax
    observations for :meth:`Fp8DelayedScaler.update`.

    Gradients MUST be computed through :meth:`value_and_grad` (not bare
    ``jax.value_and_grad``): the forward amaxes ride out of the grad
    transform as an aux output and the E5M2 cotangent amaxes come back
    as the gradients of per-site probe scalars — both plain functional
    outputs, so nothing collected inside the transform ever leaks a
    tracer."""

    def __init__(self, scaler: "Fp8DelayedScaler", state: Fp8ScalingState):
        self.scaler = scaler
        self.state = state
        self._counts = {}
        self._fwd_scales, self._grad_scales = scaler.scales(state)
        self._fwd_amax = {}     # row index -> traced scalar (stash)
        self._probes = None     # f32[ng] inside value_and_grad's aug
        self._harvest = None    # (fwd f32[nf], grad f32[ng]) once done
        self.skipped_sites = []  # names that fell back (unregistered)

    def matmul(self, a, b, name="matmul", out_dtype=None):
        from apex_tpu.ops import precision as _prec

        site = self._site(name)
        paths = self.scaler.fwd_history.paths
        if f"{site}/a" not in paths:
            # not registered with this scaler: fp32-accum fallback. This
            # is load-bearing, not best-effort — sites under scan/vmap
            # (llama decoder layers) must not leak collected tracers out
            # of their transform, so only registered top-level sites
            # convert.
            self.skipped_sites.append(site)
            return _fp32acc_fallback(a, b, out_dtype)
        ia = self.scaler.fwd_history.index(f"{site}/a")
        ib = self.scaler.fwd_history.index(f"{site}/b")
        ig = self.scaler.grad_history.index(f"{site}/g")
        # the amax observations come out of the SAME fused
        # cast-and-scale pass that quantizes — one HBM read per
        # operand, not a second standalone reduction
        y, amax_a, amax_b = _prec.matmul_fp8_stats(
            a, b, self._fwd_scales[ia], self._fwd_scales[ib],
            grad_scale=self._grad_scales[ig], out_dtype=out_dtype,
            grad_probe=(None if self._probes is None
                        else self._probes[ig]))
        self._fwd_amax[ia] = amax_a
        self._fwd_amax[ib] = amax_b
        return y

    def _stack_fwd(self):
        zero = jnp.zeros([], jnp.float32)
        return jnp.stack([
            self._fwd_amax.get(i, zero)
            for i in range(len(self.scaler.fwd_history.paths))])

    def value_and_grad(self, fn, argnums=0, has_aux=False):
        """fp8-aware ``jax.value_and_grad``: same signature/return
        shape, plus the amax bookkeeping described on the class. Call
        it INSIDE the context, on the loss whose matmuls route through
        this context's sites."""
        import jax as _jax

        scalar_argnums = isinstance(argnums, int)
        nums = (argnums,) if scalar_argnums else tuple(argnums)
        ng = len(self.scaler.grad_history.paths)

        def call(*args, **kwargs):
            def aug(probes, *a, **k):
                # fresh ordinals per differentiated trace: an eval
                # forward before this call (or a previous
                # value_and_grad in a grad-accumulation loop) must not
                # shift a registered site to `name#1` — that would
                # silently fall back to fp32acc and write a zero ring
                # column
                self._probes = probes
                self._counts = {}
                self._fwd_amax = {}
                try:
                    out = fn(*a, **k)
                finally:
                    self._probes = None
                loss, aux = out if has_aux else (out, None)
                fwd = self._stack_fwd()
                self._fwd_amax = {}  # drop inner-trace stash
                return loss, (aux, fwd)

            probes0 = jnp.zeros((ng,), jnp.float32)
            (loss, (aux, fwd)), grads = _jax.value_and_grad(
                aug, argnums=(0,) + tuple(n + 1 for n in nums),
                has_aux=True)(probes0, *args, **kwargs)
            # merge with any previous harvest (microbatch accumulation
            # calls value_and_grad repeatedly): the step's observation
            # is the max over every traversal, never the last one
            if self._harvest is None:
                self._harvest = (fwd, grads[0])
            else:
                self._harvest = (jnp.maximum(self._harvest[0], fwd),
                                 jnp.maximum(self._harvest[1],
                                             grads[0]))
            # restart site ordinals for whatever follows (another grad
            # call, an eval forward) — transpose-time recompute traces
            # have already run inside the value_and_grad call above
            self._counts = {}
            user = grads[1:]
            user = user[0] if scalar_argnums else user
            return ((loss, aux) if has_aux else loss), user

        return call

    def fwd_amax(self):
        """This step's stacked E4M3 amax observations (``f32[nf]``);
        unobserved rows write 0 (a 0 never votes in the ring max)."""
        if self._harvest is not None:
            return self._harvest[0]
        return self._stack_fwd()

    def grad_amax(self):
        """Stacked E5M2 cotangent amaxes (``f32[ng]``) — the probe
        gradients :meth:`value_and_grad` harvested; all 0 when no
        backward ran (forward-only steps observe nothing)."""
        if self._harvest is not None:
            return self._harvest[1]
        return jnp.zeros((len(self.scaler.grad_history.paths),),
                         jnp.float32)


class Fp8DelayedScaler:
    """Per-tensor delayed scaling for the O4 fp8 tier.

    ``sites``: ordered matmul-site names (duplicates allowed — they
    become ``name#0``, ``name#1``, ... in trace order), each owning two
    E4M3 forward rows and one E5M2 grad row in the amax rings. The
    object is static configuration; all mutable state is the
    :class:`Fp8ScalingState` pytree, so ``scales``/``update`` are
    jit-safe and the state checkpoints like any other leaf.

    Protocol (inside the traced step)::

        with fp8.step(fp8_state) as ctx:
            loss, grads = jax.value_and_grad(loss_fn)(params, ...)
        new_fp8_state = fp8.update(fp8_state, ctx,
                                   reduce_axes=("dp",))  # in shard_map
    """

    def __init__(self, sites, history: int = 16, margin: float = 0.0):
        from apex_tpu.observability.numerics.history import AmaxHistory

        counts: dict = {}
        canon = []
        for s in sites:
            k = counts.get(s, 0)
            counts[s] = k + 1
            canon.append(f"{s}#{k}")
        if not canon:
            raise ValueError("Fp8DelayedScaler needs at least one site")
        self.sites = tuple(canon)
        self.history = int(history)
        self.margin = float(margin)
        self.fwd_history = AmaxHistory(
            [f"{c}/{op}" for c in canon for op in ("a", "b")],
            length=history)
        self.grad_history = AmaxHistory(
            [f"{c}/g" for c in canon], length=history)

    @classmethod
    def for_step(cls, fn, *example_args, history: int = 16,
                 margin: float = 0.0) -> "Fp8DelayedScaler":
        """Build a scaler sized for ``fn``'s fp8 sites by abstractly
        tracing it under a discovery context (``jax.eval_shape`` — no
        FLOPs, no device buffers). ``fn`` should be the step whose
        matmuls route through ``ops.precision.matmul_amp`` — including
        its backward (pass the ``value_and_grad`` form) so recompute
        sites register too. Sites under ``lax.scan``/``vmap``/``remat``
        are recorded like any other but will be skipped at apply time;
        prefer explicit ``Fp8DelayedScaler([names...])`` when the step
        mixes transformed and top-level sites."""
        import jax

        with Fp8SiteRecorder() as rec:
            jax.eval_shape(fn, *example_args)
        return cls(rec.sites, history=history, margin=margin)

    # ---- jit-safe state protocol -------------------------------------

    def init(self) -> Fp8ScalingState:
        return Fp8ScalingState(
            fwd=self.fwd_history.init(),
            grad=self.grad_history.init(),
            steps=jnp.zeros([], jnp.int32),
        )

    def scales(self, state: Fp8ScalingState):
        """(fwd_scales f32[2*n_sites], grad_scales f32[n_sites]) —
        delayed per-tensor factors from the ring max: multiply a tensor
        by its scale before the fp8 cast so the history's max lands at
        the format edge / 2^margin. Fresh rows (no signal yet) scale
        by 1."""
        from apex_tpu.observability.numerics.history import (
            F8_E4M3_MAX,
            F8_E5M2_MAX,
        )

        return (self.fwd_history.scales(state.fwd, fp8_max=F8_E4M3_MAX,
                                        margin=self.margin),
                self.grad_history.scales(state.grad, fp8_max=F8_E5M2_MAX,
                                         margin=self.margin))

    def step(self, state: Fp8ScalingState) -> _Fp8Apply:
        """The per-step context manager (see class docstring)."""
        return _Fp8Apply(self, state)

    def update(self, state: Fp8ScalingState, ctx: _Fp8Apply,
               reduce_axes=()) -> Fp8ScalingState:
        """Write this step's collected amaxes into the rings (one
        column write per ring). Inside ``shard_map`` pass every mesh
        axis in ``reduce_axes``: observations are pmax-voted so ALL
        ranks write identical columns and the delayed scales stay
        replicated (the fp8 analog of ``scaled_update``'s psum'd
        overflow flag)."""
        fwd = ctx.fwd_amax()
        grad = ctx.grad_amax()
        if reduce_axes:
            axes = tuple(reduce_axes)
            fwd = jax.lax.pmax(to_varying(fwd, axes), axes)
            grad = jax.lax.pmax(to_varying(grad, axes), axes)
        return Fp8ScalingState(
            fwd=self.fwd_history.update(state.fwd, fwd),
            grad=self.grad_history.update(state.grad, grad),
            steps=state.steps + 1,
        )

    # ---- host-side serialization -------------------------------------

    def state_dict(self, state: Fp8ScalingState) -> dict:
        return {
            "sites": list(self.sites),
            "history": self.history,
            "margin": self.margin,
            "fwd": self.fwd_history.state_dict(state.fwd),
            "grad": self.grad_history.state_dict(state.grad),
            "steps": int(jax.device_get(state.steps)),
        }

    def load_state_dict(self, d: dict) -> Fp8ScalingState:
        if tuple(d.get("sites", ())) != self.sites:
            raise ValueError(
                "fp8 scaling state was recorded for a different site "
                f"set ({list(d.get('sites', ()))} vs {list(self.sites)});"
                " refusing to misalign the amax rings")
        return Fp8ScalingState(
            fwd=self.fwd_history.load_state_dict(d["fwd"]),
            grad=self.grad_history.load_state_dict(d["grad"]),
            # .get default: dicts written before the steps counter load
            # as "no updates seen yet"
            steps=jnp.asarray(d.get("steps", 0), jnp.int32),
        )


def scaled_update(tx, scaler: LossScaler, grads, opt_state, params,
                  scaler_state, overflow_reduce_axes=()):
    """One amp step: unscale → overflow check → conditional optimizer update.

    The TPU-native equivalent of apex's ``scale_loss`` context epilogue +
    patched ``optimizer.step`` skip (ref apex/amp/_process_optimizer.py).
    On overflow the optimizer state and params are left untouched via
    ``lax.cond`` — the whole step stays on device.

    Inside ``shard_map``, pass every mesh axis name in
    ``overflow_reduce_axes``: the overflow flag is psum-voted across them
    so ALL ranks take the same cond branch (the in-graph analog of the
    reference's NCCL-allreduced overflow buffer,
    ref apex/amp/scaler.py:unscale_with_stashed + _amp_state master flag).

    Returns ``(updates, new_opt_state, new_scaler_state, overflow)``.
    """
    with scope("amp/unscale"):
        unscaled, overflow = scaler.unscale(grads, scaler_state)
        if overflow_reduce_axes:
            ovf = to_varying(overflow.astype(jnp.float32),
                             overflow_reduce_axes)
            overflow = jax.lax.psum(ovf, tuple(overflow_reduce_axes)) > 0

    def do_update(_):
        return tx.update(unscaled, opt_state, params)

    # both cond branches must produce identical avals; derive the skip
    # branch's zeros from the update branch's output shapes/dtypes (updates
    # may be in grad dtype while params are in model dtype). Under
    # shard_map the update branch's avals can be VARYING over mesh axes
    # (e.g. tp-sharded parameters) — match each leaf's vma or lax.cond
    # rejects the branches with a type error.
    out_shapes = jax.eval_shape(do_update, None)

    def _match_vma(x, sd):
        return to_varying(x, sd.vma or ())

    def skip(_):
        zeros = jax.tree_util.tree_map(
            lambda sd: _match_vma(jnp.zeros(sd.shape, sd.dtype), sd),
            out_shapes[0])
        kept = jax.tree_util.tree_map(_match_vma, opt_state, out_shapes[1])
        return zeros, kept

    with scope("amp/update"):
        updates, new_opt_state = jax.lax.cond(overflow, skip, do_update,
                                              None)
        new_scaler_state = scaler.update(scaler_state, overflow)
    return updates, new_opt_state, new_scaler_state, overflow
