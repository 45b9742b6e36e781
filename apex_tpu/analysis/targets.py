"""Registered jaxpr-engine analysis targets: the repo's real entry
points, traced with representative avals and run through every jaxpr
check. ``python -m apex_tpu.analysis`` and tests/run_analysis execute
all of them, so a regression in donation discipline, collective axis
wiring, or a kernel's BlockSpecs fails tier-1 without hardware.

Each target is a zero-arg callable returning a list of Findings. Keep
them cheap: tracing only (no compile, no execution) on the CPU backend.
"""

from __future__ import annotations

from apex_tpu.analysis.findings import Finding
from apex_tpu.analysis.jaxpr_checks import JAXPR_CHECKS, analyze_fn
from apex_tpu.analysis.precision_checks import (
    PRECISION_CHECKS,
    analyze_precision,
)
from apex_tpu.analysis.sharding_checks import (
    SHARDING_CHECKS,
    analyze_sharding,
)
from apex_tpu.analysis.memory_checks import MEMORY_CHECKS, analyze_memory
from apex_tpu.analysis.spmd_checks import SPMD_CHECKS, analyze_spmd
from apex_tpu.analysis.state_checks import STATE_CHECKS, analyze_state

TARGETS = {}

# Per-target comms-bytes / peak-HBM estimates from the last
# analyze_sharding run of each sharding target (filled as the targets
# execute; read by run_sharding_findings and bench.py).
SHARDING_STATS = {}

# Per-target grandfather lists (the jaxpr analog of `# apex-lint:
# disable`, which only reaches AST findings): @target(..., allow=(...))
# drops those check ids from that target's findings at the source, so a
# deliberate half-precision path doesn't need a global baseline slot.
# The CLI's --allow target:check lands here too (see run_targets).
TARGET_ALLOW = {}

# Check ids produced by non-tracing targets (everything else emits the
# jaxpr_checks.JAXPR_CHECKS ids). The CLI derives --list-checks, check-id
# validation, and target narrowing from this — register new
# target-provided checks here, not in cli.py.
TARGET_CHECKS = ("kernel-auto-provenance", "step-record-schema")

# Check ids that require running the tracing targets (the CLI runs the
# full target suite when any of these is requested).
TRACING_CHECKS = (tuple(JAXPR_CHECKS) + tuple(PRECISION_CHECKS)
                  + tuple(SHARDING_CHECKS) + tuple(SPMD_CHECKS)
                  + tuple(STATE_CHECKS) + tuple(MEMORY_CHECKS))

# Per-target collective/host-effect counts from the last analyze_spmd
# run of each spmd target (the analysis/spmd_* gauge family).
SPMD_STATS = {}

# Per-target carried/saved leaf counts from the last analyze_state run
# of each state target (the analysis/state_* gauge family).
STATE_STATS = {}

# Per-target peak/steady liveness numbers from the last analyze_memory
# run of each memory target (the analysis/memory_* gauge family).
MEMORY_STATS = {}


def target(name, allow=()):
    def deco(fn):
        TARGETS[name] = fn
        if allow:
            unknown = set(allow) - set(TRACING_CHECKS) - set(TARGET_CHECKS)
            if unknown:
                raise ValueError(
                    f"@target({name!r}) allows unknown check id(s) "
                    f"{sorted(unknown)}")
            TARGET_ALLOW[name] = frozenset(allow)
        return fn
    return deco


@target("fused_adam_flat_step")
def _fused_adam_flat_step():
    """The flat-buffer Adam path behind a donated train step — the first
    customer the ISSUE names: its donated aliasing was never
    machine-checked."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.optimizers import fused_adam

    params = {"w": jnp.zeros((64, 128), jnp.float32),
              "b": jnp.zeros((128,), jnp.float32)}
    tx = fused_adam(lr=1e-3, weight_decay=0.01, flat=True)
    state = tx.init(params)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)

    def train_step(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state

    return analyze_fn(train_step, params, state, grads,
                      donate_argnums=(0, 1), name="fused_adam_flat_step")


@target("fused_adam_flat_kernel")
def _fused_adam_flat_kernel():
    """The Pallas flat-Adam kernel's BlockSpecs (scalar block + slab
    padding are the Mosaic-sensitive parts)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.optimizers import fused_adam
    from apex_tpu.ops import pallas_config

    params = {"w": jnp.zeros((4096,), jnp.float32)}
    tx = fused_adam(lr=1e-3, flat=True, use_kernel=True)
    state = tx.init(params)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    with pallas_config.force("interpret"):
        return analyze_fn(lambda g, s, p: tx.update(g, s, p),
                          grads, state, params,
                          name="fused_adam_flat_kernel")


@target("flash_attention_fwd")
def _flash_attention_fwd():
    import jax.numpy as jnp

    from apex_tpu.ops import pallas_config
    from apex_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((2, 256, 4, 64), jnp.bfloat16)
    with pallas_config.force("on"):
        return analyze_fn(
            lambda q, k, v: flash_attention(q, k, v, causal=True),
            q, q, q, name="flash_attention_fwd")


@target("layer_norm_fwd")
def _layer_norm_fwd():
    import jax.numpy as jnp

    from apex_tpu.ops import pallas_config
    from apex_tpu.ops.layer_norm import layer_norm

    x = jnp.zeros((256, 1024), jnp.bfloat16)
    w = jnp.ones((1024,), jnp.float32)
    b = jnp.zeros((1024,), jnp.float32)
    with pallas_config.force("on"):
        return analyze_fn(lambda x, w, b: layer_norm(x, w, b, (1024,)),
                          x, w, b, name="layer_norm_fwd")


@target("causal_softmax")
def _causal_softmax():
    import jax.numpy as jnp

    from apex_tpu.ops import pallas_config
    from apex_tpu.transformer.functional.fused_softmax import (
        scaled_upper_triang_masked_softmax,
    )

    x = jnp.zeros((8, 256, 256), jnp.bfloat16)
    with pallas_config.force("on"):
        return analyze_fn(
            lambda x: scaled_upper_triang_masked_softmax(x, None, 1.0),
            x, name="causal_softmax")


@target("causal_softmax_bwd")
def _causal_softmax_bwd():
    """Forward and backward kernels (``apex_softmax_bwd``) at once: the
    pallas-block check sees the backward's three row blocks."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import pallas_config
    from apex_tpu.transformer.functional.fused_softmax import (
        scaled_upper_triang_masked_softmax,
    )

    def loss(x):
        y = scaled_upper_triang_masked_softmax(x, None, 1.0)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    x = jnp.zeros((8, 256, 1024), jnp.bfloat16)
    with pallas_config.force("on"):
        return analyze_fn(jax.grad(loss), x, name="causal_softmax_bwd")


@target("tp_collectives")
def _tp_collectives():
    """Tensor-parallel allreduce wiring against the live parallel_state
    mesh — the collective-axis check's first customer."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer import parallel_state

    owned = not parallel_state.model_parallel_is_initialized()
    if owned:
        tp = 2 if len(jax.devices()) >= 2 else 1
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=tp)
    try:
        mesh = parallel_state.get_mesh()
        axis = parallel_state.get_tensor_model_parallel_group()
        tp = mesh.shape[axis]

        def allreduce(x):
            return jax.lax.psum(x, axis)

        fn = shard_map(allreduce, mesh=mesh, in_specs=P(axis),
                       out_specs=P())
        return analyze_fn(fn, jnp.zeros((tp * 8,), jnp.float32),
                          mesh_axes=mesh, name="tp_collectives")
    finally:
        if owned:
            parallel_state.destroy_model_parallel()


@target("kernel-auto-provenance")
def _kernel_auto_provenance():
    """Every pinned _KERNEL_AUTO verdict must name its evidence artifact
    (satellite: ops/pallas_config.py provenance)."""
    from apex_tpu.ops import pallas_config

    return [Finding("kernel-auto-provenance", "error",
                    "apex_tpu/ops/pallas_config.py", 0, "_KERNEL_AUTO",
                    problem)
            for problem in pallas_config.validate_kernel_auto_provenance()]


@target("step-record-schema")
def _step_record_schema():
    """The observability layer's own gate: a StepReporter record built
    from synthetic inputs must carry every STEP_RECORD_FIELDS key and
    survive a registry JSONL round-trip — the step-record schema is the
    evidence format every perf PR reads, so drift fails tier-1 here
    (ISSUE 2 satellite: the new module is registered and linted like
    any other entry point; the AST engine covers its sources via the
    default path set)."""
    import json as _json

    from apex_tpu.observability.registry import MetricRegistry
    from apex_tpu.observability.step_report import (
        STEP_RECORD_FIELDS, StepReporter,
    )

    findings = []

    def problem(msg):
        findings.append(Finding(
            "step-record-schema", "error",
            "apex_tpu/observability/step_report.py", 0, "StepReporter",
            msg))

    reg = MetricRegistry()
    rec = StepReporter("schema_check", registry=reg, tokens_per_step=1024,
                       flops_per_step=1e12, device_kind="cpu",
                       peak=1e15).step(0.01, loss=1.0)
    for field in STEP_RECORD_FIELDS:
        if field not in rec:
            problem(f"step record is missing documented field "
                    f"{field!r}")
    try:
        records = reg.to_records()
        _json.dumps(records)
    except (TypeError, ValueError) as e:
        problem(f"registry records are not JSON-serializable: {e}")
        return findings
    if not any(r.get("type") == "event" and r.get("name") == "step"
               for r in records):
        problem("StepReporter.step did not append a 'step' event to "
                "the registry")
    return findings


# ----------------------------------------------- precision-flow targets
# (ISSUE 3): the amp/optimizer/normalization/transformer entry points
# whose documented precision discipline the dataflow checks enforce.
# All are trace-only on the CPU backend, like everything above.

def _leaf_count(tree):
    import jax
    return len(jax.tree_util.tree_leaves(tree))


@target("mlp_train_step")
def _mlp_train_step():
    """bf16 MLP forward+backward with an fp32 loss: every dot must pin
    an fp32 accumulator (mlp.py preferred_element_type) and the loss
    reduction must run in fp32 — the seeded-regression anchor the ISSUE
    names (drop the preferred_element_type and tier-1 fails here)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.mlp import mlp_function

    params = (jnp.zeros((128, 256), jnp.bfloat16),
              jnp.zeros((256,), jnp.bfloat16),
              jnp.zeros((256, 64), jnp.bfloat16),
              jnp.zeros((64,), jnp.bfloat16))
    x = jnp.zeros((32, 128), jnp.bfloat16)
    y = jnp.zeros((32, 64), jnp.float32)

    def loss_fn(params, x, y):
        out = mlp_function(True, "relu", x, *params)
        d = out.astype(jnp.float32) - y
        return jnp.mean(jnp.square(d))

    return analyze_precision(
        lambda p, x, y: jax.value_and_grad(loss_fn)(p, x, y),
        params, x, y, name="mlp_train_step")


@target("amp_o1_train_step")
def _amp_o1_train_step():
    """O1: fp32 params, bf16 boundary casting via the active policy,
    loss scaled before backward. The precision contract here is that
    boundary-cast matmuls still accumulate fp32 and the loss math stays
    fp32 — exactly what docs/amp.md promises for O1."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.amp import amp as amp_mod
    from apex_tpu.amp.frontend import Policy
    from apex_tpu.amp.scaler import LossScaler
    from apex_tpu.mlp import mlp_function

    params = (jnp.zeros((128, 256), jnp.float32),
              jnp.zeros((256,), jnp.float32),
              jnp.zeros((256, 64), jnp.float32),
              jnp.zeros((64,), jnp.float32))
    x = jnp.zeros((32, 128), jnp.float32)
    y = jnp.zeros((32, 64), jnp.float32)
    scaler = LossScaler("dynamic")
    sstate = scaler.init()
    policy = Policy(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                    output_dtype=jnp.float32)

    def scaled_loss(params, x, y, sstate):
        out = mlp_function(True, "relu", x, *params)
        loss = jnp.mean(jnp.square(out.astype(jnp.float32) - y))
        return scaler.scale_loss(loss, sstate)

    with amp_mod.casting(policy):
        return analyze_precision(
            lambda p, x, y, s: jax.value_and_grad(scaled_loss)(p, x, y, s),
            params, x, y, sstate, name="amp_o1_train_step")


@target("amp_o2_master_update")
def _amp_o2_master_update():
    """O2 update phase: bf16 model copy, fp32 master + moments, scaled
    bf16 grads through unscale -> overflow-gated FusedAdam -> master
    apply -> half re-materialization. Exercises master-weights (the
    fp32 path must never dip to half) and loss-scale-bypass (the grads
    must pass the scaler's unscale before touching state)."""
    import jax
    import jax.numpy as jnp
    import optax

    from apex_tpu.amp.scaler import LossScaler, scaled_update
    from apex_tpu.optimizers import fused_adam

    master = {"w": jnp.zeros((64, 128), jnp.float32),
              "b": jnp.zeros((128,), jnp.float32)}
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16), master)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.ones_like(p, jnp.bfloat16), master)
    tx = fused_adam(lr=1e-3, weight_decay=0.01, flat=True)
    state = tx.init(master)
    scaler = LossScaler("dynamic")
    sstate = scaler.init()

    def update(grads, opt_state, master, params, sstate):
        updates, new_opt, new_ss, overflow = scaled_update(
            tx, scaler, grads, opt_state, master, sstate)
        new_master = optax.apply_updates(master, updates)
        new_params = jax.tree_util.tree_map(
            lambda m, p: m.astype(p.dtype), new_master, params)
        return new_master, new_opt, new_params, new_ss

    n_master = _leaf_count(master)
    n_state = _leaf_count(state)
    return analyze_precision(
        update, grads, state, master, params, sstate,
        roles={0: "grad", 1: "master", 2: "master", 3: "param",
               4: "scale"},
        master_outs=tuple(range(n_master + n_state)),
        name="amp_o2_master_update")


@target("fused_adam_tree_master_step")
def _fused_adam_tree_master_step():
    """Per-tensor FusedAdam over fp32 master params: the whole update
    chain (m, v, decay, apply) must stay fp32."""
    import jax
    import jax.numpy as jnp
    import optax

    from apex_tpu.optimizers import fused_adam

    master = {"w": jnp.zeros((64, 128), jnp.float32),
              "b": jnp.zeros((128,), jnp.float32)}
    tx = fused_adam(lr=1e-3, weight_decay=0.01, flat=False)
    state = tx.init(master)
    grads = jax.tree_util.tree_map(jnp.ones_like, master)

    def step(grads, state, master):
        updates, new_state = tx.update(grads, state, master)
        return optax.apply_updates(master, updates), new_state

    n_out = _leaf_count(master) + _leaf_count(state)
    return analyze_precision(
        step, grads, state, master,
        roles={1: "master", 2: "master"},
        master_outs=tuple(range(n_out)),
        name="fused_adam_tree_master_step")


@target("fused_lamb_master_step")
def _fused_lamb_master_step():
    """FusedLAMB over fp32 master params: grad-norm, trust ratio and
    moments are all reductions/chains that must accumulate fp32."""
    import jax
    import jax.numpy as jnp
    import optax

    from apex_tpu.optimizers import fused_lamb

    master = {"w": jnp.zeros((64, 128), jnp.float32),
              "b": jnp.zeros((128,), jnp.float32)}
    tx = fused_lamb(lr=1e-3, weight_decay=0.01)
    state = tx.init(master)
    grads = jax.tree_util.tree_map(jnp.ones_like, master)

    def step(grads, state, master):
        updates, new_state = tx.update(grads, state, master)
        return optax.apply_updates(master, updates), new_state

    n_out = _leaf_count(master) + _leaf_count(state)
    return analyze_precision(
        step, grads, state, master,
        roles={1: "master", 2: "master"},
        master_outs=tuple(range(n_out)),
        name="fused_lamb_master_step")


@target("fused_layer_norm_fwd_bwd")
def _fused_layer_norm_fwd_bwd():
    """FusedLayerNorm forward+backward on bf16 activations with fp32
    affine params (the Megatron mixed pattern): statistics and both
    backward reductions must be fp32 — the jnp fallback path is the one
    dataflow can see (the Pallas kernels are covered by their own unit
    tests and the pallas-block check)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.normalization import fused_layer_norm_affine

    x = jnp.zeros((256, 1024), jnp.bfloat16)
    w = jnp.ones((1024,), jnp.float32)
    b = jnp.zeros((1024,), jnp.float32)

    def loss(x, w, b):
        y = fused_layer_norm_affine(x, w, b, (1024,))
        return jnp.sum(y.astype(jnp.float32))

    return analyze_precision(
        lambda x, w, b: jax.grad(loss, argnums=(0, 1, 2))(x, w, b),
        x, w, b, name="fused_layer_norm_fwd_bwd")


@target("fused_rms_norm_fwd_bwd")
def _fused_rms_norm_fwd_bwd():
    import jax
    import jax.numpy as jnp

    from apex_tpu.normalization import fused_rms_norm_affine

    x = jnp.zeros((256, 1024), jnp.bfloat16)
    w = jnp.ones((1024,), jnp.float32)

    def loss(x, w):
        y = fused_rms_norm_affine(x, w, (1024,))
        return jnp.sum(y.astype(jnp.float32))

    return analyze_precision(
        lambda x, w: jax.grad(loss, argnums=(0, 1))(x, w),
        x, w, name="fused_rms_norm_fwd_bwd")


@target("fp8_matmul_delayed_scaling")
def _fp8_matmul_delayed_scaling():
    """The O4 epilogue end-to-end (ISSUE 13): one matmul site through
    the Fp8DelayedScaler context — scale-in, E4M3 cast, fp32-acc dot,
    scale-out, E5M2 grad cast, ring update. Both fp8 checks stay armed
    at 0 findings here because every cast sits behind a live,
    history-derived scale; drop the scale (or feed a constant) and
    tier-1 fails at the seeded regressions in
    tests/run_analysis/test_precision_checks.py."""
    import jax.numpy as jnp

    from apex_tpu.amp.scaler import Fp8DelayedScaler

    fp8 = Fp8DelayedScaler(["proj"], history=4)
    state = fp8.init()
    a = jnp.zeros((16, 32), jnp.bfloat16)
    b = jnp.zeros((32, 64), jnp.bfloat16)

    def step(a, b, state):
        with fp8.step(state) as ctx:
            def loss(a, b):
                y = ctx.matmul(a, b, name="proj")
                return jnp.sum(y.astype(jnp.float32))

            l, grads = ctx.value_and_grad(loss, argnums=(0, 1))(a, b)
        return l, grads, fp8.update(state, ctx)

    return analyze_precision(
        step, a, b, state,
        roles={2: ("fp8_scale", "amax_hist")},
        name="fp8_matmul_delayed_scaling")


@target("fp8_mlp_train_step")
def _fp8_mlp_train_step():
    """O4 over the mlp entry point: bf16 params, fp8 forward matmuls
    via the routed ``matmul_amp`` sites, fp32 loss — the whole fwd+bwd
    traced under the live context, so the fp8 casts inside the real
    library path (not a synthetic matmul) carry their scale provenance
    through the lattice. Also keeps lowprec-accum armed on the fp8
    path's de-scale/bias epilogue."""
    import jax.numpy as jnp

    from apex_tpu.amp.scaler import Fp8DelayedScaler
    from apex_tpu.mlp import mlp_function

    params = (jnp.zeros((64, 128), jnp.bfloat16),
              jnp.zeros((128,), jnp.bfloat16),
              jnp.zeros((128, 32), jnp.bfloat16),
              jnp.zeros((32,), jnp.bfloat16))
    x = jnp.zeros((16, 64), jnp.bfloat16)
    y = jnp.zeros((16, 32), jnp.float32)

    def loss(params, x, y):
        out = mlp_function(True, "relu", x, *params)
        return jnp.mean(jnp.square(out.astype(jnp.float32) - y))

    fp8 = Fp8DelayedScaler.for_step(loss, params, x, y, history=4)
    state = fp8.init()

    def step(params, x, y, state):
        with fp8.step(state) as ctx:
            l, grads = ctx.value_and_grad(loss)(params, x, y)
        return l, grads, fp8.update(state, ctx)

    return analyze_precision(
        step, params, x, y, state,
        roles={3: ("fp8_scale", "amax_hist")},
        name="fp8_mlp_train_step")


@target("tp_fused_softmax")
def _tp_fused_softmax():
    """Tensor-parallel fused softmax, jnp fallback path on bf16 logits:
    the exp must sit behind an fp32 upcast + max subtraction (the
    Pallas kernel keeps the same contract in VMEM)."""
    import jax.numpy as jnp

    from apex_tpu.transformer.functional.fused_softmax import (
        scaled_upper_triang_masked_softmax,
    )

    x = jnp.zeros((8, 256, 256), jnp.bfloat16)
    return analyze_precision(
        lambda x: scaled_upper_triang_masked_softmax(x, None, 1.0),
        x, name="tp_fused_softmax")


# ------------------------------------------------ sharding-flow targets
# (ISSUE 4): the parallelism entry points whose comms/HBM behavior the
# sharding checks pin down — TP layers fwd+bwd under GSPMD constraints,
# the shard_map collectives (PP 1F1B, DDP buckets, MoE all_to_all), and
# the TP-sharded optimizer master step. Trace-only, CPU backend.

def _world():
    import jax
    return len(jax.devices())


def _tp_size():
    world = _world()
    for tp in (4, 2):
        if world % tp == 0 and world >= tp:
            return tp
    return 1


def _owned_mesh(**kw):
    """(mesh, axis_sizes, owned) against parallel_state, honoring a mesh
    a caller already installed (same pattern as _tp_collectives)."""
    from apex_tpu.transformer import parallel_state

    owned = not parallel_state.model_parallel_is_initialized()
    if owned:
        parallel_state.initialize_model_parallel(**kw)
    mesh = parallel_state.get_mesh()
    sizes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    return mesh, sizes, owned


def _release_mesh(owned):
    if owned:
        from apex_tpu.transformer import parallel_state
        parallel_state.destroy_model_parallel()


def _tp_linear_fwd_bwd(kind, name):
    """Column/row-parallel fwd+bwd under GSPMD: partitioned params +
    the layers' own with_sharding_constraint boundaries. The propagated
    shardings must agree with every boundary (implicit-reshard), the
    params must actually shard (replicated-large), and the step must
    fit the HBM budget."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer.tensor_parallel.layers import (
        ColumnParallelLinear,
        RowParallelLinear,
        param_partition_specs,
    )

    mesh, sizes, owned = _owned_mesh(
        tensor_model_parallel_size_=_tp_size())
    try:
        if kind == "column":
            mod = ColumnParallelLinear(output_size=64,
                                       gather_output=False,
                                       params_dtype=jnp.float32)
            x = jnp.zeros((8, 32), jnp.bfloat16)
        else:
            mod = RowParallelLinear(output_size=32,
                                    input_is_parallel=True,
                                    params_dtype=jnp.float32)
            x = jnp.zeros((8, 64), jnp.bfloat16)
        with jax.sharding.set_mesh(mesh):
            variables = mod.init(jax.random.PRNGKey(0), x)
            specs = param_partition_specs(variables)

            def loss(variables, x):
                y, _ = mod.apply(variables, x)
                return jnp.sum(y.astype(jnp.float32))

            stats = SHARDING_STATS.setdefault(name, {})
            return analyze_sharding(
                jax.value_and_grad(loss), variables, x,
                in_specs=[specs, P(None, None)], axis_sizes=sizes,
                stats_out=stats, name=name)
    finally:
        _release_mesh(owned)


@target("tp_column_parallel_fwd_bwd")
def _tp_column_parallel_fwd_bwd():
    return _tp_linear_fwd_bwd("column", "tp_column_parallel_fwd_bwd")


@target("tp_row_parallel_fwd_bwd")
def _tp_row_parallel_fwd_bwd():
    """Row-parallel: the tp-contracted gemm leaves partial sums that
    the output constraint must resolve (the allreduce shows up in the
    target's comms-bytes estimate, not as a finding)."""
    return _tp_linear_fwd_bwd("row", "tp_row_parallel_fwd_bwd")


@target("tp_fused_softmax_sharded")
def _tp_fused_softmax_sharded():
    """The TP fused softmax under shard_map with the batch/head dim
    sharded over tp — collective-free by construction; the sharding
    pass proves it stays that way (0 comms bytes)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer.functional.fused_softmax import (
        scaled_upper_triang_masked_softmax,
    )

    mesh, sizes, owned = _owned_mesh(
        tensor_model_parallel_size_=_tp_size())
    try:
        fn = jax.shard_map(
            lambda x: scaled_upper_triang_masked_softmax(x, None, 1.0),
            mesh=mesh, in_specs=P("tp"), out_specs=P("tp"))
        stats = SHARDING_STATS.setdefault("tp_fused_softmax_sharded", {})
        return analyze_sharding(
            fn, jnp.zeros((8, 64, 64), jnp.bfloat16), axis_sizes=sizes,
            stats_out=stats, name="tp_fused_softmax_sharded")
    finally:
        _release_mesh(owned)


def _pp_1f1b(name, forward_only):
    """Shared builder for the two 1F1B pipeline targets (same stage
    model, shapes and mesh — one is the fwd+bwd step, the other the
    forward-only slice)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_pipelining_without_interleaving,
    )

    world = _world()
    pp = 4 if world % 4 == 0 and world >= 4 else (
        2 if world % 2 == 0 else 1)
    mesh, sizes, owned = _owned_mesh(pipeline_model_parallel_size_=pp)
    try:
        dim, m_count, mb = 8, 4, 2

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        def loss_fn(y, t):
            return jnp.mean((y - t) ** 2)

        params = {"w": jnp.zeros((pp, dim, dim)),
                  "b": jnp.zeros((pp, dim))}
        x = jnp.zeros((m_count, mb, dim))
        tgt = jnp.zeros((m_count, mb, dim))

        def step(params, x, tgt):
            local = jax.tree_util.tree_map(lambda p: p[0], params)
            loss, grads = forward_backward_pipelining_without_interleaving(
                stage_fn, loss_fn, local, x, tgt,
                forward_only=forward_only, axis_name="pp")
            if forward_only:
                return loss
            return loss, jax.tree_util.tree_map(
                lambda g: g[None], grads)

        out_specs = P() if forward_only else (P(), P("pp"))
        fn = jax.shard_map(step, mesh=mesh,
                           in_specs=(P("pp"), P(), P()),
                           out_specs=out_specs)
        stats = SHARDING_STATS.setdefault(name, {})
        return analyze_sharding(fn, params, x, tgt, axis_sizes=sizes,
                                stats_out=stats, name=name)
    finally:
        _release_mesh(owned)


@target("pp_1f1b_microbatch_step", allow=("dead-collective",))
def _pp_1f1b_microbatch_step():
    """1F1B microbatch train step (fwd+bwd) over the 'pp' ring.

    allow=dead-collective: differentiating the collective schedule
    makes AD transpose pbroadcasts into psums of replicated cotangents
    (summing n identical per-device contributions IS the chain rule —
    a scale by axis size, statically resolvable but AD-emitted, not
    user-written). The check stays armed for hand-written code via the
    forward-only slice of this very schedule below."""
    return _pp_1f1b("pp_1f1b_microbatch_step", forward_only=False)


@target("pp_1f1b_forward")
def _pp_1f1b_forward():
    """Forward-only slice of the 1F1B schedule: every collective here
    is hand-written (the scan ppermutes, the last-stage loss psum), so
    dead-collective stays fully armed on the pipeline family."""
    return _pp_1f1b("pp_1f1b_forward", forward_only=True)


@target("ddp_bucket_allreduce_step")
def _ddp_bucket_allreduce_step():
    """DDP gradient sync over 'dp': per-leaf and flat-bucket allreduce.
    The axis-size probes must be static (the psum(ones) pattern this
    target caught in parallel/distributed.py was a dead collective
    riding every bucket)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.distributed import (
        sync_gradients,
        sync_gradients_flat,
    )

    world = _world()
    tp = 2 if world % 2 == 0 and world > 1 else 1
    mesh, sizes, owned = _owned_mesh(tensor_model_parallel_size_=tp)
    try:
        grads = {"w": jnp.zeros((128, 128)), "b": jnp.zeros((128,))}
        spec = {"w": P("dp"), "b": P("dp")}

        def step(grads):
            # both reduction paths over the SAME raw grads (chaining
            # them would double-reduce — which this target's own
            # dead-collective check correctly flags)
            flat = sync_gradients_flat(grads, axis_name="dp")
            plain = sync_gradients(grads, axis_name="dp",
                                   gradient_predivide_factor=2.0)
            return jax.tree_util.tree_map(jnp.add, flat, plain)

        fn = jax.shard_map(step, mesh=mesh, in_specs=(spec,),
                           out_specs=spec)
        stats = SHARDING_STATS.setdefault("ddp_bucket_allreduce_step", {})
        return analyze_sharding(fn, grads, axis_sizes=sizes,
                                stats_out=stats,
                                name="ddp_bucket_allreduce_step")
    finally:
        _release_mesh(owned)


def _ddp_grad_model():
    """Shared model for the two overlapped-DDP targets: a dp-sharded
    batch producing full-shaped, genuinely per-rank gradients inside
    the shard_map body (an input-specced grad tree would either shrink
    to the local shard — breaking the bucket plan — or arrive
    replicated and trip dead-collective on the reduce)."""
    import jax.numpy as jnp

    def grads_of(x):
        # x: the local (batch/dp, 256) shard
        return {"w": (x.T @ x).astype(jnp.float32),
                "b": jnp.sum(x, axis=0)}

    return grads_of


@target("ddp_overlap_bucket_step")
def _ddp_overlap_bucket_step():
    """Backward-interleaved bucket allreduce (ISSUE 11 tentpole): the
    barrier-chained per-bucket psums of sync_gradients_overlapped over
    'dp'. The optimization_barrier issue-order chain must add no comms
    of its own and no reshards; the estimated bytes are the allreduce
    baseline the zero1 target's 0.75x acceptance ratio is measured
    against."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.overlap import sync_gradients_overlapped

    mesh, sizes, owned = _owned_mesh()
    try:
        grads_of = _ddp_grad_model()

        def step(x):
            return sync_gradients_overlapped(
                grads_of(x), axis_name="dp", bucket_cap_mb=0.1,
                gradient_predivide_factor=2.0)

        fn = jax.shard_map(step, mesh=mesh, in_specs=(P("dp"),),
                           out_specs={"w": P(), "b": P()},
                           check_vma=False)
        stats = SHARDING_STATS.setdefault("ddp_overlap_bucket_step", {})
        return analyze_sharding(
            fn, jnp.zeros((8 * sizes.get("dp", 1), 256), jnp.float32),
            axis_sizes=sizes, stats_out=stats,
            name="ddp_overlap_bucket_step")
    finally:
        _release_mesh(owned)


@target("zero1_fused_adam_step")
def _zero1_fused_adam_step():
    """ZeRO-1 sharded-optimizer step (ISSUE 11 tentpole): per-bucket
    psum_scatter of the fp32 grads + all_gather of the updated bf16
    params, state shards donated. The sharding-flow estimate must price
    this at <= 0.75x the allreduce target above (fp32 grads at twice
    the bf16 param width: RS 1.0 + AG 0.5 vs allreduce 2.0), with all
    five checks at 0 findings."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.zero import Zero1FusedAdam

    mesh, sizes, owned = _owned_mesh()
    try:
        dp = sizes.get("dp", 1)
        params = {"w": jnp.zeros((256, 256), jnp.bfloat16),
                  "b": jnp.zeros((256,), jnp.bfloat16)}
        opt = Zero1FusedAdam(lr=1e-3, weight_decay=0.01, axis_name="dp",
                             num_shards=dp, bucket_cap_mb=0.1)
        state = opt.init(params)
        grads_of = _ddp_grad_model()

        def step(x, state, params):
            return opt.step(grads_of(x), state, params)

        state_specs = opt.state_specs(params)
        fn = jax.shard_map(
            step, mesh=mesh,
            in_specs=(P("dp"), state_specs, {"w": P(), "b": P()}),
            out_specs=({"w": P(), "b": P()}, state_specs),
            check_vma=False)
        stats = SHARDING_STATS.setdefault("zero1_fused_adam_step", {})
        return analyze_sharding(
            fn, jnp.zeros((8 * dp, 256), jnp.float32), state, params,
            donate_argnums=(1,), axis_sizes=sizes, stats_out=stats,
            name="zero1_fused_adam_step")
    finally:
        _release_mesh(owned)


@target("fused_adam_master_sharded_step")
def _fused_adam_master_sharded_step():
    """Per-tensor FusedAdam over tp-sharded fp32 master params under
    GSPMD, donated state: master/m/v shard like the params they mirror
    (replicated-large's canonical customer) and the donated buffers
    earn their HBM credit in the budget walk."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.optimizers import fused_adam

    mesh, sizes, owned = _owned_mesh(
        tensor_model_parallel_size_=_tp_size())
    try:
        master = {"w": jnp.zeros((256, 1024), jnp.float32),
                  "b": jnp.zeros((1024,), jnp.float32)}
        tx = fused_adam(lr=1e-3, weight_decay=0.01, flat=False)
        state = tx.init(master)
        grads = jax.tree_util.tree_map(jnp.ones_like, master)

        def step(grads, state, master):
            updates, new_state = tx.update(grads, state, master)
            return optax.apply_updates(master, updates), new_state

        wspec = {"w": P(None, "tp"), "b": P("tp")}
        state_spec = jax.tree_util.tree_map(
            lambda s: (wspec["w"] if getattr(s, "ndim", 0) == 2 else
                       wspec["b"] if getattr(s, "ndim", 0) == 1 else P()),
            state, is_leaf=lambda s: hasattr(s, "shape"))
        with jax.sharding.set_mesh(mesh):
            stats = SHARDING_STATS.setdefault(
                "fused_adam_master_sharded_step", {})
            return analyze_sharding(
                step, grads, state, master,
                in_specs=[wspec, state_spec, wspec],
                donate_argnums=(1, 2), axis_sizes=sizes,
                stats_out=stats, name="fused_adam_master_sharded_step")
    finally:
        _release_mesh(owned)


@target("moe_dispatch")
def _moe_dispatch():
    """GShard MoE dispatch over 'ep': tokens shard over dp×ep so the
    all_to_all pair actually moves expert slabs (with replicated
    tokens it would be a dead collective — the seeded regression)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.transformer.moe import (
        MoEConfig,
        init_moe_params,
        moe_mlp,
    )

    world = _world()
    ep = 4 if world % 4 == 0 and world >= 4 else (
        2 if world % 2 == 0 else 1)
    dp = world // ep
    mesh = Mesh(np.asarray(jax.devices()).reshape(dp, ep), ("dp", "ep"))
    sizes = {"dp": dp, "ep": ep}
    cfg = MoEConfig(hidden_size=16, ffn_hidden_size=32,
                    num_experts=max(ep, 2), top_k=2)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)

    def step(p, x):
        y, aux = moe_mlp(p, x, cfg, ep_axis="ep")
        return y, jax.lax.pmean(aux, "dp")

    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=({"router": P(), "wi": P("ep"), "wo": P("ep")},
                  P(("dp", "ep"))),
        out_specs=(P(("dp", "ep")), P()), check_vma=False)
    stats = SHARDING_STATS.setdefault("moe_dispatch", {})
    return analyze_sharding(
        fn, params, jnp.zeros((8 * max(dp * ep, 1), 16)),
        axis_sizes=sizes, stats_out=stats, name="moe_dispatch")


SHARDING_TARGETS = (
    "tp_column_parallel_fwd_bwd", "tp_row_parallel_fwd_bwd",
    "tp_fused_softmax_sharded", "pp_1f1b_microbatch_step",
    "pp_1f1b_forward", "ddp_bucket_allreduce_step",
    "ddp_overlap_bucket_step", "zero1_fused_adam_step",
    "fused_adam_master_sharded_step", "moe_dispatch",
)


# --------------------------------------------- rank-consistency targets
# (ISSUE 14): the real grad-sync/pipeline/optimizer schedules run
# through the spmd rank-consistency checks — collectives under
# rank-divergent control, out_specs claiming replication the program
# does not establish, uncoordinated RNG, unordered host effects between
# collectives. Trace-only, CPU backend, like everything above.


def _analyze_spmd_target(name, fn, *args, **kw):
    stats = SPMD_STATS.setdefault(name, {})
    return analyze_spmd(fn, *args, name=name, stats_out=stats, **kw)


@target("spmd_ddp_sync_gradients")
def _spmd_ddp_sync_gradients():
    """The per-leaf + flat-bucket DDP grad sync (sync_gradients /
    sync_gradients_flat): grads born per-rank from the dp-sharded
    batch, psum-reduced, stored through P() out_specs — the exact
    replication contract rank-divergent-update audits. Drop a psum and
    tier-1 fails here."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.distributed import (
        sync_gradients,
        sync_gradients_flat,
    )

    mesh, sizes, owned = _owned_mesh()
    try:
        grads_of = _ddp_grad_model()

        def step(x):
            g = grads_of(x)
            flat = sync_gradients_flat(g, axis_name="dp")
            plain = sync_gradients(g, axis_name="dp",
                                   gradient_predivide_factor=2.0)
            return jax.tree_util.tree_map(jnp.add, flat, plain)

        fn = jax.shard_map(step, mesh=mesh, in_specs=(P("dp"),),
                           out_specs={"w": P(), "b": P()},
                           check_vma=False)
        return _analyze_spmd_target(
            "spmd_ddp_sync_gradients", fn,
            jnp.zeros((8 * sizes.get("dp", 1), 256), jnp.float32),
            axis_sizes=sizes)
    finally:
        _release_mesh(owned)


@target("spmd_ddp_overlap_bucket_step")
def _spmd_ddp_overlap_bucket_step():
    """The barrier-chained overlapped bucket allreduce (ISSUE 11's
    engine): the optimization_barrier issue chain must not launder
    distinctness or anchor-free host effects into the schedule."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.overlap import sync_gradients_overlapped

    mesh, sizes, owned = _owned_mesh()
    try:
        grads_of = _ddp_grad_model()

        def step(x):
            return sync_gradients_overlapped(
                grads_of(x), axis_name="dp", bucket_cap_mb=0.1,
                gradient_predivide_factor=2.0)

        fn = jax.shard_map(step, mesh=mesh, in_specs=(P("dp"),),
                           out_specs={"w": P(), "b": P()},
                           check_vma=False)
        return _analyze_spmd_target(
            "spmd_ddp_overlap_bucket_step", fn,
            jnp.zeros((8 * sizes.get("dp", 1), 256), jnp.float32),
            axis_sizes=sizes)
    finally:
        _release_mesh(owned)


@target("spmd_fleet_probe_grad_sync")
def _spmd_fleet_probe_grad_sync():
    """The overlapped grad sync with the PR 11 fleet barrier-wait probe
    ARMED: its io_callback enter marker is barrier-tied into the
    collective operand and its exit callback is fed the reduced result,
    so unordered-host-effect must hold the probe's own call sites at 0
    — the acceptance clause ISSUE 14 names."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.observability.fleet import probe
    from apex_tpu.parallel.overlap import sync_gradients_overlapped

    mesh, sizes, owned = _owned_mesh()
    was = probe._ENABLED
    probe.enable()
    try:
        grads_of = _ddp_grad_model()

        def step(x):
            return sync_gradients_overlapped(
                grads_of(x), axis_name="dp", bucket_cap_mb=0.1)

        fn = jax.shard_map(step, mesh=mesh, in_specs=(P("dp"),),
                           out_specs={"w": P(), "b": P()},
                           check_vma=False)
        findings = _analyze_spmd_target(
            "spmd_fleet_probe_grad_sync", fn,
            jnp.zeros((8 * sizes.get("dp", 1), 256), jnp.float32),
            axis_sizes=sizes)
        stats = SPMD_STATS["spmd_fleet_probe_grad_sync"]
        if not stats.get("host_effects"):
            # the probe silently tracing to nothing would hollow the
            # acceptance contract out — same loud-failure rule as a
            # typo'd target name
            raise RuntimeError(
                "fleet probe did not emit host callbacks into the "
                "traced grad sync — is probe.enable() broken?")
        return findings
    finally:
        probe._ENABLED = was
        _release_mesh(owned)


@target("spmd_zero1_fused_adam_step")
def _spmd_zero1_fused_adam_step():
    """ZeRO-1 scatter/gather: params must exit replicated (the
    all_gather), per-rank mu/nu shards must exit through P('dp')
    out_specs — a rank-indexed dynamic_slice feeding state is only
    legal because the out_names declare the dim-0 sharding."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.zero import Zero1FusedAdam

    mesh, sizes, owned = _owned_mesh()
    try:
        dp = sizes.get("dp", 1)
        params = {"w": jnp.zeros((256, 256), jnp.bfloat16),
                  "b": jnp.zeros((256,), jnp.bfloat16)}
        opt = Zero1FusedAdam(lr=1e-3, weight_decay=0.01, axis_name="dp",
                             num_shards=dp, bucket_cap_mb=0.1)
        state = opt.init(params)
        grads_of = _ddp_grad_model()

        def step(x, state, params):
            return opt.step(grads_of(x), state, params)

        state_specs = opt.state_specs(params)
        fn = jax.shard_map(
            step, mesh=mesh,
            in_specs=(P("dp"), state_specs, {"w": P(), "b": P()}),
            out_specs=({"w": P(), "b": P()}, state_specs),
            check_vma=False)
        return _analyze_spmd_target(
            "spmd_zero1_fused_adam_step", fn,
            jnp.zeros((8 * dp, 256), jnp.float32), state, params,
            axis_sizes=sizes)
    finally:
        _release_mesh(owned)


@target("spmd_pp_1f1b_microbatch_step")
def _spmd_pp_1f1b_microbatch_step():
    """The 1F1B pipeline train step: scan-carried ppermutes keep the
    activations pp-distinct, the last-stage loss select is rank-origin
    data — and the loss psum + P('pp') grad out_specs must account for
    every one of those axes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_pipelining_without_interleaving,
    )

    world = _world()
    pp = 4 if world % 4 == 0 and world >= 4 else (
        2 if world % 2 == 0 else 1)
    mesh, sizes, owned = _owned_mesh(pipeline_model_parallel_size_=pp)
    try:
        dim, m_count, mb = 8, 4, 2

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        def loss_fn(y, t):
            return jnp.mean((y - t) ** 2)

        params = {"w": jnp.zeros((pp, dim, dim)),
                  "b": jnp.zeros((pp, dim))}
        x = jnp.zeros((m_count, mb, dim))
        tgt = jnp.zeros((m_count, mb, dim))

        def step(params, x, tgt):
            local = jax.tree_util.tree_map(lambda p: p[0], params)
            loss, grads = \
                forward_backward_pipelining_without_interleaving(
                    stage_fn, loss_fn, local, x, tgt,
                    forward_only=False, axis_name="pp")
            return loss, jax.tree_util.tree_map(
                lambda g: g[None], grads)

        fn = jax.shard_map(step, mesh=mesh,
                           in_specs=(P("pp"), P(), P()),
                           out_specs=(P(), P("pp")))
        return _analyze_spmd_target(
            "spmd_pp_1f1b_microbatch_step", fn, params, x, tgt,
            axis_sizes=sizes)
    finally:
        _release_mesh(owned)


@target("spmd_llama_o4_step")
def _spmd_llama_o4_step():
    """The llama O4 train step (ISSUE 13's fp8 tier over the 3D mesh),
    mirroring examples/llama_train.py --opt-level O4: pipelined
    forward, vocab-parallel CE, fp8 delayed scaling pmax'd over every
    axis, dp-pmean'd grads — the largest real schedule in the gate.
    The fp8 state and loss exit through P() out_specs, so a missing
    reduce anywhere in that chain is a rank-divergent-update here."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.amp import Fp8DelayedScaler
    from apex_tpu.models import llama
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        pipelined_forward,
    )
    from apex_tpu.transformer.tensor_parallel.cross_entropy import (
        vocab_parallel_cross_entropy,
    )
    from apex_tpu.transformer.tensor_parallel.mappings import _to_varying

    import numpy as np

    world = _world()
    if world >= 8:
        pp, dp, tp = 2, 2, 2
    elif world >= 4:
        pp, dp, tp = 1, 2, 2
    else:
        pp, dp, tp = 1, 1, max(world, 1)
    n_dev = pp * dp * tp
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]).reshape(pp, dp, tp),
                ("pp", "dp", "tp"))
    sizes = {"pp": pp, "dp": dp, "tp": tp}
    sp = tp > 1
    M, mb, s = 2, 2, 16
    cfg = llama.tiny(num_layers=max(pp, 1), num_heads=2 * tp,
                     num_kv_heads=tp, hidden_size=32 * tp,
                     intermediate_size=64 * tp, vocab_size=128 * tp,
                     max_seq_len=s)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    stage_params = llama.split_stages(params, pp)
    io_params = {k: v for k, v in params.items() if k != "layers"}
    tx = fused_adam(lr=1e-3)
    fp8 = Fp8DelayedScaler(["lm_head"], history=4)

    def psum(t, ax):
        return jax.lax.psum(_to_varying(t, ax), ax)

    def pmean(t, ax):
        return jax.lax.pmean(_to_varying(t, ax), ax)

    def train_step(stage_params, io_params, opt_state, tokens, targets,
                   fp8_state):
        pp_rank = jax.lax.axis_index("pp")
        pp_size = jax.lax.axis_size("pp")

        def vary_all(t):
            for ax in ("pp", "dp", "tp"):
                t = jax.tree_util.tree_map(
                    lambda a, ax=ax: _to_varying(a, ax), t)
            return t

        def total_loss(trees):
            stage, io = trees
            stage = jax.tree_util.tree_map(lambda a: a[0], stage)
            stage, io = vary_all(stage), vary_all(io)
            x_mb = vary_all(jax.vmap(
                lambda tok: llama.embed(io, tok, cfg, tp_axis="tp",
                                        sequence_parallel=sp))(tokens))
            positions = llama._positions(mb, s, None)

            def stage_fn(sp_params, x):
                return llama.stage_fn(sp_params, x, cfg, positions,
                                      tp_axis="tp", cp_axis=None,
                                      sequence_parallel=sp)

            outs = pipelined_forward(stage_fn, stage, x_mb,
                                     axis_name="pp", remat=True)
            o2 = outs.reshape((M * mb,) + outs.shape[2:])
            t2 = targets.reshape((M * mb,) + targets.shape[2:])
            logits = llama.lm_head(io, o2, cfg, tp_axis="tp",
                                   sequence_parallel=sp)
            losses = jnp.mean(vocab_parallel_cross_entropy(
                logits, t2, axis_name="tp"))
            local = jnp.where(pp_rank == pp_size - 1, losses, 0.0)
            return jax.lax.psum(local, "pp")

        with fp8.step(fp8_state) as fp8_ctx:
            loss, (g_stage, g_io) = fp8_ctx.value_and_grad(
                total_loss)((stage_params, io_params))
        new_fp8 = fp8.update(fp8_state, fp8_ctx,
                             reduce_axes=("pp", "dp", "tp"))
        g_stage = jax.tree_util.tree_map(
            lambda g: pmean(g, "dp"), g_stage)
        g_io = jax.tree_util.tree_map(
            lambda g: pmean(psum(g, "pp"), "dp"), g_io)
        if sp:
            g_stage = {k: (psum(v, "tp") if k.endswith("norm") else v)
                       for k, v in g_stage.items()}
            g_io = {k: (psum(v, "tp") if k == "final_norm" else v)
                    for k, v in g_io.items()}
        grads = {"stage": g_stage, "io": g_io}
        updates, opt_state = tx.update(
            grads, opt_state, {"stage": stage_params, "io": io_params})
        new_stage = jax.tree_util.tree_map(
            jnp.add, stage_params, updates["stage"])
        new_io = jax.tree_util.tree_map(
            jnp.add, io_params, updates["io"])
        loss = jax.lax.pmean(jax.lax.pmean(loss, "dp"), "tp")
        return new_stage, new_io, opt_state, new_fp8, loss

    from apex_tpu.optimizers import opt_partition_specs

    lp = llama.param_specs(cfg)["layers"]
    io_specs = {"embed": P("tp", None), "final_norm": P(),
                "lm_head": P(None, "tp")}
    stage_specs = {k: P("pp", *lp[k]) for k in lp}
    with mesh:
        opt_state = tx.init({"stage": stage_params, "io": io_params})
        opt_specs = opt_partition_specs(
            tx, {"stage": stage_params, "io": io_params},
            {"stage": stage_specs, "io": io_specs})
        fp8_state0 = fp8.init()
        fp8_specs = jax.tree_util.tree_map(lambda _: P(), fp8_state0)
        fn = jax.shard_map(
            train_step, mesh=mesh,
            in_specs=(stage_specs, io_specs, opt_specs,
                      P(None, "dp", None), P(None, "dp", None),
                      fp8_specs),
            out_specs=(stage_specs, io_specs, opt_specs, fp8_specs,
                       P()),
            check_vma=False)
        tokens = jnp.zeros((M, mb * dp, s), jnp.int32)
        return _analyze_spmd_target(
            "spmd_llama_o4_step", fn, stage_params, io_params,
            opt_state, tokens, tokens, fp8_state0, axis_sizes=sizes)


@target("spmd_simple_distributed")
def _spmd_simple_distributed():
    """examples/simple_distributed.py's own train step (the satellite:
    the example now does its DDP reduction explicitly under
    check_vma=False, and THIS target is what keeps that pmean in
    place — remove it and tier-1 fails as a rank-divergent-update)."""
    import os
    import sys

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from examples.simple_distributed import make_train_step

    from apex_tpu.optimizers import fused_adam

    world = _world()
    mesh = Mesh(np.asarray(jax.devices()[:world]), ("data",))
    sizes = {"data": world}
    tx = fused_adam(lr=1e-2)
    w = jnp.zeros((16, 1))
    opt_state = tx.init(w)
    x = jnp.zeros((8 * world, 16))
    y = jnp.zeros((8 * world, 1))
    fn = jax.shard_map(
        make_train_step(tx), mesh=mesh,
        in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()), check_vma=False)
    return _analyze_spmd_target(
        "spmd_simple_distributed", fn, w, opt_state, x, y,
        axis_sizes=sizes)


SPMD_TARGETS = (
    "spmd_ddp_sync_gradients", "spmd_ddp_overlap_bucket_step",
    "spmd_fleet_probe_grad_sync", "spmd_zero1_fused_adam_step",
    "spmd_pp_1f1b_microbatch_step", "spmd_llama_o4_step",
    "spmd_simple_distributed", "spmd_serving_decode_step",
)


def run_targets(names=None, extra_allow=None, timings=None):
    """Run the registered targets; returns (findings, errors) where
    errors maps target name -> repr of an exception that kept the target
    from tracing at all (itself a failure the caller should surface).

    ``extra_allow``: {target name: set of check ids} merged over the
    ``@target(allow=...)`` lists — findings of an allowed check from
    that target are dropped (the per-target grandfather the CLI's
    ``--allow target:check`` feeds). ``timings``: optional dict that
    receives per-target wall seconds (the CLI rolls these up into the
    per-engine gate-latency summary)."""
    import time

    findings, errors = [], {}
    for name, fn in TARGETS.items():
        if names is not None and name not in names:
            continue
        allowed = set(TARGET_ALLOW.get(name, ()))
        if extra_allow:
            allowed |= set(extra_allow.get(name, ()))
        t0 = time.perf_counter()  # apex-lint: disable=raw-clock
        try:
            got = fn()
        except Exception as e:  # noqa: BLE001 — report, don't abort the scan
            errors[name] = repr(e)[:300]
            continue
        finally:
            if timings is not None:
                timings[name] = (
                    time.perf_counter() - t0)  # apex-lint: disable=raw-clock
        if allowed:
            got = [f for f in got if f.check not in allowed]
        findings.extend(got)
    return findings, errors


def run_precision_findings(registry=None, names=None):
    """Run only the precision-flow targets and publish their finding
    counts to the observability registry (``analysis/precision``
    counter family) — the hook bench.py reports through. Returns
    (findings, errors)."""
    from apex_tpu.analysis.precision_checks import report_to_registry

    wanted = names if names is not None else PRECISION_TARGETS
    findings, errors = run_targets(wanted)
    findings = [f for f in findings if f.check in PRECISION_CHECKS]
    report_to_registry(findings, registry=registry)
    return findings, errors


PRECISION_TARGETS = (
    "mlp_train_step", "amp_o1_train_step", "amp_o2_master_update",
    "fused_adam_tree_master_step", "fused_lamb_master_step",
    "fused_layer_norm_fwd_bwd", "fused_rms_norm_fwd_bwd",
    "tp_fused_softmax", "fp8_matmul_delayed_scaling",
    "fp8_mlp_train_step",
)


def run_sharding_findings(registry=None, names=None):
    """Run only the sharding-flow targets and publish finding counts +
    per-target comms-bytes / peak-HBM estimates to the observability
    registry (``analysis/sharding_*`` family) — the hook bench.py
    reports through. Returns (findings, errors, stats) where stats is
    {target: {"comms_bytes", "peak_hbm_bytes", ...}}."""
    from apex_tpu.analysis.sharding_checks import (
        SHARDING_CHECKS as _SC,
        report_to_registry,
    )

    wanted = tuple(names) if names is not None else SHARDING_TARGETS
    unknown = set(wanted) - set(TARGETS)
    if unknown:
        # a typo'd name silently yielding an all-zero stats row would
        # read as "analyzed and clean" forever — same loud-failure rule
        # as the CLI's unknown-check/path validation
        raise ValueError(
            f"unknown sharding target(s) {sorted(unknown)}; valid: "
            f"{sorted(SHARDING_TARGETS)}")
    findings, errors = run_targets(set(wanted))
    findings = [f for f in findings if f.check in _SC]
    results = {}
    for name in wanted:
        if name in errors:
            continue
        results[name] = (
            [f for f in findings if f.symbol == name],
            dict(SHARDING_STATS.get(name, {})),
        )
    report_to_registry(results, registry=registry)
    stats = {name: s for name, (_, s) in results.items()}
    return findings, errors, stats


def run_spmd_findings(registry=None, names=None):
    """Run only the rank-consistency targets and publish finding counts
    + per-target collective/host-effect counts to the observability
    registry (``analysis/spmd_*`` family) — the hook bench.py reports
    through. Returns (findings, errors, stats)."""
    from apex_tpu.analysis.spmd_checks import (
        SPMD_CHECKS as _SP,
        report_to_registry as _report,
    )

    wanted = tuple(names) if names is not None else SPMD_TARGETS
    unknown = set(wanted) - set(TARGETS)
    if unknown:
        raise ValueError(
            f"unknown spmd target(s) {sorted(unknown)}; valid: "
            f"{sorted(SPMD_TARGETS)}")
    findings, errors = run_targets(set(wanted))
    findings = [f for f in findings if f.check in _SP]
    results = {}
    for name in wanted:
        if name in errors:
            continue
        results[name] = (
            [f for f in findings if f.symbol == name],
            dict(SPMD_STATS.get(name, {})),
        )
    _report(results, registry=registry)
    stats = {name: s for name, (_, s) in results.items()}
    return findings, errors, stats


# ---- checkpoint/state-flow targets (ISSUE 18) ------------------------
# The resume-compatibility surface: each target is a train step in
# carry form (state as argnum 0, new state in the outputs) run through
# analyze_state — the step-carry fixpoint, save-tree coverage, the
# manifest schema round-trip, and (where state is dp-sharded) the
# elastic-reshard proof. All at 0 findings: every seeded regression
# lives in tests/run_analysis/test_state_checks.py.

@target("state_llama_o4_step")
def _state_llama_o4_step():
    """The llama O4 train step in carry form: params + fused-adam tree
    state + the fp8 delayed-scaling rings all round one step. The
    fixpoint must see every fp8 ring column and the adam moments as
    step-carried, and the identity save tree must cover them — drop
    any field from the carry's save path and this target turns red."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.amp import Fp8DelayedScaler
    from apex_tpu.models import llama
    from apex_tpu.optimizers import fused_adam

    cfg = llama.tiny(num_layers=1, num_heads=2, num_kv_heads=1,
                     hidden_size=32, intermediate_size=64,
                     vocab_size=128, max_seq_len=16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tx = fused_adam(lr=1e-3)
    fp8 = Fp8DelayedScaler(["lm_head"], history=4)
    carry = (params, tx.init(params), fp8.init())
    tokens = jnp.zeros((2, 16), jnp.int32)

    def train_step(carry, tokens, targets):
        params, opt_state, fp8_state = carry

        def loss_fn(p):
            logits = llama.forward(p, tokens, cfg, tp_axis=None,
                                   cp_axis=None, ep_axis=None)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                lp, targets[..., None], axis=-1))

        with fp8.step(fp8_state) as ctx:
            loss, grads = ctx.value_and_grad(loss_fn)(params)
        new_fp8 = fp8.update(fp8_state, ctx)
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = jax.tree_util.tree_map(jnp.add, params, updates)
        return (new_params, new_opt, new_fp8), loss

    stats = STATE_STATS.setdefault("state_llama_o4_step", {})
    return analyze_state(train_step, carry, tokens, tokens,
                         name="state_llama_o4_step", stats_out=stats)


@target("state_zero1_fused_adam_step")
def _state_zero1_fused_adam_step():
    """ZeRO-1 carry step + the elastic-reshard proof: the dp-sharded
    mu/nu buckets must be step-carried, covered by the save tree,
    schema-stable through the format-2 manifest encoding, AND legally
    re-shardable onto every candidate the optimizer itself claims
    (state_layout/elastic_candidates) — the machine check on zero.py's
    pure-reshard contract."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.zero import Zero1FusedAdam

    mesh, sizes, owned = _owned_mesh()
    try:
        dp = sizes.get("dp", 1)
        params = {"w": jnp.zeros((256, 256), jnp.bfloat16),
                  "b": jnp.zeros((256,), jnp.bfloat16)}
        opt = Zero1FusedAdam(lr=1e-3, weight_decay=0.01, axis_name="dp",
                             num_shards=dp, bucket_cap_mb=0.1)
        state = opt.init(params)
        grads_of = _ddp_grad_model()

        def step(x, state, params):
            return opt.step(grads_of(x), state, params)

        state_specs = opt.state_specs(params)
        param_specs = {"w": P(), "b": P()}
        fn = jax.shard_map(
            step, mesh=mesh,
            in_specs=(P("dp"), state_specs, param_specs),
            out_specs=(param_specs, state_specs),
            check_vma=False)

        def train_step(carry, x):
            params, ostate = carry
            new_params, new_ostate = fn(x, ostate, params)
            return new_params, new_ostate

        stats = STATE_STATS.setdefault("state_zero1_fused_adam_step", {})
        return analyze_state(
            train_step, (params, state),
            jnp.zeros((8 * dp, 256), jnp.float32),
            name="state_zero1_fused_adam_step",
            specs=(param_specs, state_specs),
            reshard_layout=opt.state_layout(params),
            reshard_candidates=opt.elastic_candidates(params),
            axis_sizes=sizes, stats_out=stats)
    finally:
        _release_mesh(owned)


@target("state_ddp_overlap_step")
def _state_ddp_overlap_step():
    """Overlapped-DDP amp step: flat-adam state plus the LossScaleState
    counters round the carry through scaled_update's lax.cond skip —
    the fixpoint must prove both cond branches keep the opt state
    live, and every scaler counter saved."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.amp import LossScaler, scaled_update
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.parallel.overlap import sync_gradients_overlapped

    mesh, sizes, owned = _owned_mesh()
    try:
        dp = sizes.get("dp", 1)
        params = {"w": jnp.zeros((256, 256), jnp.float32),
                  "b": jnp.zeros((256,), jnp.float32)}
        tx = fused_adam(lr=1e-3, flat=True)
        scaler = LossScaler()
        carry = (params, tx.init(params), scaler.init())
        grads_of = _ddp_grad_model()

        def inner(x, params, opt_state, sstate):
            grads = sync_gradients_overlapped(
                grads_of(x), axis_name="dp", bucket_cap_mb=0.1)
            updates, new_opt, new_sstate, _ovf = scaled_update(
                tx, scaler, grads, opt_state, params, sstate,
                overflow_reduce_axes=("dp",))
            new_params = jax.tree_util.tree_map(
                jnp.add, params, updates)
            return new_params, new_opt, new_sstate

        fn = jax.shard_map(
            inner, mesh=mesh, in_specs=(P("dp"), P(), P(), P()),
            out_specs=(P(), P(), P()), check_vma=False)

        def train_step(carry, x):
            params, opt_state, sstate = carry
            return fn(x, params, opt_state, sstate)

        stats = STATE_STATS.setdefault("state_ddp_overlap_step", {})
        return analyze_state(
            train_step, carry,
            jnp.zeros((8 * dp, 256), jnp.float32),
            name="state_ddp_overlap_step", axis_sizes=sizes,
            stats_out=stats)
    finally:
        _release_mesh(owned)


@target("state_resilient_resume_path")
def _state_resilient_resume_path():
    """The ResilientTrainLoop resume composition: restore → first step
    with the restored reference retained as fallback_state
    (loop.resume_path mirrors run()'s real shape). The loop's step
    contract forbids donation, and this target is what enforces it —
    jit the step with donate_argnums=(0,) and restore-donation-hazard
    fires on the held fallback reference."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.resilience.loop import resume_path

    key = jax.random.PRNGKey(0)
    state = {"w": jnp.ones((16, 16), jnp.float32)}

    @jax.jit  # NON-donating: the loop's documented step contract
    def step_fn(state, step):
        g = jax.random.normal(jax.random.fold_in(key, step), (16, 16))
        w = state["w"] - 0.01 * (g + 0.1 * state["w"])
        return {"w": w}, {"loss": jnp.mean(w * w)}

    stats = STATE_STATS.setdefault("state_resilient_resume_path", {})
    return analyze_state(
        step_fn, state, jnp.int32(0),
        name="state_resilient_resume_path",
        save_tree_of=lambda s: {"state": s},  # the loop's save shape
        resume_fn=resume_path(step_fn), resume_args=(jnp.int32(0),),
        stats_out=stats)


STATE_TARGETS = (
    "state_llama_o4_step", "state_zero1_fused_adam_step",
    "state_ddp_overlap_step", "state_resilient_resume_path",
    "state_serving_decode_step",
)


def run_state_findings(registry=None, names=None):
    """Run only the checkpoint/state-flow targets and publish finding
    counts (zero-filled over every check id) + per-target carried/saved
    leaf counts to the observability registry (``analysis/state_*``
    family) — the hook bench.py reports through. Returns
    (findings, errors, stats)."""
    from apex_tpu.analysis.state_checks import (
        STATE_CHECKS as _ST,
        report_to_registry as _report,
    )

    wanted = tuple(names) if names is not None else STATE_TARGETS
    unknown = set(wanted) - set(TARGETS)
    if unknown:
        raise ValueError(
            f"unknown state target(s) {sorted(unknown)}; valid: "
            f"{sorted(STATE_TARGETS)}")
    findings, errors = run_targets(set(wanted))
    findings = [f for f in findings if f.check in _ST]
    results = {}
    for name in wanted:
        if name in errors:
            continue
        results[name] = (
            [f for f in findings if f.symbol == name],
            dict(STATE_STATS.get(name, {})),
        )
    _report(results, registry=registry)
    stats = {name: s for name, (_, s) in results.items()}
    return findings, errors, stats


@target("memory_llama_o4_step")
def _memory_llama_o4_step():
    """The llama O4 train step through the live-interval lattice: the
    carry is donated (the run loop's real calling convention), so every
    param/moment/fp8-ring buffer earns its donation credit and the
    peak is the transient working set — hold an activation across the
    backward or drop a donation and this target turns red."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.amp import Fp8DelayedScaler
    from apex_tpu.models import llama
    from apex_tpu.optimizers import fused_adam

    cfg = llama.tiny(num_layers=1, num_heads=2, num_kv_heads=1,
                     hidden_size=32, intermediate_size=64,
                     vocab_size=128, max_seq_len=16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tx = fused_adam(lr=1e-3)
    fp8 = Fp8DelayedScaler(["lm_head"], history=4)
    carry = (params, tx.init(params), fp8.init())
    tokens = jnp.zeros((2, 16), jnp.int32)

    def train_step(carry, tokens, targets):
        params, opt_state, fp8_state = carry

        def loss_fn(p):
            logits = llama.forward(p, tokens, cfg, tp_axis=None,
                                   cp_axis=None, ep_axis=None)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                lp, targets[..., None], axis=-1))

        with fp8.step(fp8_state) as ctx:
            loss, grads = ctx.value_and_grad(loss_fn)(params)
        new_fp8 = fp8.update(fp8_state, ctx)
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = jax.tree_util.tree_map(jnp.add, params, updates)
        return (new_params, new_opt, new_fp8), loss

    stats = MEMORY_STATS.setdefault("memory_llama_o4_step", {})
    return analyze_memory(train_step, carry, tokens, tokens,
                          name="memory_llama_o4_step",
                          donate_argnums=(0,), state_argnums=(0,),
                          stats_out=stats)


@target("memory_zero1_fused_adam_step")
def _memory_zero1_fused_adam_step():
    """ZeRO-1 carry step under the liveness walk: the dp-sharded mu/nu
    buckets and params are donated carry, so the interval lattice must
    see their updates land in-place-shaped and charge only the
    reduce-scatter transients against the peak."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.zero import Zero1FusedAdam

    mesh, sizes, owned = _owned_mesh()
    try:
        dp = sizes.get("dp", 1)
        params = {"w": jnp.zeros((256, 256), jnp.bfloat16),
                  "b": jnp.zeros((256,), jnp.bfloat16)}
        opt = Zero1FusedAdam(lr=1e-3, weight_decay=0.01, axis_name="dp",
                             num_shards=dp, bucket_cap_mb=0.1)
        state = opt.init(params)
        grads_of = _ddp_grad_model()

        def step(x, state, params):
            return opt.step(grads_of(x), state, params)

        state_specs = opt.state_specs(params)
        param_specs = {"w": P(), "b": P()}
        fn = jax.shard_map(
            step, mesh=mesh,
            in_specs=(P("dp"), state_specs, param_specs),
            out_specs=(param_specs, state_specs),
            check_vma=False)

        def train_step(carry, x):
            params, ostate = carry
            new_params, new_ostate = fn(x, ostate, params)
            return new_params, new_ostate

        stats = MEMORY_STATS.setdefault("memory_zero1_fused_adam_step",
                                        {})
        return analyze_memory(
            train_step, (params, state),
            jnp.zeros((8 * dp, 256), jnp.float32),
            name="memory_zero1_fused_adam_step",
            donate_argnums=(0,), state_argnums=(0,),
            axis_sizes=sizes, stats_out=stats)
    finally:
        _release_mesh(owned)


@target("memory_ddp_overlap_step")
def _memory_ddp_overlap_step():
    """Overlapped-DDP amp step through the interval lattice: bucketed
    grad allreduce + scaled_update's cond must not hold the full grad
    tree and the bucket slabs live at once past the spike gate, and
    the donated carry (params, flat-adam state, scaler counters)
    collects its credit."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.amp import LossScaler, scaled_update
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.parallel.overlap import sync_gradients_overlapped

    mesh, sizes, owned = _owned_mesh()
    try:
        dp = sizes.get("dp", 1)
        params = {"w": jnp.zeros((256, 256), jnp.float32),
                  "b": jnp.zeros((256,), jnp.float32)}
        tx = fused_adam(lr=1e-3, flat=True)
        scaler = LossScaler()
        carry = (params, tx.init(params), scaler.init())
        grads_of = _ddp_grad_model()

        def inner(x, params, opt_state, sstate):
            grads = sync_gradients_overlapped(
                grads_of(x), axis_name="dp", bucket_cap_mb=0.1)
            updates, new_opt, new_sstate, _ovf = scaled_update(
                tx, scaler, grads, opt_state, params, sstate,
                overflow_reduce_axes=("dp",))
            new_params = jax.tree_util.tree_map(
                jnp.add, params, updates)
            return new_params, new_opt, new_sstate

        fn = jax.shard_map(
            inner, mesh=mesh, in_specs=(P("dp"), P(), P(), P()),
            out_specs=(P(), P(), P()), check_vma=False)

        def train_step(carry, x):
            params, opt_state, sstate = carry
            return fn(x, params, opt_state, sstate)

        stats = MEMORY_STATS.setdefault("memory_ddp_overlap_step", {})
        return analyze_memory(
            train_step, carry,
            jnp.zeros((8 * dp, 256), jnp.float32),
            name="memory_ddp_overlap_step",
            donate_argnums=(0,), state_argnums=(0,),
            axis_sizes=sizes, stats_out=stats)
    finally:
        _release_mesh(owned)


@target("memory_fused_adam_master_sharded")
def _memory_fused_adam_master_sharded():
    """The calibration loop's 3.4x outlier (fused Adam over tp-sharded
    fp32 masters) under the liveness walk, fully donated: grads, state
    AND masters die into their updates, so every slab earns donation
    credit and the modeled peak is the number hbm_priors.json's ratio
    corrects. The grads slot is donated here where the sharding twin
    (fused_adam_master_sharded_step) historically was not — exactly
    the missed-donation pattern the check exists to catch."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.optimizers import fused_adam

    mesh, sizes, owned = _owned_mesh(
        tensor_model_parallel_size_=_tp_size())
    try:
        master = {"w": jnp.zeros((256, 1024), jnp.float32),
                  "b": jnp.zeros((1024,), jnp.float32)}
        tx = fused_adam(lr=1e-3, weight_decay=0.01, flat=False)
        state = tx.init(master)
        grads = jax.tree_util.tree_map(jnp.ones_like, master)

        def step(grads, state, master):
            updates, new_state = tx.update(grads, state, master)
            return optax.apply_updates(master, updates), new_state

        wspec = {"w": P(None, "tp"), "b": P("tp")}
        state_spec = jax.tree_util.tree_map(
            lambda s: (wspec["w"] if getattr(s, "ndim", 0) == 2 else
                       wspec["b"] if getattr(s, "ndim", 0) == 1 else P()),
            state, is_leaf=lambda s: hasattr(s, "shape"))
        with jax.sharding.set_mesh(mesh):
            stats = MEMORY_STATS.setdefault(
                "memory_fused_adam_master_sharded", {})
            return analyze_memory(
                step, grads, state, master,
                in_specs=[wspec, state_spec, wspec],
                donate_argnums=(0, 1, 2), state_argnums=(1,),
                axis_sizes=sizes, stats_out=stats,
                name="memory_fused_adam_master_sharded")
    finally:
        _release_mesh(owned)


MEMORY_TARGETS = (
    "memory_llama_o4_step", "memory_zero1_fused_adam_step",
    "memory_ddp_overlap_step", "memory_fused_adam_master_sharded",
    "memory_serving_decode_step",
)


def run_memory_findings(registry=None, names=None):
    """Run only the memory-liveness targets and publish finding counts
    (zero-filled over every check id) + per-target peak/steady bytes to
    the observability registry (``analysis/memory_findings*`` +
    ``analysis/memory_peak_hbm_bytes`` family) — the hook bench.py
    reports through. Returns (findings, errors, stats)."""
    from apex_tpu.analysis.memory_checks import (
        MEMORY_CHECKS as _MC,
        report_to_registry as _report,
    )

    wanted = tuple(names) if names is not None else MEMORY_TARGETS
    unknown = set(wanted) - set(TARGETS)
    if unknown:
        raise ValueError(
            f"unknown memory target(s) {sorted(unknown)}; valid: "
            f"{sorted(MEMORY_TARGETS)}")
    findings, errors = run_targets(set(wanted))
    findings = [f for f in findings if f.check in _MC]
    results = {}
    for name in wanted:
        if name in errors:
            continue
        results[name] = (
            [f for f in findings if f.symbol == name],
            dict(MEMORY_STATS.get(name, {})),
        )
    _report(results, registry=registry)
    stats = {name: s for name, (_, s) in results.items()}
    return findings, errors, stats


# ------------------------------------------------------- serving targets
#
# The serving decode step (apex_tpu/serving/scheduler.py) as analysis
# targets: the same static-shape step the engine jits, proven through
# the state fixpoint (carried tokens/pages/positions), the memory
# liveness walk (donated page buffers), and — for fleet serving — the
# SPMD audit of the dp-replicated variant. They live in the state/
# memory/spmd family tuples (their checks ARE those families') but
# roll their wall time into the dedicated "serving" engine bucket
# (cli.target_engine checks SERVING_TARGETS first).


def _serving_decode_fixture():
    """Tiny-llama decode-step fixture shared by the serving targets:
    (cfg, params, decode_fn, carry, tables, active) with 2 slots over
    8 pages of 4 tokens (+ trash page), both rows mid-sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models import llama
    from apex_tpu.serving.scheduler import build_decode_step

    cfg = llama.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    page_size, num_pages, batch, maxp = 4, 8, 2, 4
    decode = build_decode_step(cfg, page_size)
    shape = (cfg.num_layers, num_pages + 1, page_size,
             cfg.num_kv_heads, cfg.head_dim)
    carry = (jnp.zeros((batch,), jnp.int32),
             jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype),
             jnp.full((batch,), 5, jnp.int32))
    tables = jnp.asarray(
        np.arange(batch * maxp).reshape(batch, maxp), jnp.int32)
    active = jnp.ones((batch,), bool)

    def serve_step(carry, params, tables, active):
        tokens, k_pages, v_pages, pos = carry
        # tokens ride the carry as they do in the engine: the step
        # before's output, no row fresh
        nxt, k_pages, v_pages, _ = decode(params, {}, k_pages, v_pages,
                                          None, tokens, tables, pos, active,
                                          ~active, tokens)
        return nxt, k_pages, v_pages, pos + 1

    return cfg, params, serve_step, carry, tables, active


@target("state_serving_decode_step")
def _state_serving_decode_step():
    """The serving decode step through the state fixpoint: tokens,
    both page buffers and the position vector are the carry a
    continuous-batching server threads forever — every one must flow
    step-to-step (a dropped page buffer would silently serve from a
    stale cache)."""
    _cfg, params, serve_step, carry, tables, active = \
        _serving_decode_fixture()
    stats = STATE_STATS.setdefault("state_serving_decode_step", {})
    return analyze_state(serve_step, carry, params, tables, active,
                         name="state_serving_decode_step",
                         stats_out=stats)


@target("memory_serving_decode_step")
def _memory_serving_decode_step():
    """The serving decode step through the liveness walk with the
    carry donated — the engine's jit donates both page buffers every
    step, so the lattice must see the scatter updates land
    in-place-shaped and charge only the per-step activations (not a
    second cache) against the peak."""
    _cfg, params, serve_step, carry, tables, active = \
        _serving_decode_fixture()
    stats = MEMORY_STATS.setdefault("memory_serving_decode_step", {})
    return analyze_memory(serve_step, carry, params, tables, active,
                          name="memory_serving_decode_step",
                          donate_argnums=(0,), state_argnums=(0,),
                          stats_out=stats)


@target("spmd_serving_decode_step")
def _spmd_serving_decode_step():
    """Fleet serving: dp-replicated decode shards the slot arrays and
    page buffers over 'dp' (replica-private caches), params
    replicated. There are NO collectives by design — each replica
    serves its own requests — and the SPMD audit is what keeps that
    true (an accidental cross-replica reduction would both corrupt
    tokens and serialize the fleet)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models import llama
    from apex_tpu.serving.scheduler import build_decode_step

    mesh, sizes, owned = _owned_mesh()
    try:
        dp = sizes.get("dp", 1)
        cfg = llama.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        page_size, num_pages, batch, maxp = 4, 8, 2, 4
        decode = build_decode_step(cfg, page_size)

        def local_step(params, k_pages, v_pages, tokens, tables, pos,
                       active):
            return decode(params, {}, k_pages, v_pages, None, tokens,
                          tables, pos, active, ~active, tokens)[:3]

        shape = (cfg.num_layers, dp * (num_pages + 1), page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        k_pages = jnp.zeros(shape, cfg.dtype)
        v_pages = jnp.zeros(shape, cfg.dtype)
        tokens = jnp.zeros((dp * batch,), jnp.int32)
        tables = jnp.asarray(
            np.tile(np.arange(batch * maxp).reshape(batch, maxp),
                    (dp, 1)), jnp.int32)
        pos = jnp.full((dp * batch,), 5, jnp.int32)
        active = jnp.ones((dp * batch,), bool)
        fn = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(None, "dp"), P(None, "dp"), P("dp"),
                      P("dp"), P("dp"), P("dp")),
            out_specs=(P("dp"), P(None, "dp"), P(None, "dp")),
            check_vma=False)
        return _analyze_spmd_target(
            "spmd_serving_decode_step", fn, params, k_pages, v_pages,
            tokens, tables, pos, active, axis_sizes=sizes)
    finally:
        _release_mesh(owned)


# The dedicated wall-time bucket (cli.ENGINE_NAMES "serving"): checked
# FIRST by cli.target_engine, so these names bucket here even though
# they also belong to the state/memory/spmd family tuples above.
SERVING_TARGETS = (
    "state_serving_decode_step", "memory_serving_decode_step",
    "spmd_serving_decode_step",
)
