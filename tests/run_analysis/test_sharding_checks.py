"""Sharding-flow checks: ≥2 seeded regressions per check family plus
the clean-counterpart cases, the registry publisher, and the --diff
CLI mode. Every seeded program is the bug the check exists for — if a
fix regresses the detector, these fail without hardware."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.analysis.sharding_checks import (
    SHARDING_CHECKS,
    analyze_sharding,
)

SIZES = {"dp": 2, "tp": 4}


def _mesh():
    return Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("dp", "tp"))


def _checks(findings, check):
    return [f for f in findings if f.check == check]


# ------------------------------------------------------ implicit-reshard

def test_implicit_reshard_axis_move_at_constraint():
    """Seeded: value arrives sharded over tp on dim 0, constraint wants
    tp on dim 1 — a hidden all-to-all."""
    mesh = _mesh()

    def fn(x):
        return jax.lax.with_sharding_constraint(
            x * 2.0, NamedSharding(mesh, P(None, "tp")))

    f = analyze_sharding(fn, jnp.zeros((64, 64)),
                         in_specs=[P("tp", None)], axis_sizes=SIZES)
    hits = _checks(f, "implicit-reshard")
    assert len(hits) == 1
    assert "all-to-all" in hits[0].message


def test_implicit_reshard_join_conflict():
    """Seeded: two operands of one add carry the same mesh axis on
    different dims — the 'missing with_sharding_constraint' shape."""
    f = analyze_sharding(
        lambda a, b: a + b, jnp.zeros((64, 64)), jnp.zeros((64, 64)),
        in_specs=[P("tp", None), P(None, "tp")], axis_sizes=SIZES)
    hits = _checks(f, "implicit-reshard")
    assert len(hits) == 1
    assert "different dims" in hits[0].message


def test_implicit_reshard_dim_axis_conflict_at_constraint():
    mesh = _mesh()

    def fn(x):
        return jax.lax.with_sharding_constraint(
            x * 2.0, NamedSharding(mesh, P("dp", None)))

    f = analyze_sharding(fn, jnp.zeros((64, 64)),
                         in_specs=[P("tp", None)], axis_sizes=SIZES)
    assert _checks(f, "implicit-reshard")


def test_explicit_gather_constraint_is_not_flagged():
    """Constraining a sharded value to replicated is the documented way
    to ASK for an all-gather (gather_output) — explicitly not a
    finding."""
    mesh = _mesh()

    def fn(x):
        return jax.lax.with_sharding_constraint(
            x * 2.0, NamedSharding(mesh, P(None, None)))

    f = analyze_sharding(fn, jnp.zeros((64, 64)),
                         in_specs=[P("tp", None)], axis_sizes=SIZES)
    assert not _checks(f, "implicit-reshard")


def test_join_conflict_ignores_non_elementwise_ops():
    """An embedding lookup legitimately mixes a tp-sharded table with
    differently-sharded indices — gather/take must not be treated as an
    elementwise join (review-confirmed false positive)."""
    f = analyze_sharding(
        lambda table, idx: jnp.take(table, idx, axis=0),
        jnp.zeros((64, 64)), jnp.zeros((8, 8), jnp.int32),
        in_specs=[P(None, "tp"), P("tp", None)], axis_sizes=SIZES)
    assert not _checks(f, "implicit-reshard")


def test_agreeing_boundary_is_clean():
    mesh = _mesh()

    def fn(x, w):
        y = x @ w
        return jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P("dp", "tp")))

    f = analyze_sharding(fn, jnp.zeros((8, 16)), jnp.zeros((16, 32)),
                         in_specs=[P("dp", None), P(None, "tp")],
                         axis_sizes=SIZES)
    assert not _checks(f, "implicit-reshard")


# ------------------------------------------------------ replicated-large

def test_replicated_large_master_weights():
    """Seeded: fp32 master weights big enough to matter, fully
    replicated although tp divides their dims — the TP master-weight
    smell."""
    master = jnp.zeros((512, 1024), jnp.float32)  # 2 MiB

    def step(m, g):
        return m - 0.1 * g

    f = analyze_sharding(step, master, jnp.zeros_like(master),
                         in_specs=[P(), P(None, "tp")],
                         axis_sizes=SIZES)
    hits = _checks(f, "replicated-large")
    assert len(hits) == 1
    assert "replicated" in hits[0].message


def test_replicated_large_activation_buffer():
    f = analyze_sharding(
        lambda x: jnp.tanh(x), jnp.zeros((2048, 512), jnp.float32),
        in_specs=[P(None, None)], axis_sizes=SIZES)
    assert _checks(f, "replicated-large")


def test_replicated_small_or_sharded_is_clean():
    # below threshold
    f = analyze_sharding(lambda x: x * 2, jnp.zeros((64, 64)),
                         in_specs=[P()], axis_sizes=SIZES)
    assert not _checks(f, "replicated-large")
    # sharded
    f = analyze_sharding(lambda x: x * 2,
                         jnp.zeros((2048, 512), jnp.float32),
                         in_specs=[P(None, "tp")], axis_sizes=SIZES)
    assert not _checks(f, "replicated-large")
    # unknown spec: the engine stays quiet
    f = analyze_sharding(lambda x: x * 2,
                         jnp.zeros((2048, 512), jnp.float32),
                         axis_sizes=SIZES)
    assert not _checks(f, "replicated-large")


def test_replicated_large_threshold_knob():
    f = analyze_sharding(lambda x: x * 2, jnp.zeros((64, 64)),
                         in_specs=[P()], axis_sizes=SIZES,
                         replicated_threshold_bytes=1024)
    assert _checks(f, "replicated-large")


# --------------------------------------------------------- psum-scatter

def test_psum_scatter_raw_pattern():
    """Seeded: psum immediately sliced to this rank's chunk — the
    hand-rolled reduce-scatter."""
    mesh = _mesh()

    def body(x):
        y = jax.lax.psum(x, "tp")
        r = jax.lax.axis_index("tp")
        return jax.lax.dynamic_slice_in_dim(y, r * 4, 4, axis=0)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(None, "tp"),
                       out_specs=P("tp"), check_vma=False)
    f = analyze_sharding(fn, jnp.zeros((16, 16)), axis_sizes=SIZES)
    hits = _checks(f, "psum-scatter")
    assert len(hits) == 1
    assert "psum_scatter" in hits[0].message


def test_psum_scatter_via_mappings_composition():
    """Seeded: reduce_from + scatter_to region composition — the
    mappings-level spelling of the same bug (a row-parallel output
    immediately re-scattered should be reduce_scatter instead)."""
    from apex_tpu.transformer.tensor_parallel.mappings import (
        reduce_from_tensor_model_parallel_region,
        scatter_to_tensor_model_parallel_region,
    )

    mesh = _mesh()

    def body(x):
        y = reduce_from_tensor_model_parallel_region(x, "tp")
        return scatter_to_tensor_model_parallel_region(y, "tp")

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(None, "tp"),
                       out_specs=P(None, "tp"), check_vma=False)
    f = analyze_sharding(fn, jnp.zeros((16, 16)), axis_sizes=SIZES)
    assert _checks(f, "psum-scatter")


def test_psum_scatter_clean_when_scattered_properly():
    """The one-call fix the check points at: the fused last-dim
    reduce-scatter region (and its sequence-parallel sibling) trace
    clean."""
    from apex_tpu.transformer.tensor_parallel.mappings import (
        reduce_scatter_to_tensor_model_parallel_region,
    )

    mesh = _mesh()

    def body(x):
        return reduce_scatter_to_tensor_model_parallel_region(x, "tp")

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(None, "tp"),
                       out_specs=P(None, "tp"), check_vma=False)
    f = analyze_sharding(fn, jnp.zeros((16, 16)), axis_sizes=SIZES)
    assert not _checks(f, "psum-scatter")
    # slicing something that is NOT a psum result is also clean
    def body2(x):
        r = jax.lax.axis_index("tp")
        return jax.lax.dynamic_slice_in_dim(x, r * 4, 4, axis=0)

    fn2 = jax.shard_map(body2, mesh=_mesh(), in_specs=P(None, "tp"),
                        out_specs=P("tp"), check_vma=False)
    f = analyze_sharding(fn2, jnp.zeros((16, 16)), axis_sizes=SIZES)
    assert not _checks(f, "psum-scatter")


# ------------------------------------------------------- dead-collective

def test_dead_collective_psum_of_ones_probe():
    """Seeded: the pre-fix parallel/distributed.py axis-size probe —
    psum(jnp.ones(())) emits a real collective for a compile-time
    constant."""
    mesh = _mesh()

    def body(g):
        g = jax.lax.psum(g, "dp")
        n = jax.lax.psum(jnp.ones((), g.dtype), "dp")  # the bug
        return g / n

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"), check_vma=False)
    f = analyze_sharding(fn, jnp.zeros((16, 8)), axis_sizes=SIZES)
    hits = _checks(f, "dead-collective")
    assert len(hits) == 1
    assert "axis_size" in hits[0].message


def test_dead_collective_all_gather_of_replicated():
    mesh = _mesh()

    def body(x, table):
        # table arrives replicated (P() in_spec) — gathering it moves
        # n-1 copies of data every rank already has
        t = jax.lax.all_gather(table, "tp", axis=0, tiled=True)
        return x + jnp.sum(t)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(None, "tp"), P()),
                       out_specs=P(None, "tp"), check_vma=False)
    f = analyze_sharding(fn, jnp.zeros((8, 16)), jnp.zeros((4, 4)),
                         axis_sizes=SIZES)
    assert _checks(f, "dead-collective")


def test_dead_collective_clean_on_varying_data():
    mesh = _mesh()

    def body(g):
        return jax.lax.psum(g, "dp")

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                       out_specs=P(), check_vma=False)
    f = analyze_sharding(fn, jnp.zeros((16, 8)), axis_sizes=SIZES)
    assert not _checks(f, "dead-collective")


def test_dead_collective_tree_psum_judged_leaf_by_leaf():
    """A psum of a pytree is one collective per leaf, so each is judged
    on its own operand: in (ones, x) the constant leaf is dead and the
    varying one is not — in either order."""
    mesh = _mesh()

    for tree in (lambda x: (jnp.ones(()), x), lambda x: (x, jnp.ones(()))):
        def body(x, tree=tree):
            a, b = jax.lax.psum(tree(x), "dp")
            return x + a * 0 + b * 0

        fn = jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp"), check_vma=False)
        f = analyze_sharding(fn, jnp.zeros((16, 8)), axis_sizes=SIZES)
        assert len(_checks(f, "dead-collective")) == 1

    def body_all_const(x):
        a, b = jax.lax.psum((jnp.ones(()), jnp.full((), 2.0)), "dp")
        return x + a * 0 + b * 0

    fn = jax.shard_map(body_all_const, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"), check_vma=False)
    f = analyze_sharding(fn, jnp.zeros((16, 8)), axis_sizes=SIZES)
    assert _checks(f, "dead-collective")


def test_fixed_ddp_sync_is_clean():
    """The committed fix: sync_gradients / sync_gradients_flat now use
    the static axis size — reverting them to psum(ones) fails
    test_dead_collective_psum_of_ones_probe's pattern via the
    registered ddp target too."""
    from apex_tpu.parallel.distributed import (
        sync_gradients,
        sync_gradients_flat,
    )

    mesh = _mesh()

    def step(grads):
        flat = sync_gradients_flat(grads, axis_name="dp")
        plain = sync_gradients(grads, axis_name="dp",
                               gradient_predivide_factor=2.0)
        return jax.tree_util.tree_map(jnp.add, flat, plain)

    spec = {"w": P("dp"), "b": P("dp")}
    fn = jax.shard_map(step, mesh=mesh, in_specs=(spec,),
                       out_specs=spec)
    f = analyze_sharding(fn, {"w": jnp.zeros((64, 8)),
                              "b": jnp.zeros((8,))}, axis_sizes=SIZES)
    assert not _checks(f, "dead-collective")


# ----------------------------------------------------------- hbm-budget

def test_hbm_budget_fires_on_big_live_set():
    def fn(a):
        b = a @ a
        c = b @ b
        return jnp.sum(c)

    f = analyze_sharding(fn, jnp.zeros((512, 512)),
                         in_specs=[P()], axis_sizes=SIZES,
                         hbm_budget_bytes=1 << 20,
                         replicated_threshold_bytes=1 << 30)
    hits = _checks(f, "hbm-budget")
    assert len(hits) == 1
    assert "budget" in hits[0].message


def test_hbm_budget_donation_credit_saves_the_step():
    """Seeded pair: the same update passes the budget only when the old
    state is donated — the liveness credit the check exists to model."""
    state = jnp.zeros((512, 512))  # 1 MiB

    def update(s, g):
        return s * 0.9 + g

    # kept: s and g are caller-owned for the whole step -> peak 4 MiB
    # (s, g, s*0.9, out). donated: s dies after the multiply, g after
    # the add -> peak 3 MiB. The budget sits between the two.
    budget = int(3.5 * (1 << 20))
    common = dict(in_specs=[P(), P()], axis_sizes=SIZES,
                  hbm_budget_bytes=budget,
                  replicated_threshold_bytes=1 << 30)
    f_kept = analyze_sharding(update, state, jnp.zeros_like(state),
                              **common)
    f_donated = analyze_sharding(update, state, jnp.zeros_like(state),
                                 donate_argnums=(0, 1), **common)
    assert _checks(f_kept, "hbm-budget")
    assert not _checks(f_donated, "hbm-budget")


def test_hbm_budget_respects_sharding():
    """tp-sharding the tensors divides the local live set 4x."""
    def fn(a):
        return jnp.tanh(a) * 2.0

    x = jnp.zeros((1024, 1024))  # 4 MiB global
    budget = 3 << 20
    f = analyze_sharding(fn, x, in_specs=[P(None, "tp")],
                         axis_sizes=SIZES, hbm_budget_bytes=budget)
    assert not _checks(f, "hbm-budget")
    f = analyze_sharding(fn, x, in_specs=[P()], axis_sizes=SIZES,
                         hbm_budget_bytes=budget,
                         replicated_threshold_bytes=1 << 30)
    assert _checks(f, "hbm-budget")


def test_hbm_budget_env_knob(monkeypatch):
    from apex_tpu.ops.pallas_config import device_hbm_bytes

    monkeypatch.setenv("APEX_TPU_HBM_BYTES", "12345")
    assert device_hbm_bytes() == 12345
    monkeypatch.setenv("APEX_TPU_HBM_BYTES", "not-a-number")
    with pytest.raises(ValueError, match="APEX_TPU_HBM_BYTES"):
        device_hbm_bytes()
    monkeypatch.delenv("APEX_TPU_HBM_BYTES")
    assert device_hbm_bytes() >= 1 << 30


# ------------------------------------------------- plumbing / registry

def test_unknown_check_id_rejected():
    with pytest.raises(ValueError, match="unknown sharding check"):
        analyze_sharding(lambda x: x, jnp.zeros((2,)),
                         checks=["implicit-reshrad"])


def test_stats_out_filled_even_when_clean():
    stats = {}
    f = analyze_sharding(lambda x: x * 2, jnp.zeros((8, 8)),
                         in_specs=[P()], axis_sizes=SIZES,
                         stats_out=stats)
    assert not f
    assert stats["peak_hbm_bytes"] > 0
    assert "comms_bytes" in stats


def test_run_sharding_findings_publishes_family():
    from apex_tpu.analysis import run_sharding_findings
    from apex_tpu.observability import MetricRegistry

    reg = MetricRegistry()
    findings, errors, stats = run_sharding_findings(
        registry=reg, names=("ddp_bucket_allreduce_step",
                             "tp_column_parallel_fwd_bwd"))
    assert not errors, errors
    assert not findings, [f.render() for f in findings]
    records = reg.to_records()
    names = {r.get("name") for r in records}
    assert "analysis/sharding_findings_total" in names
    assert "analysis/sharding_comms_bytes" in names
    assert "analysis/sharding_peak_hbm_bytes" in names
    by_target = {r["labels"]["target"] for r in records
                 if r.get("name") == "analysis/sharding_comms_bytes"}
    assert by_target == {"ddp_bucket_allreduce_step",
                         "tp_column_parallel_fwd_bwd"}
    assert stats["ddp_bucket_allreduce_step"]["comms_bytes"] > 0


def test_all_sharding_targets_trace_clean():
    """The tier-1 contract: every registered sharding target runs and
    reports 0 findings (the gate the ISSUE acceptance names) — the two
    ISSUE 11 comms-engine targets included."""
    from apex_tpu.analysis import run_sharding_findings

    findings, errors, stats = run_sharding_findings(registry=None)
    assert not errors, errors
    assert not findings, [f.render() for f in findings]
    assert len(stats) >= 8
    # the comms estimates are the evidence bench.py ships: the
    # collective-bearing targets must report real bytes
    assert stats["ddp_bucket_allreduce_step"]["comms_bytes"] > 0
    assert stats["moe_dispatch"]["comms_bytes"] > 0
    assert stats["tp_row_parallel_fwd_bwd"]["comms_bytes"] > 0
    assert stats["ddp_overlap_bucket_step"]["comms_bytes"] > 0
    assert stats["zero1_fused_adam_step"]["comms_bytes"] > 0


def test_zero1_step_priced_at_most_three_quarters_of_allreduce():
    """ISSUE 11 acceptance: the sharding-flow estimator prices the
    ZeRO-1 step's dp comms at <= 0.75x the overlapped-allreduce
    target's bytes (fp32 reduce-scatter + bf16 param all-gather vs
    the fp32 allreduce), with both targets at 0 findings."""
    from apex_tpu.analysis import run_sharding_findings

    findings, errors, stats = run_sharding_findings(
        registry=None, names=("ddp_overlap_bucket_step",
                              "zero1_fused_adam_step"))
    assert not errors, errors
    assert not findings, [f.render() for f in findings]
    allreduce = stats["ddp_overlap_bucket_step"]["comms_bytes"]
    zero1 = stats["zero1_fused_adam_step"]["comms_bytes"]
    assert allreduce > 0
    assert zero1 * 4 <= allreduce * 3, (
        f"zero1 {zero1} B > 0.75x allreduce {allreduce} B")


# -------------------------------------------------------------- --diff
# (in-process cli.main: each `python -m` subprocess costs ~8s of jax
# import against the tier-1 870s budget)

def _run_main(args, capsys):
    from apex_tpu.analysis import cli

    rc = cli.main(list(args))
    captured = capsys.readouterr()
    return rc, captured.out, captured.err


def test_diff_mode_fails_only_on_new(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import time, jax\n"
        "def t(fn, x):\n"
        "    t0 = time.perf_counter()\n"
        "    jax.block_until_ready(fn(x))\n"
        "    return time.perf_counter() - t0\n")
    base_args = ["--no-jaxpr", "--root", str(tmp_path), str(bad)]
    rc, out, err = _run_main(base_args + ["--json"], capsys)
    assert rc == 1
    base = tmp_path / "base.json"
    base.write_text(out)
    # same findings vs the stored run: nothing new, exit 0
    rc, out, err = _run_main(base_args + ["--diff", str(base)], capsys)
    assert rc == 0, (out, err)
    assert "1 grandfathered" in err
    # a second, NEW violation still fails
    bad.write_text(bad.read_text().replace(
        "    return time.perf_counter() - t0\n",
        "    import random\n"
        "    t1 = time.perf_counter()\n"
        "    jax.block_until_ready(fn(x))\n"
        "    return t1 - t0\n"))
    rc, out, err = _run_main(base_args + ["--diff", str(base)], capsys)
    assert rc == 1, (out, err)


def test_diff_composes_with_baseline_by_max_not_sum(tmp_path, capsys):
    """A finding present in BOTH bases must not double its grandfather
    budget: a second, genuinely new occurrence of the same key still
    fails the gate."""
    from apex_tpu.analysis.findings import save_baseline, Finding

    one = ("import time, jax\n"
           "def t(fn, x):\n"
           "    t0 = time.perf_counter()\n"
           "    jax.block_until_ready(fn(x))\n"
           "    return time.perf_counter() - t0\n")
    bad = tmp_path / "bad.py"
    bad.write_text(one)
    base_args = ["--no-jaxpr", "--root", str(tmp_path), str(bad)]
    rc, out, _err = _run_main(base_args + ["--json"], capsys)
    assert rc == 1
    diff_base = tmp_path / "diff_base.json"
    diff_base.write_text(out)
    finding = json.loads(out)["findings"][0]
    # the dump carries extra derived keys (e.g. the rename-fix
    # fingerprint) next to the Finding fields — keep only the latter
    finding = {k: v for k, v in finding.items()
               if k in Finding.__dataclass_fields__}
    baseline = tmp_path / "baseline.json"
    save_baseline(str(baseline), [Finding(**finding)])
    # one occurrence, covered by both bases: clean
    rc, _out, _err = _run_main(
        base_args + ["--baseline", str(baseline),
                     "--diff", str(diff_base)], capsys)
    assert rc == 0
    # a SECOND occurrence of the same key must still fail (sum
    # semantics would grant it a budget of 2)
    bad.write_text(one.replace(
        "    return time.perf_counter() - t0\n",
        "    t1 = time.perf_counter()\n"
        "    jax.block_until_ready(fn(x))\n"
        "    return t1 - t0\n"))
    rc, _out, _err = _run_main(
        base_args + ["--baseline", str(baseline),
                     "--diff", str(diff_base)], capsys)
    assert rc == 1


def test_diff_mode_rejects_unknown_schema(tmp_path, capsys):
    base = tmp_path / "base.json"
    base.write_text(json.dumps({
        "schema_version": 99, "kind": "apex_tpu.analysis",
        "findings": []}))
    # a bad base fails fast — before any target traces
    rc, _out, err = _run_main(["--no-ast", "--diff", str(base)], capsys)
    assert rc == 2
    assert "schema_version 99" in err


def test_diff_mode_rejects_non_report(tmp_path, capsys):
    base = tmp_path / "base.json"
    base.write_text(json.dumps({"grandfathered": {}}))
    rc, _out, err = _run_main(["--no-ast", "--diff", str(base)], capsys)
    assert rc == 2
    assert "kind" in err


def test_run_sharding_findings_rejects_unknown_target():
    from apex_tpu.analysis import run_sharding_findings

    with pytest.raises(ValueError, match="unknown sharding target"):
        run_sharding_findings(names=("tp_colunm_parallel_fwd_bwd",))


def test_sharding_checks_listed():
    from apex_tpu.analysis.cli import known_checks

    assert set(SHARDING_CHECKS) <= known_checks()
