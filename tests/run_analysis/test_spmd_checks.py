"""SPMD rank-consistency checks + nondeterministic-collective-order
AST lint (ISSUE 14).

The CI contract the tentpole names: every seeded regression — the
divergent-cond collective, the PR 11 one-rank-desync chaos pattern
caught STATICALLY, the uncoordinated RNG pair, the unanchored host
effect, the unsorted bucket loop — is caught here in tier-1, the
registered spmd targets stay at 0 findings (incl. the fleet-probe-armed
grad sync), and the AST check holds the live tree at 0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.analysis.ast_checks import lint_paths, lint_source
from apex_tpu.analysis.spmd_checks import SPMD_CHECKS, analyze_spmd
from apex_tpu.analysis.targets import (
    SPMD_TARGETS,
    run_spmd_findings,
    run_targets,
)

from jax import shard_map

from jax.sharding import Mesh, PartitionSpec as P


def _mesh(n=8, axis="dp"):
    return Mesh(np.asarray(jax.devices()[:n]), (axis,))


def _checks(findings):
    return sorted({f.check for f in findings})


def _grads_of(x):
    return {"w": (x.T @ x).astype(jnp.float32), "b": jnp.sum(x, axis=0)}


# ----------------------------------- collective-in-divergent-control


class TestDivergentControl:
    def test_seeded_divergent_cond_collective_caught(self):
        """The acceptance-named seeded regression: a psum issued only
        on ranks whose axis_index clears a threshold — half the fleet
        arrives, the other half never does."""

        def bad(x):
            r = jax.lax.axis_index("dp")
            return jax.lax.cond(
                r > 2, lambda v: jax.lax.psum(v, "dp"), lambda v: v, x)

        fn = shard_map(bad, mesh=_mesh(), in_specs=(P("dp"),),
                       out_specs=P("dp"), check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((8, 4)), name="bad_cond")
        assert _checks(found) == ["collective-in-divergent-control"]
        assert "deadlock" in found[0].message

    def test_seeded_divergent_while_collective_caught(self):
        """Same hazard through a while loop: the trip COUNT differs per
        rank, so ranks issue different numbers of psums."""

        def bad(x):
            r = jax.lax.axis_index("dp")

            def cond(carry):
                i, _ = carry
                return i < r

            def body(carry):
                i, v = carry
                return i + 1, jax.lax.psum(v, "dp")

            return jax.lax.while_loop(cond, body, (0, x))[1]

        fn = shard_map(bad, mesh=_mesh(), in_specs=(P("dp"),),
                       out_specs=P("dp"), check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((8, 4)), name="bad_while",
                             checks=("collective-in-divergent-control",))
        assert _checks(found) == ["collective-in-divergent-control"]

    def test_carry_divergent_while_predicate_caught(self):
        """Review regression: the predicate only becomes rank-divergent
        THROUGH the loop carry (per-rank early exit) — the divergence
        judgment must run on the warmed carries, not the initial
        (replicated) values."""

        def bad(x):
            def cond(carry):
                flag, _ = carry
                return flag < 10

            def body(carry):
                flag, v = carry
                # the carry picks up rank-distinctness on iteration 1
                flag = flag + jax.lax.axis_index("dp")
                return flag, jax.lax.psum(v, "dp")

            return jax.lax.while_loop(cond, body, (0, x))[1]

        fn = shard_map(bad, mesh=_mesh(), in_specs=(P("dp"),),
                       out_specs=P("dp"), check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((8, 4)), name="carry_while",
                             checks=("collective-in-divergent-control",))
        assert _checks(found) == ["collective-in-divergent-control"]

    def test_rank_invariant_predicate_clean(self):
        """A predicate REDUCED before branching (every rank agrees) is
        the sanctioned shape — the amp overflow-skip cond."""

        def good(x):
            flag = jax.lax.pmax(jnp.max(x), "dp") > 100.0
            return jax.lax.cond(
                flag, lambda v: jax.lax.psum(v, "dp"), lambda v: v, x)

        fn = shard_map(good, mesh=_mesh(), in_specs=(P("dp"),),
                       out_specs=P("dp"), check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((8, 4)), name="good_cond",
                             checks=("collective-in-divergent-control",))
        assert found == []

    def test_collective_on_other_axis_clean(self):
        """A predicate divergent over 'dp' does not endanger a 'tp'
        collective: within one tp group the dp coordinate is fixed, so
        every member agrees about the branch."""
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                    ("dp", "tp"))

        def fn_body(x):
            r = jax.lax.axis_index("dp")
            return jax.lax.cond(
                r > 1, lambda v: jax.lax.psum(v, "tp"), lambda v: v, x)

        fn = shard_map(fn_body, mesh=mesh, in_specs=(P(("dp", "tp")),),
                       out_specs=P(("dp", "tp")), check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((8, 4)), name="tp_in_dp_cond",
                             checks=("collective-in-divergent-control",))
        assert found == []


# ------------------------------------------- rank-divergent-update


class TestRankDivergentUpdate:
    def test_seeded_one_rank_desync_caught(self):
        """The PR 11 chaos pattern, caught statically: rank 5 (and only
        rank 5) perturbs the params, which the out_specs then claim are
        replicated — the fingerprint desync before it happens."""

        def bad(params, x):
            g = jax.lax.pmean(x.sum(axis=0), "dp")
            r = jax.lax.axis_index("dp")
            poisoned = params + jnp.where(r == 5, 1e-3, 0.0)
            return poisoned - 0.1 * g

        fn = shard_map(bad, mesh=_mesh(), in_specs=(P(), P("dp")),
                       out_specs=P(), check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((4,)), jnp.zeros((16, 4)),
                             name="one_rank_desync")
        assert _checks(found) == ["rank-divergent-update"]
        assert "axis_index" in found[0].message

    def test_seeded_missing_grad_reduce_caught(self):
        """Per-rank gradients stored into replicated params with no
        psum on the path — the plain missing-allreduce bug."""

        def bad(params, x):
            g = x.sum(axis=0)  # local grads, never reduced
            return params - 0.1 * g

        fn = shard_map(bad, mesh=_mesh(), in_specs=(P(), P("dp")),
                       out_specs=P(), check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((4,)), jnp.zeros((16, 4)),
                             name="missing_reduce")
        assert _checks(found) == ["rank-divergent-update"]
        assert "axis_index" not in found[0].message

    def test_reduced_update_clean(self):
        def good(params, x):
            g = jax.lax.pmean(x.sum(axis=0), "dp")
            return params - 0.1 * g

        fn = shard_map(good, mesh=_mesh(), in_specs=(P(), P("dp")),
                       out_specs=P(), check_vma=False)
        assert analyze_spmd(fn, jnp.zeros((4,)), jnp.zeros((16, 4)),
                            name="good_update") == []

    def test_sharded_out_specs_declare_the_divergence(self):
        """Per-rank state exiting through P('dp') out_specs is the
        declared ZeRO shape, not a desync."""

        def good(x):
            return x.sum(axis=0)  # stays per-rank

        fn = shard_map(good, mesh=_mesh(), in_specs=(P("dp"),),
                       out_specs=P("dp"), check_vma=False)
        assert analyze_spmd(fn, jnp.zeros((16, 4)),
                            name="sharded_out") == []

    def test_size_one_axes_never_divergent(self):
        """Review regression: on a degenerate (1-device) mesh every
        axis has one rank — axis_index is the constant 0 and sharded
        data has one shard, so NOTHING can diverge. Findings must not
        depend on the host device count a mesh was built over."""
        mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("dp",))

        def body(params, x):
            g = x.sum(axis=0)  # "unreduced" — but there is one rank
            r = jax.lax.axis_index("dp")
            return params + jnp.where(r == 5, 1e-3, 0.0) - 0.1 * g

        fn = shard_map(body, mesh=mesh1, in_specs=(P(), P("dp")),
                       out_specs=P(), check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((4,)), jnp.zeros((16, 4)),
                             name="one_device")
        assert found == []

    def test_declared_replicated_outs_without_shard_map(self):
        """The GSPMD-world form: no shard_map boundary, the caller
        declares which outputs must be rank-invariant."""

        def step(params, g):
            return params - 0.1 * g, g

        found = analyze_spmd(
            step, jnp.zeros((4,)), jnp.zeros((4,)),
            in_distinct={1: ("dp",)}, replicated_outs=(0,),
            axis_sizes={"dp": 8}, name="declared")
        assert _checks(found) == ["rank-divergent-update"]
        # allowed-axes form: the same divergence, declared sharded
        found = analyze_spmd(
            step, jnp.zeros((4,)), jnp.zeros((4,)),
            in_distinct={1: ("dp",)}, replicated_outs={0: ("dp",)},
            axis_sizes={"dp": 8}, name="declared_ok")
        assert found == []


# ------------------------------------------------ uncoordinated-rng


class TestUncoordinatedRng:
    def test_seeded_shared_stream_on_sharded_data_caught(self):
        """Every rank draws the SAME normal sample and applies it to
        its own shard — correlated noise that should be independent."""

        def bad(key, x):
            return x + jax.random.normal(key, x.shape)

        fn = shard_map(bad, mesh=_mesh(), in_specs=(P(), P("dp")),
                       out_specs=P("dp"), check_vma=False)
        found = analyze_spmd(fn, jax.random.PRNGKey(0),
                             jnp.zeros((16, 4)), name="shared_stream")
        assert _checks(found) == ["uncoordinated-rng"]
        assert found[0].severity == "warning"
        assert "fold" in found[0].message

    def test_seeded_rank_noise_on_replicated_state_caught(self):
        """The converse: rank-folded randomness reaching a store the
        out_specs claim replicated — per-rank noise desyncs params."""

        def bad(params, key):
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
            return params + 0.01 * jax.random.normal(key, params.shape)

        fn = shard_map(bad, mesh=_mesh(), in_specs=(P(), P()),
                       out_specs=P(), check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((4,)),
                             jax.random.PRNGKey(0), name="rank_noise")
        assert _checks(found) == ["uncoordinated-rng"]
        assert found[0].severity == "error"

    def test_rank_folded_stream_on_sharded_path_clean(self):
        """fold_in(key, axis_index) + per-rank output: the coordinated
        dropout idiom — the integer key fold must NOT read as a
        shared-stream join."""

        def good(key, x):
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
            return x + jax.random.normal(key, x.shape)

        fn = shard_map(good, mesh=_mesh(), in_specs=(P(), P("dp")),
                       out_specs=P("dp"), check_vma=False)
        assert analyze_spmd(fn, jax.random.PRNGKey(0),
                            jnp.zeros((16, 4)), name="good_rng") == []

    def test_checks_filter_routes_rng_form_correctly(self):
        """Review regression: the RNG-divergent replicated store must
        fire under checks=['uncoordinated-rng'] (the documented home
        of pattern (a)), and degrade to the generic
        rank-divergent-update when only THAT check is requested — a
        caller's checks= filter may never return a check id it
        excluded, nor silently skip the hazard."""

        def bad(params, key):
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
            return params + 0.01 * jax.random.normal(key, params.shape)

        fn = shard_map(bad, mesh=_mesh(), in_specs=(P(), P()),
                       out_specs=P(), check_vma=False)
        args = (jnp.zeros((4,)), jax.random.PRNGKey(0))
        only_rng = analyze_spmd(fn, *args, name="route_rng",
                                checks=("uncoordinated-rng",))
        assert _checks(only_rng) == ["uncoordinated-rng"]
        only_update = analyze_spmd(fn, *args, name="route_upd",
                                   checks=("rank-divergent-update",))
        assert _checks(only_update) == ["rank-divergent-update"]

    def test_reduced_noise_to_replicated_state_clean(self):
        """Per-rank noise pmean'd before the store is coordinated."""

        def good(params, key):
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
            noise = jax.random.normal(key, params.shape)
            return params + jax.lax.pmean(noise, "dp")

        fn = shard_map(good, mesh=_mesh(), in_specs=(P(), P()),
                       out_specs=P(), check_vma=False)
        assert analyze_spmd(fn, jnp.zeros((4,)),
                            jax.random.PRNGKey(0),
                            name="reduced_noise") == []


# -------------------------------------------- unordered-host-effect


class TestUnorderedHostEffect:
    def test_seeded_unanchored_debug_callback_caught(self):
        def bad(x):
            g = _grads_of(x)
            w = jax.lax.psum(g["w"], "dp")
            jax.debug.callback(lambda v: None, g["b"])  # unanchored
            b = jax.lax.psum(g["b"], "dp")
            return {"w": w, "b": b}

        fn = shard_map(bad, mesh=_mesh(), in_specs=(P("dp"),),
                       out_specs={"w": P(), "b": P()}, check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((64, 16)), name="bad_dbg",
                             checks=("unordered-host-effect",))
        assert _checks(found) == ["unordered-host-effect"]

    def test_seeded_unanchored_io_callback_caught(self):
        from jax.experimental import io_callback

        def bad(x):
            g = _grads_of(x)
            w = jax.lax.psum(g["w"], "dp")
            io_callback(lambda: np.int32(0),
                        jax.ShapeDtypeStruct((), jnp.int32),
                        ordered=False)
            b = jax.lax.psum(g["b"], "dp")
            return {"w": w, "b": b}

        fn = shard_map(bad, mesh=_mesh(), in_specs=(P("dp"),),
                       out_specs={"w": P(), "b": P()}, check_vma=False)
        found = analyze_spmd(fn, jnp.zeros((64, 16)), name="bad_io",
                             checks=("unordered-host-effect",))
        assert _checks(found) == ["unordered-host-effect"]

    def test_result_anchored_callback_clean(self):
        """A callback FED a collective's result is ordered against it —
        the fleet probe's exit shape."""

        def good(x):
            g = _grads_of(x)
            w = jax.lax.psum(g["w"], "dp")
            jax.debug.callback(lambda v: None, w.ravel()[0])
            b = jax.lax.psum(g["b"], "dp")
            return {"w": w, "b": b}

        fn = shard_map(good, mesh=_mesh(), in_specs=(P("dp"),),
                       out_specs={"w": P(), "b": P()}, check_vma=False)
        assert analyze_spmd(fn, jnp.zeros((64, 16)), name="good_dbg",
                            checks=("unordered-host-effect",)) == []

    def test_fleet_probe_sites_pass(self):
        """The acceptance clause: the PR 11 barrier-wait probe's own
        call sites (io_callback token barrier-tied INTO the psum
        operand, exit callback fed the reduced result) analyze clean."""
        from apex_tpu.observability.fleet import probe
        from apex_tpu.parallel.overlap import sync_gradients_overlapped

        was = probe._ENABLED
        probe.enable()
        try:
            def step(x):
                return sync_gradients_overlapped(
                    _grads_of(x), axis_name="dp", bucket_cap_mb=0.1)

            fn = shard_map(step, mesh=_mesh(), in_specs=(P("dp"),),
                           out_specs={"w": P(), "b": P()},
                           check_vma=False)
            stats = {}
            # 256-wide grads split into >1 bucket at the 0.1 MB cap,
            # so the probe brackets a multi-collective chain
            found = analyze_spmd(fn, jnp.zeros((64, 256)),
                                 name="probe_sync", stats_out=stats)
            assert found == []
            # the probe really was armed (callbacks in the trace)
            assert stats["host_effects"] >= 2
            assert stats["collectives"] >= 2
        finally:
            probe._ENABLED = was


# --------------------------------------------------- entry contract


class TestEntry:
    def test_unknown_check_id_loud(self):
        with pytest.raises(ValueError, match="unknown spmd check"):
            analyze_spmd(lambda x: x, jnp.zeros(()), checks=("nope",))

    def test_stats_populated_without_findings(self):
        def fn(x):
            return jax.lax.psum(x, "dp")

        wrapped = shard_map(fn, mesh=_mesh(), in_specs=(P("dp"),),
                            out_specs=P(), check_vma=False)
        stats = {}
        analyze_spmd(wrapped, jnp.zeros((8, 4)), name="s",
                     stats_out=stats)
        assert stats == {"collectives": 1, "host_effects": 0}


class TestRegisteredTargets:
    def test_spmd_targets_zero_findings(self):
        findings, errors = run_targets(set(SPMD_TARGETS))
        assert errors == {}
        assert findings == []

    def test_run_spmd_findings_publishes_metrics(self):
        from apex_tpu.observability.registry import MetricRegistry

        reg = MetricRegistry()
        findings, errors, stats = run_spmd_findings(registry=reg)
        assert errors == {}
        assert findings == []
        assert set(stats) == set(SPMD_TARGETS)
        # every real schedule in the gate actually issues collectives
        assert all(s["collectives"] > 0 for s in stats.values())
        # the probe-armed target carries host effects
        assert stats["spmd_fleet_probe_grad_sync"]["host_effects"] > 0
        records = reg.to_records()
        names = {r["name"] for r in records}
        assert "analysis/spmd_findings_total" in names
        assert "analysis/spmd_collectives" in names

    def test_unknown_target_loud(self):
        with pytest.raises(ValueError, match="unknown spmd target"):
            run_spmd_findings(names=("nope",))

    def test_check_ids_registered(self):
        from apex_tpu.analysis.cli import known_checks

        for cid in SPMD_CHECKS:
            assert cid in known_checks()
        assert "nondeterministic-collective-order" in known_checks()


# ----------------------------- nondeterministic-collective-order (AST)


_NONDET_SRC = """
import os
import jax

def sync_buckets(leaves, sizes):
    for dt in {l.dtype for l in leaves}:
        red = jax.lax.psum(leaves[0], "dp")
    for f in os.listdir("plans"):
        buckets.append(f)
    for dt in set(sizes):
        plan = plan_buckets(sizes[dt], 1 << 20)
    for dt in sorted({l.dtype for l in leaves}):
        ok = jax.lax.psum(leaves[0], "dp")
    for dt in {l.dtype for l in leaves}:
        harmless = dt  # no comms / buckets in this body
"""


class TestNondetCollectiveOrderLint:
    def test_seeded_unsorted_iterations_caught(self):
        found = lint_source(
            _NONDET_SRC, "apex_tpu/parallel/foo.py",
            abspath="/repo/apex_tpu/parallel/foo.py")
        hits = [f for f in found
                if f.check == "nondeterministic-collective-order"]
        # set-comp + listdir + set() call; sorted() and the
        # comms-free body stay quiet
        assert [f.line for f in hits] == [6, 8, 10]

    def test_runtime_and_distributed_ground_covered(self):
        for rel in ("apex_tpu/runtime/foo.py",
                    "apex_tpu/distributed/foo.py"):
            found = lint_source(_NONDET_SRC, rel, abspath=f"/r/{rel}")
            assert any(f.check == "nondeterministic-collective-order"
                       for f in found), rel

    def test_out_of_scope_paths_exempt(self):
        for rel in ("apex_tpu/ops/foo.py", "examples/foo.py",
                    "bench.py"):
            found = lint_source(_NONDET_SRC, rel, abspath=f"/r/{rel}")
            assert not any(
                f.check == "nondeterministic-collective-order"
                for f in found), rel

    def test_suppression_comment_respected(self):
        src = ("def f(leaves):\n"
               "    # apex-lint: disable=nondeterministic-collective-order\n"
               "    for dt in {l.dtype for l in leaves}:\n"
               "        red = jax.lax.psum(leaves[0], 'dp')\n")
        found = lint_source(src, "apex_tpu/parallel/foo.py",
                            abspath="/r/apex_tpu/parallel/foo.py")
        assert not any(f.check == "nondeterministic-collective-order"
                       for f in found)

    @pytest.mark.slow
    def test_live_tree_at_zero(self):
        import os

        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        found = lint_paths(
            [os.path.join(repo, "apex_tpu")], root=repo,
            checks=("nondeterministic-collective-order",))
        assert found == []


# --------------------------------------------------- live tree at 0
# (one per jaxpr check family: the REAL schedules under the gate — the
# registered-targets test above is the canonical form; these pin each
# check id to a named schedule so a regression names its check)


@pytest.mark.parametrize("check", SPMD_CHECKS)
def test_live_schedules_clean_per_check(check):
    findings, errors = run_targets(set(SPMD_TARGETS))
    assert errors == {}
    assert [f for f in findings if f.check == check] == []
