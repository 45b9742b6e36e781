"""Trace-analysis half of pyprof (ref apex/pyprof/prof/prof.py +
parse/parse.py): parse an xplane capture of one llama train step and
attribute time to ops — the report must name the matmuls and the
collectives and the attribution must be self-consistent."""

import numpy as np
import pytest

pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from apex_tpu.pyprof import parse, prof


@pytest.fixture(scope="module")
def llama_capture(tmp_path_factory):
    """One dp=2×tp=2 llama train step (grads pmean-synced over dp, TP
    collectives over tp), traced on the CPU mesh."""
    from apex_tpu.models import llama
    from apex_tpu.optimizers import fused_adam

    cfg = llama.tiny(num_layers=2, vocab_size=128, hidden_size=64,
                     num_heads=4, num_kv_heads=2, intermediate_size=128)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tx = fused_adam(lr=1e-3)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    specs = llama.param_specs(cfg)

    def step(p, opt_state, tokens):
        def loss_fn(p):
            l = llama.loss_fn(p, (tokens, tokens), cfg, tp_axis="tp",
                              cp_axis=None)
            return jax.lax.pmean(l, "dp")

        loss, grads = jax.value_and_grad(loss_fn)(p)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, "dp"), grads)
        updates, opt_state = tx.update(grads, opt_state, p)
        return jax.tree_util.tree_map(jnp.add, p, updates), opt_state, loss

    from apex_tpu.optimizers import opt_partition_specs

    with mesh:
        opt_state = tx.init(params)
        opt_specs = opt_partition_specs(tx, params, specs)
        jstep = jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(specs, opt_specs, P("dp", None)),
            out_specs=(specs, opt_specs, P())))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                    cfg.vocab_size)
        out = jstep(params, opt_state, tokens)  # compile outside trace
        jax.block_until_ready(out)
        logdir = str(tmp_path_factory.mktemp("trace"))
        with jax.profiler.trace(logdir):
            out = jstep(params, opt_state, tokens)
            jax.block_until_ready(out)
    return logdir


def test_parse_finds_hlo_ops(llama_capture):
    paths = parse.find_xplane_paths(llama_capture)
    assert paths, "capture produced no xplane file"
    records = parse.parse_xspace(paths)
    assert len(records) > 50
    # exclusive time must be positive and never exceed inclusive
    assert all(0 <= r.self_ps <= r.duration_ps for r in records)
    assert any(r.self_ps > 0 for r in records)


def test_report_names_matmul_and_collectives(llama_capture):
    report = prof.Report.from_capture(llama_capture)
    cats = report.by_category()
    assert "matmul" in cats and cats["matmul"]["self_us"] > 0, (
        f"no matmul attribution: {list(cats)}")
    # tp row/column collectives + the dp grad pmean must show up
    assert "collective" in cats and cats["collective"]["occurrences"] > 0, (
        f"no collective attribution: {list(cats)}")
    names = " ".join(o.name for o in report.ops)
    assert "dot" in names
    assert "psum" in names or "all-reduce" in names or "all_gather" in names


def test_report_shares_and_serialization(llama_capture):
    report = prof.Report.from_capture(llama_capture)
    shares = [o.share for o in report.ops]
    assert abs(sum(shares) - 1.0) < 1e-6
    assert shares == sorted(shares, reverse=True)
    d = report.to_dict(top=10)
    assert len(d["ops"]) == 10
    assert d["total_self_us"] > 0
    table = report.format_table(top=5)
    assert "TOTAL" in table and "category" in table
    # no device plane on the CPU mesh: flops absent, utilization == 0
    util = report.utilization(peak_tflops=197.0)
    assert util["mfu"] == 0.0


def test_classify_categories():
    assert parse.classify("all-reduce.1") == "collective"
    assert parse.classify("psum_invariant.7") == "collective"
    assert parse.classify("ppermute.2") == "collective"
    assert parse.classify("dot_general.3") == "matmul"
    assert parse.classify("convolution.4") == "convolution"
    assert parse.classify("copy.16") == "data-movement"
    assert parse.classify("wrapped_reduce.2") == "reduction"
    assert parse.classify("add_rsqrt_fusion") == "fusion-elementwise"
    # a non-attention Pallas kernel (custom-call) must NOT be labeled
    # attention
    assert parse.classify("fused_adam_custom-call") == "custom-kernel"
    assert parse.classify("custom-call.3") == "custom-kernel"
    assert parse.classify("flash_fwd_custom-call") == "attention-kernel"
    assert parse.is_container("while.5")
    assert not parse.is_container("dot.1")


def _add_stat(pb, ev, plane, name, value):
    """Append a stat to an event, interning stat metadata on the plane."""
    sid = next((m.id for m in plane.stat_metadata.values()
                if m.name == name), None)
    if sid is None:
        sid = len(plane.stat_metadata) + 1
        plane.stat_metadata[sid].id = sid
        plane.stat_metadata[sid].name = name
    s = ev.stats.add()
    s.metadata_id = sid
    if isinstance(value, str):
        s.str_value = value
    else:
        s.int64_value = int(value)


def _tpu_dialect_capture(tmp_path):
    """Synthetic xplane in the REAL TPU capture dialect (r5): op events
    named with the full '%op.N = ...' HLO text, timing in
    device_offset_ps/device_duration_ps stats (no 'hlo_op' stat on the
    op line), plus 'Steps' markers and an 'Async XLA Ops' line."""
    from apex_tpu.pyprof.parse import _xplane_pb2

    pb = _xplane_pb2()
    xs = pb.XSpace()
    plane = xs.planes.add()
    plane.name = "/device:TPU:0"

    def add_line(name):
        line = plane.lines.add()
        line.name = name
        return line

    def add_event(line, name, offset_ps, dur_ps, stats=(),
                  device_stats=True):
        mid = len(plane.event_metadata) + 1
        plane.event_metadata[mid].id = mid
        plane.event_metadata[mid].name = name
        ev = line.events.add()
        ev.metadata_id = mid
        if device_stats:
            # TPU op dialect: event offset/duration unused, timing in stats
            ev.offset_ps = 0
            ev.duration_ps = 0
            _add_stat(pb, ev, plane, "device_offset_ps", offset_ps)
            _add_stat(pb, ev, plane, "device_duration_ps", dur_ps)
        else:
            # 'Steps' markers carry plain event timing (real r5 capture)
            ev.offset_ps = offset_ps
            ev.duration_ps = dur_ps
        for k, v in stats:
            _add_stat(pb, ev, plane, k, v)
        return ev

    steps = add_line("Steps")
    for i in range(2):
        add_event(steps, f"step{i}", i * 1_000_000_000, 1_000_000_000,
                  device_stats=False)

    ops = add_line("XLA Ops")
    add_event(ops, "%dot.1 = bf16[128,128]{1,0:T(8,128)} dot(...)",
              0, 600_000_000)
    add_event(ops, "%fusion.2 = bf16[128]{0} fusion(...)",
              600_000_000, 300_000_000)
    add_event(ops, "%all-reduce.3 = bf16[128]{0} all-reduce(...)",
              1_000_000_000, 400_000_000)

    async_line = add_line("Async XLA Ops")
    add_event(async_line,
              "%slice-start.9 = (...) async-start(...), calls=...",
              0, 900_000_000, stats=[("hlo_op", "slice-done.9")])

    out = tmp_path / "vm.xplane.pb"
    out.write_bytes(xs.SerializeToString())
    return str(out)


def test_tpu_dialect_parse_and_report(tmp_path):
    path = _tpu_dialect_capture(tmp_path)
    steps = parse.step_times_us([path])
    assert steps == [1000.0, 1000.0]

    records = parse.parse_xspace([path])
    op_lines = {r.line for r in records}
    assert "XLA Ops" in op_lines and "Async XLA Ops" in op_lines

    report = prof.Report.from_records(records, steps_us=steps)
    # main table: the three 'XLA Ops' events only, classified through
    # the %-sigil HLO text
    assert report.total_self_us == pytest.approx(1300.0)
    cats = report.by_category()
    assert cats["matmul"]["self_us"] == pytest.approx(600.0)
    assert cats["collective"]["self_us"] == pytest.approx(400.0)
    names = [o.name for o in report.ops]
    assert "dot.1" in names and "all-reduce.3" in names
    # async copies live in their own bucket, not the exclusive total
    assert [o.name for o in report.async_ops] == ["slice-start.9"]
    assert report.async_ops[0].share == pytest.approx(0.45)
    d = report.to_dict()
    assert d["steps"]["n"] == 2
    assert d["async_ops"][0]["name"] == "slice-start.9"


def test_short_name_and_tpu_classify():
    assert parse.short_name("%slice-start.73 = (...) async-start(...)") \
        == "slice-start.73"
    assert parse.short_name("fusion.2") == "fusion.2"
    assert parse.classify(
        "%slice-start.73 = (...) async-start(...)") == "data-movement"
    assert parse.classify(
        "%dot.1 = bf16[8,8]{1,0} dot(...)") == "matmul"
    assert parse.classify(
        "%convolution_add_fusion.4 = ...") == "convolution"
