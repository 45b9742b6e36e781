"""The standalone test harness itself (ref apex/transformer/testing/):
args/global_vars singletons, commons fixtures, DistributedTestBase, and the
standalone GPT/BERT builders driven through the collective pipeline."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map

from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.pipeline_parallel.schedules import pipelined_forward
from apex_tpu.transformer.testing import (
    build_mesh,
    commons,
    fwd_step_func,
    global_vars,
    set_random_seed,
)
from apex_tpu.transformer.testing.arguments import parse_args
from apex_tpu.transformer.testing.distributed_test_base import (
    DistributedTestBase,
)
from apex_tpu.transformer.testing import standalone_bert, standalone_gpt


@pytest.fixture(autouse=True)
def _clean_globals():
    global_vars.destroy_global_vars()
    yield
    global_vars.destroy_global_vars()
    parallel_state.destroy_model_parallel()


# ----------------------------------------------------------------- arguments


def test_parse_args_megatron_flags_and_derived():
    args = parse_args(args=[
        "--num-layers", "8", "--hidden-size", "32",
        "--num-attention-heads", "4", "--micro-batch-size", "2",
        "--global-batch-size", "16", "--tensor-model-parallel-size", "2",
        "--pipeline-model-parallel-size", "2", "--bf16",
        "--some-unknown-cuda-flag", "7",   # ignored, like the ref harness
    ])
    assert args.ffn_hidden_size == 128          # derived 4*h
    assert args.kv_channels == 8                # derived h/heads
    assert args.model_parallel_size == 4
    assert args.params_dtype == "bfloat16"


def test_parse_args_rejects_fp16_plus_bf16():
    with pytest.raises(ValueError):
        parse_args(args=["--fp16", "--bf16"])


def test_parse_args_virtual_pp_divisibility():
    with pytest.raises(ValueError):
        parse_args(args=[
            "--num-layers", "6", "--pipeline-model-parallel-size", "2",
            "--virtual-pipeline-model-parallel-size", "2"])


# --------------------------------------------------------------- global_vars


def test_global_vars_lifecycle():
    with pytest.raises(AssertionError):
        global_vars.get_args()
    args = global_vars.set_global_variables(
        args=["--global-batch-size", "8", "--micro-batch-size", "2"],
        data_parallel_size=2)
    assert global_vars.get_args() is args
    assert global_vars.get_num_microbatches() == 2   # 8 / (2 * 2)
    assert global_vars.get_current_global_batch_size() == 8
    with pytest.raises(AssertionError):
        global_vars.set_global_variables(args=[])    # double init


def test_timers():
    global_vars.set_global_variables(args=[], data_parallel_size=1)
    timers = global_vars.get_timers()
    timers("fwd").start()
    timers("fwd").stop()
    assert timers("fwd").elapsed(reset=False) >= 0.0


# ------------------------------------------------------------------- commons


def test_toy_model_and_fwd_step():
    key = set_random_seed(1234)
    sp = commons.init_toy_stage_params(key, hidden_size=8, layers_per_stage=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    y, loss_fn = fwd_step_func(x, sp)
    assert y.shape == x.shape
    loss, metrics = loss_fn(y)
    assert np.isfinite(float(loss)) and "avg" in metrics


def test_build_mesh_and_initialize_distributed():
    mesh = build_mesh((2, 2, 2), ("pp", "dp", "tp"))
    assert mesh.shape == {"pp": 2, "dp": 2, "tp": 2}
    mesh2 = commons.initialize_distributed(tp=2, pp=2)
    assert parallel_state.get_tensor_model_parallel_world_size() == 2
    assert mesh2.shape["dp"] == 2


# ------------------------------------------------------- DistributedTestBase


class _MeshCase(DistributedTestBase):
    TP = 2
    PP = 2

    def test_mesh_alive(self):
        assert self.mesh.shape["tp"] == 2
        assert parallel_state.get_pipeline_model_parallel_world_size() == 2


def test_distributed_test_base_runs():
    import unittest

    suite = unittest.defaultTestLoader.loadTestsFromTestCase(_MeshCase)
    result = unittest.TextTestRunner(verbosity=0).run(suite)
    assert result.wasSuccessful()


# -------------------------------------------- standalone GPT through the pipe


def _pipeline_loss_vs_single(provider, param_specs_fn, make_batch,
                             head_loss_call):
    """Drive a standalone model through a REAL pp=2 x tp=2 composition
    (params sharded per the model's param_specs, vocab-parallel embedding
    and CE over 'tp') and compare the loss against the single-process
    full-model forward — the reference harness's pipeline parity check."""
    args = global_vars.set_global_variables(args=[
        "--num-layers", "4", "--hidden-size", "16",
        "--num-attention-heads", "2", "--seq-length", "16",
        "--padded-vocab-size", "64", "--micro-batch-size", "2",
        "--tensor-model-parallel-size", "2",
        "--pipeline-model-parallel-size", "2"])
    cfg, init_params, split_stages, embed_fn, stage_fn, head_fn = provider(
        args)
    params = init_params(jax.random.PRNGKey(0), cfg)
    M, mb, s = 2, 2, args.seq_length
    batch = make_batch(jax.random.PRNGKey(1), M, mb, s, cfg)

    pp, tp = (args.pipeline_model_parallel_size,
              args.tensor_model_parallel_size)
    mesh = build_mesh((pp, tp), ("pp", "tp"))
    stages = split_stages(params, pp)
    io = {k: v for k, v in params.items() if k != "layers"}
    specs = param_specs_fn(cfg, tp_axis="tp")
    stage_specs = {k: P("pp", *specs["layers"][k]) for k in stages}
    io_specs = {k: specs[k] for k in io}

    from apex_tpu.transformer.tensor_parallel.mappings import _to_varying

    def vary(t):
        for ax in ("pp", "tp"):
            t = jax.tree_util.tree_map(
                lambda a, ax=ax: _to_varying(a, ax), t)
        return t

    def shard_step(stages, io, *batch):
        stage = vary(jax.tree_util.tree_map(lambda a: a[0], stages))
        io = vary(io)
        x_mb = vary(jax.vmap(
            lambda tok: embed_fn(io, tok, cfg, tp_axis="tp"))(batch[0]))
        outs = pipelined_forward(
            lambda sp, x: stage_fn(sp, x, cfg, tp_axis="tp"), stage, x_mb,
            axis_name="pp")
        losses = jax.vmap(
            lambda o, *rest: head_fn(io, o, *rest, cfg, tp_axis="tp")
        )(outs, *[vary(b) for b in batch[1:]])
        last = jax.lax.axis_index("pp") == jax.lax.axis_size("pp") - 1
        loss = jax.lax.psum(jnp.where(last, jnp.mean(losses), 0.0), "pp")
        return jax.lax.pmean(loss, "tp")[None]

    with mesh:
        out = jax.jit(shard_map(
            shard_step, mesh=mesh,
            in_specs=(stage_specs, io_specs, *[P()] * len(batch)),
            out_specs=P(),
        ))(stages, io, *batch)
    piped = float(out[0])
    single = head_loss_call(params, cfg, batch)
    np.testing.assert_allclose(piped, single, rtol=2e-4, atol=2e-5)


def test_standalone_gpt_pipeline_matches_single():
    from apex_tpu.models import gpt2

    def make_batch(key, M, mb, s, cfg):
        tokens = jax.random.randint(key, (M, mb, s), 0, cfg.vocab_size)
        return (tokens, jnp.roll(tokens, -1, -1))

    def single(params, cfg, batch):
        tokens, targets = batch
        losses = [
            float(gpt2.loss_fn(params, (tokens[i], targets[i]), cfg,
                               tp_axis=None, remat=False))
            for i in range(tokens.shape[0])]
        return float(np.mean(losses))

    _pipeline_loss_vs_single(
        standalone_gpt.gpt_model_provider, gpt2.param_specs, make_batch,
        single)


def test_standalone_bert_pipeline_matches_single():
    from apex_tpu.models import bert

    def make_batch(key, M, mb, s, cfg):
        k1, k2 = jax.random.split(key)
        tokens = jax.random.randint(k1, (M, mb, s), 0, cfg.vocab_size)
        targets = jax.random.randint(k2, (M, mb, s), 0, cfg.vocab_size)
        loss_mask = jnp.ones((M, mb, s), jnp.float32)
        return (tokens, targets, loss_mask)

    def single(params, cfg, batch):
        tokens, targets, loss_mask = batch
        losses = [
            float(bert.loss_fn(
                params, (tokens[i], targets[i], loss_mask[i]), cfg,
                tp_axis=None, remat=False))
            for i in range(tokens.shape[0])]
        return float(np.mean(losses))

    _pipeline_loss_vs_single(
        standalone_bert.bert_model_provider, bert.param_specs, make_batch,
        single)
