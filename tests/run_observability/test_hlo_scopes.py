"""`op_scopes` on the compiled text of a tiny amp-O2 GPT-2 step with remat
(ISSUE 26): the scopes that `models/gpt2.py`, `amp` and `fused_adam` leave in
the HLO metadata come back as (phase, scope) per instruction, fusions
included, and little of the step is left unnamed."""

import collections
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from apex_tpu import amp
from apex_tpu.models import gpt2
from apex_tpu.observability import scope
from apex_tpu.observability.profiling.hlo_scopes import (
    PHASES,
    op_scopes,
    phase_and_scope,
)
from apex_tpu.optimizers import fused_adam

MODEL_SCOPES = ("gpt2/embed", "gpt2/attn", "gpt2/mlp", "gpt2/final_ln",
                "gpt2/head_ce", "amp/cast_model")


@pytest.fixture(scope="module")
def compiled_text():
    cfg = gpt2.tiny()
    handle = amp.initialize(opt_level="O2", verbosity=0)
    tx = fused_adam(lr=1e-4)

    def step(params, opt_state, sstate, batch):
        def scaled(p):
            loss = gpt2.loss_fn(handle.policy.cast_model(p), batch, cfg,
                                tp_axis=None, remat=True, vocab_chunks=2)
            return handle.scaler.scale_loss(loss, sstate), loss

        grads, loss = jax.grad(scaled, has_aux=True)(params)
        updates, opt_state, sstate, _ = handle.scaled_update(
            tx, grads, opt_state, params, sstate)
        with scope("optimizer/apply"):
            params = optax.apply_updates(params, updates)
        return params, opt_state, sstate, loss

    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        gpt2.init_params(jax.random.PRNGKey(0), cfg))
    tokens = jnp.zeros((2, 16), jnp.int32)
    return jax.jit(step).lower(params, tx.init(params), handle.scaler.init(),
                               (tokens, tokens)).compile().as_text()


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp()/while/body/closed_call/gpt2/attn/dot_general",
     ("forward", "gpt2/attn")),
    ("jit(step)/jvp(gpt2/final_ln)/mul", ("forward", "gpt2/final_ln")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/gpt2/mlp"
     "/mul", ("backward", "gpt2/mlp")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/gpt2/attn/bhqk,bkhd->bqhd/transpose",
     ("recompute", "gpt2/attn")),
    ("jit(step)/transpose(jvp(amp/cast_model))/convert_element_type",
     ("backward", "amp/cast_model")),
    ("jit(step)/amp/update/cond/branch_1_fun/fused_adam/tree/mul",
     ("optimizer", "amp/update/fused_adam/tree")),
    ("jit(step)/amp/unscale/jit(_where)/select_n",
     ("optimizer", "amp/unscale")),
    ("jit(step)/ddp/sync/div", ("other", "ddp/sync")),
    ("jit(step)/add", ("other", "")),
    ("reduce_sum", ("other", "")),
])
def test_phase_and_scope_of_an_op_name(op_name, want):
    assert phase_and_scope(op_name) == want


def test_every_scope_is_found_in_its_phases(compiled_text):
    found = collections.defaultdict(set)
    for phase, scope in op_scopes(compiled_text).values():
        assert phase in PHASES
        found[phase].add(scope)
    for scope in MODEL_SCOPES:
        assert any(s.startswith(scope) for s in found["forward"]), scope
        assert any(s.startswith(scope) for s in found["backward"]), scope
    for scope in ("gpt2/attn", "gpt2/mlp"):
        assert any(s.startswith(scope) for s in found["recompute"]), scope
    assert any(s.startswith("amp/update") for s in found["optimizer"])
    assert any("fused_adam/" in s for s in found["optimizer"])
    assert {"amp/unscale", "optimizer/apply"} <= found["optimizer"]


def test_fusions_are_named_and_little_is_left_unnamed(compiled_text):
    scopes = op_scopes(compiled_text)
    fusions = [n for n in scopes if re.match(r"^[\w\-]*fusion(\.\d+)?$", n)]
    assert len(fusions) > 20
    assert sum(scopes[n][0] != "other" for n in fusions) > 0.9 * len(fusions)
    # of the entry computation's instructions that carry an op_name, the
    # arguments aside: the copies, broadcasts and prefetches XLA adds of its
    # own carry none, and nothing can name them
    entry = compiled_text[compiled_text.index("\nENTRY "):]
    named = [m.group(1) for line in entry.splitlines()
             if "op_name=" in line and " parameter(" not in line
             and (m := re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s", line))]
    assert len(named) > 50
    other = [n for n in named if scopes[n][0] == "other"]
    assert len(other) < 0.05 * len(named), other
