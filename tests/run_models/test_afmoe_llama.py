"""A stack of more than one layer kind through `models/llama.py` and
`models/generate.py` (ISSUE 33): a dense lead and dropless expert layers that
hold a share of the experts, sliding-window and full attention layers, head
norms, an output gate, RoPE on the sliding layers only and the embedding's
multiplier, against the plain reference `perfbench/references/afmoe.py` on the
same seeded float32 weights, with a window shorter than the sequence. Each
part left out or misplaced reads over the tolerance that the sound model
meets; what is not built refuses by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import generate as gen, llama
from perfbench.references import afmoe as ref
from perfbench.references.common import seed_words
from perfbench.runners import serve_afmoe

TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=32, intermediate_size=128, moe_intermediate_size=48,
            num_hidden_layers=5, num_dense_layers=1,
            layer_types=["sliding_attention"] * 4 + ["full_attention"],
            num_experts=8, experts_held={"first": 8, "count": 8, "of": 32},
            num_experts_per_tok=4, num_shared_experts=1, rms_norm_eps=1e-5,
            rope_theta=10000, route_norm=True, route_scale=2.448,
            score_func="sigmoid", sliding_window=12, mup_enabled=True,
            tie_word_embeddings=False, vocab_size=96, torch_dtype="float32",
            max_position_embeddings=256)
CFG = serve_afmoe.model_config(TINY)
SEQ = 32
SOUND = 2e-5          # widest |logit difference| the sound model may read
UNBOUND = dict(tp_axis=None, cp_axis=None, ep_axis=None, remat=False)


@pytest.fixture(scope="module")
def params():
    p = ref.init(*seed_words(2 ** 31 + 33), TINY)
    # a bias large enough to change who is chosen: 0.01 moves near-ties only
    p["layers"]["router_bias"] = p["layers"]["router_bias"] * 30.0
    return p


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(1).integers(0, 96, SEQ).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    return np.asarray(ref.row_logits(params, jnp.asarray(tokens),
                                     jnp.arange(SEQ), TINY))


def forward(params, tokens, cfg):
    return np.asarray(llama.forward(params, jnp.asarray(tokens)[None], cfg,
                                    **UNBOUND))[0]


def test_forward_is_the_reference_with_a_window_shorter_than_the_sequence(
        params, tokens, want):
    assert CFG.sliding_window < SEQ
    got = forward(params, tokens, CFG)
    assert np.abs(got - want).max() <= SOUND
    assert np.abs(want).max() > 1.0


@pytest.mark.parametrize("fault", [
    dict(attn_output_gate=False),                      # the gate left out
    dict(qk_norm=False),                               # the head norms
    dict(rope_full_attention=True),                    # RoPE on the full layer
    dict(sliding_window=SEQ + 1),                      # the window left out
    dict(sliding_window=13),                           # ... off by one
    dict(num_shared_experts=0),                        # the shared expert
    dict(route_scale=1.0),
    dict(route_norm=False),
    dict(embed_scale=1.0),                             # the multiplier
    dict(router_bias=False),                           # the bias left out
    dict(router_score="softmax"),
    dict(experts_held=(0, 8)),                         # another share
], ids=lambda f: "-".join(f"{k}={v}" for k, v in f.items()))
def test_each_part_left_out_or_misplaced_reads_over_the_tolerance(
        params, tokens, want, fault):
    got = forward(params, tokens, dataclasses.replace(CFG, **fault))
    assert np.abs(got - want).max() > 100 * SOUND


def test_the_bias_misplaced_into_the_weight_reads_over_the_tolerance(
        params, tokens, want, monkeypatch):
    from apex_tpu.transformer import moe

    real = moe.route

    def biased_weights(x, router, bias=None, **kw):
        w, idx = real(x, router, bias, **{**kw, "route_norm": False})
        w = w + jnp.take_along_axis(jnp.broadcast_to(bias, (x.shape[0], 32)),
                                    idx, axis=-1)
        return w / jnp.sum(w, -1, keepdims=True) * kw["route_scale"], idx

    monkeypatch.setattr(moe, "route", biased_weights)
    assert np.abs(forward(params, tokens, CFG) - want).max() > 100 * SOUND


def test_generate_decodes_what_the_reference_puts_first(params, tokens):
    """Prefill of 20 positions (cut by the window of 12), then 12 steps of
    decode across more of it, on a contiguous cache: each new token is the
    reference's best at its position, and its logit gap is round-off."""
    out = np.asarray(gen.generate(params, jnp.asarray(tokens[:20])[None],
                                  CFG, 12))[0]
    seq = np.zeros(SEQ, np.int32)
    seq[:31] = out[:-1]
    logits = np.asarray(ref.row_logits(params, jnp.asarray(seq),
                                       jnp.arange(19, 31), TINY))
    gaps = logits.max(-1) - logits[np.arange(12), out[20:]]
    assert gaps.max() <= SOUND


def test_init_params_has_the_references_tree(params):
    mine = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0),
                                                    CFG))
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    specs = llama.param_specs(CFG)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs,
                               is_leaf=lambda s: not isinstance(s, dict))) \
        == jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda a: 0, params))


def test_stacks_and_the_kinds_as_data(params):
    lead, experts = llama.stacks(params, CFG)
    assert lead["attn_norm"].shape[0] == 1 and "router" not in lead
    assert experts["router"].shape == (4, 64, 32)
    assert not {"wg", "wu", "wd"} & set(experts)
    assert list(np.asarray(experts["expert_at"])) == [0, 8, 16, 24]
    assert list(np.asarray(lead["sliding"])) == [True]
    assert list(np.asarray(experts["sliding"])) == [True, True, True, False]
    flat = llama.expert_stack(params)
    assert flat["wg"].shape == (32, 64, 48) and flat["wd"].shape == (32, 48, 64)
    # a model of one kind: the stack itself, nothing added
    plain = llama.init_params(jax.random.PRNGKey(0), llama.tiny())
    assert llama.stacks(plain, llama.tiny()) is plain["layers"]


def test_an_expert_stack_without_a_lead_a_window_or_a_rotation():
    """The parts are independent facts of a model: expert layers from the
    first layer on, every layer full and none rotating (the reference rotates
    sliding layers only), all experts held: one stack, one scan, no kinds."""
    tiny = dict(TINY, num_hidden_layers=3, num_dense_layers=0,
                layer_types=["full_attention"] * 3, sliding_window=None,
                num_experts=8, experts_held={"first": 0, "count": 8, "of": 8},
                num_experts_per_tok=2)
    cfg = serve_afmoe.model_config(tiny)
    assert not cfg.windowed and cfg.held == (0, 8)
    params = ref.init(*seed_words(7), tiny)
    assert "dense_layers" not in params
    stack = llama.stacks(params, cfg)
    assert isinstance(stack, dict) and "sliding" not in stack
    tokens = np.random.default_rng(2).integers(0, 96, 16).astype(np.int32)
    want = np.asarray(ref.row_logits(params, jnp.asarray(tokens),
                                     jnp.arange(16), tiny))
    assert np.abs(forward(params, tokens, cfg) - want).max() <= SOUND
    rotating = dataclasses.replace(cfg, rope_full_attention=True)
    assert np.abs(forward(params, tokens, rotating) - want).max() > 100 * SOUND


def test_what_is_not_built_refuses_by_name(params, tokens):
    batch = (jnp.asarray(tokens)[None], jnp.asarray(tokens)[None])
    with pytest.raises(NotImplementedError, match="training a dropless"):
        llama.loss_fn(params, batch, CFG, **UNBOUND)
    with pytest.raises(NotImplementedError, match="run_layers"):
        llama.run_layers(jnp.zeros((1, 4, 64)), params["layers"], CFG,
                         jnp.zeros((1, 4), jnp.int32), None, None)
    with pytest.raises(ValueError, match="layer_types"):
        llama.tiny(layer_types=("full_attention",))
    with pytest.raises(ValueError, match="sliding_window"):
        llama.tiny(layer_types=("sliding_attention", "full_attention"))
    with pytest.raises(ValueError, match="dropless expert model"):
        llama.tiny(num_experts=4, experts_held=(0, 2))
    with pytest.raises(NotImplementedError, match="looped"):
        llama.tiny(num_experts=4, moe_capacity_factor=None, num_passes=2)
