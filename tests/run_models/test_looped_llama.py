"""A looped llama stack (ISSUE 29: Ouro's architecture at a tiny size) against
the plain reference `perfbench/references/ouro_looped.py`, on the same seeded
float32 weights: the forward and the exit gate's distribution, then prefill
and decode through the `[T*L]` cache. Logits are compared, not tokens: with
random weights the largest logit changes on rounding. And a plain
`LlamaConfig` is what it was: same tree, same cache, one scan of L steps."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import generate as gen
from apex_tpu.models import llama
from perfbench.references import ouro_looped as ref
from perfbench.references.common import seed_words

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000, "total_ut_steps": 4,
        "tie_word_embeddings": False, "torch_dtype": "float32"}
CFG = llama.LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=4, max_seq_len=256, rope_theta=1e6,
    rms_eps=1e-6, dtype=jnp.float32, num_passes=4, sandwich_norm=True)
TIGHT = 2e-4          # float32 round-off through 8 layer passes, logits O(1)


@pytest.fixture(scope="module")
def params():
    return ref.init(*seed_words(2 ** 31 + 29), TINY)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, 256)


def reference_logits(params, seq):
    return ref.row_logits(params, seq, jnp.arange(len(seq)), TINY)


def test_the_tree_is_the_references_and_counts_cache_layers(params):
    mine = llama.init_params(jax.random.PRNGKey(0), CFG)
    shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype), tree)
    assert shapes(mine) == shapes(params)
    assert set(mine["layers"]) >= {"attn_post_norm", "mlp_post_norm"}
    assert mine["exit_gate"]["w"].shape == (64, 1)
    assert CFG.cache_layers == 8 and llama.tiny().cache_layers == 2
    specs = llama.param_specs(CFG)
    assert jax.tree_util.tree_structure(
        specs, is_leaf=lambda s: not isinstance(s, dict)) == \
        jax.tree_util.tree_structure(mine)


@pytest.mark.parametrize("remat", [False, True])
def test_forward_matches_the_reference(params, tokens, remat):
    got = llama.forward(params, tokens, CFG, tp_axis=None, cp_axis=None,
                        remat=remat)
    for row in range(tokens.shape[0]):
        want = reference_logits(params, tokens[row])
        assert float(jnp.max(jnp.abs(got[row] - want))) < TIGHT


def test_exit_distribution_matches_the_reference(params, tokens):
    lam, p = llama.exit_distribution(params, tokens, CFG, tp_axis=None,
                                     cp_axis=None)
    assert lam.shape == p.shape == (4, 2, 24)
    np.testing.assert_allclose(np.asarray(p.sum(0)), 1.0, atol=1e-6)
    for row in range(tokens.shape[0]):
        want_lam, want_p = ref.exit_distribution(params, tokens[row], TINY)
        np.testing.assert_allclose(lam[:, row], want_lam, atol=1e-5)
        np.testing.assert_allclose(p[:, row], want_p, atol=1e-5)
    # the gate does read the passes: they differ from each other
    assert float(jnp.max(jnp.abs(lam[0] - lam[-1]))) > 1e-3
    with pytest.raises(ValueError, match="looped"):
        plain = llama.tiny()
        llama.exit_distribution(llama.init_params(jax.random.PRNGKey(0),
                                                  plain), tokens, plain,
                                tp_axis=None, cp_axis=None)


def test_loss_and_its_gradient_run_the_loop(params, tokens):
    """`loss_fn` goes through the same passes: its value is the reference
    logits' cross-entropy, and every layer weight gets a gradient (summed
    over its four uses)."""
    batch = (tokens[:, :-1], tokens[:, 1:])
    loss, grads = jax.value_and_grad(llama.loss_fn)(
        params, batch, CFG, tp_axis=None, cp_axis=None)
    want = np.mean([
        -jax.nn.log_softmax(reference_logits(params, tokens[r, :-1]))[
            jnp.arange(23), tokens[r, 1:]] for r in range(2)])
    assert float(loss) == pytest.approx(float(want), abs=1e-4)
    for name, g in grads["layers"].items():
        assert float(jnp.max(jnp.abs(g))) > 0, name
    chunked = llama.loss_fn(params, batch, CFG, tp_axis=None, cp_axis=None,
                            vocab_chunks=4)
    assert float(chunked) == pytest.approx(float(loss), abs=1e-5)


def served_gap(params, prompt, out, cfg_dict=TINY):
    """The widest gap by which a generated token's reference logit lies
    below the reference's best, over one row."""
    p = len(prompt)
    logits = ref.row_logits(params, out[:-1], jnp.arange(p - 1, len(out) - 1),
                            cfg_dict)
    return float(jnp.max(ref.served_gaps(logits, out[p:])))


def test_generate_through_the_cache_matches_the_full_forward(params, tokens):
    prompt = tokens[:, :9]
    out = gen.generate(params, prompt, CFG, 12)
    assert out.shape == (2, 21)
    for row in range(2):
        assert served_gap(params, prompt[row], out[row]) < TIGHT


@pytest.mark.parametrize("fault", [
    dict(num_passes=3), dict(sandwich_norm=False), dict(num_passes=1)])
def test_a_model_that_is_not_the_references_fails_the_gap(params, tokens,
                                                          fault):
    """The same weights through one pass fewer, or without the norms on the
    sub-layers' outputs, do not pass for the reference's model."""
    wrong = dataclasses.replace(CFG, **fault)
    prompt = tokens[:, :9]
    out = gen.generate(params, prompt, wrong, 12)
    assert max(served_gap(params, prompt[r], out[r]) for r in range(2)) > 0.01


def test_a_plain_config_is_what_it_was():
    """With the defaults: the tree has no new leaf, the cache has L layers,
    and the decode step and the forward are one scan of L steps with no
    `llama/pass` in them."""
    from apex_tpu.serving import PagedKVCache, build_decode_step

    cfg = llama.tiny()
    assert (cfg.num_passes, cfg.sandwich_norm) == (1, False)
    p = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert sorted(p) == ["embed", "final_norm", "layers", "lm_head"]
    assert sorted(p["layers"]) == ["attn_norm", "mlp_norm", "wd", "wg", "wk",
                                   "wo", "wq", "wu", "wv"]
    cache = PagedKVCache(cfg, num_pages=6, page_size=4)
    assert cache.k_pages.shape == (2, 7, 4, 2, 16)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    pages = jax.ShapeDtypeStruct(cache.k_pages.shape, cfg.dtype)
    for c, passes in ((cfg, 1), (dataclasses.replace(cfg, num_passes=4), 4)):
        jaxpr = jax.make_jaxpr(build_decode_step(c, 4))(
            p, {}, pages if passes == 1 else jax.ShapeDtypeStruct(
                (8,) + pages.shape[1:], cfg.dtype),
            pages if passes == 1 else jax.ShapeDtypeStruct(
                (8,) + pages.shape[1:], cfg.dtype),
            None, i32(3), i32(3, 2), i32(3), jax.ShapeDtypeStruct((3,), bool),
            jax.ShapeDtypeStruct((3,), bool), i32(3))
        scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert [e.params["length"] for e in scans] == [2 * passes]
        assert ("llama/pass" in str(jaxpr.pretty_print(
            name_stack=True))) == (passes > 1)
