"""KV-cache decoding (models/generate.py): internal teacher-forcing
consistency plus token-level parity with HF generate on imported
weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import generate as gen
from apex_tpu.models import llama


def test_greedy_matches_teacher_forcing():
    """Every generated token must equal the argmax of the full
    (non-cached) forward at its position — the cache path and the
    training path are the same function."""
    cfg = llama.tiny(num_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)

    out = jax.jit(lambda p, t: gen.greedy_generate(p, t, cfg, 6))(
        params, prompt)
    assert out.shape == (2, 14)
    np.testing.assert_array_equal(np.asarray(out[:, :8]),
                                  np.asarray(prompt))

    logits = llama.forward(params, out, cfg, tp_axis=None, cp_axis=None,
                           remat=False)
    preds = np.asarray(jnp.argmax(logits, axis=-1))
    got = np.asarray(out)
    for t in range(8 - 1, 14 - 1):
        np.testing.assert_array_equal(
            got[:, t + 1], preds[:, t],
            err_msg=f"cached decode diverged at position {t + 1}")


def test_moe_greedy_matches_teacher_forcing():
    """MoE decode (VERDICT r4 missing #3): the no-drop inference router
    must reproduce the training forward exactly when the training path's
    capacity is large enough that it drops nothing either."""
    cfg = llama.tiny(num_layers=2, num_experts=4, moe_capacity_factor=8.0)
    assert cfg.moe
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)

    out = jax.jit(lambda p, t: gen.greedy_generate(p, t, cfg, 6))(
        params, prompt)
    assert out.shape == (2, 14)

    logits = llama.forward(params, out, cfg, tp_axis=None, cp_axis=None,
                           remat=False)
    preds = np.asarray(jnp.argmax(logits, axis=-1))
    got = np.asarray(out)
    for t in range(8 - 1, 14 - 1):
        np.testing.assert_array_equal(
            got[:, t + 1], preds[:, t],
            err_msg=f"moe cached decode diverged at position {t + 1}")


def test_moe_top1_switch_decode_runs():
    """Switch routing (top-1) keeps the RAW router prob as the gate —
    the decode router must preserve that (no renorm to 1.0)."""
    cfg = llama.tiny(num_layers=1, num_experts=4, moe_top_k=1,
                     moe_capacity_factor=8.0)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                cfg.vocab_size)
    out = gen.greedy_generate(params, prompt, cfg, 4)
    assert out.shape == (2, 10)
    logits = llama.forward(params, out, cfg, tp_axis=None, cp_axis=None,
                           remat=False)
    preds = np.asarray(jnp.argmax(logits, axis=-1))
    np.testing.assert_array_equal(np.asarray(out)[:, 6:],
                                  preds[:, 5:-1])


def test_decode_attention_gqa_matches_repeat_reference():
    """The grouped-einsum GQA decode attention (ISSUE 20 satellite)
    must equal the materialized jnp.repeat reference it replaced —
    same fp32 contractions over d and T, only the rep× cache copy
    removed — for scalar pos and for the serving scheduler's per-row
    [b, 1, 1] pos. Equal to fp32 rounding, not bit for bit: XLA picks
    its dot algorithm per operand layout, and the two einsums lay the
    heads out differently (3.6e-7 apart on XLA:CPU under jax 0.9)."""
    key = jax.random.PRNGKey(0)
    b, T, nkv, rep, d = 3, 16, 2, 3, 8
    nq = nkv * rep
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, 1, nq, d), jnp.float32)
    k_cache = jax.random.normal(kk, (b, T, nkv, d), jnp.float32)
    v_cache = jax.random.normal(kv, (b, T, nkv, d), jnp.float32)

    def reference(q, k_cache, v_cache, pos):
        k = jnp.repeat(k_cache, rep, axis=2)      # [b, T, nq, d]
        v = jnp.repeat(v_cache, rep, axis=2)
        scores = jnp.einsum("bqnd,btnd->bnt", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * (d ** -0.5)
        idx = jnp.arange(T)
        scores = jnp.where(idx[None, None, :] <= pos, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bnt,btnd->bnd", probs, v.astype(jnp.float32))
        return o.reshape(b, 1, nq * d)

    for pos in (0, 9, T - 1):
        want = np.asarray(reference(q, k_cache, v_cache, pos))
        got = np.asarray(gen._decode_attention(q, k_cache, v_cache, pos))
        np.testing.assert_allclose(
            got, want, rtol=2e-5, atol=2e-6,
            err_msg=f"grouped GQA attention diverged from the repeat "
                    f"reference at pos={pos}")

    # per-row positions (serving packed batch): each row must equal the
    # scalar-pos result for its own position
    rows = np.array([2, 9, 15])
    got = np.asarray(gen._decode_attention(
        q, k_cache, v_cache, jnp.asarray(rows)[:, None, None]))
    for i, p in enumerate(rows):
        want_i = np.asarray(reference(q, k_cache, v_cache, int(p)))[i]
        np.testing.assert_allclose(
            got[i], want_i, rtol=2e-5, atol=2e-6,
            err_msg=f"per-row pos diverged for row {i} (pos {p})")

    # bf16 caches exercise the astype path generate() actually runs
    got16 = np.asarray(gen._decode_attention(
        q.astype(jnp.bfloat16), k_cache.astype(jnp.bfloat16),
        v_cache.astype(jnp.bfloat16), 9))
    want16 = np.asarray(reference(
        q.astype(jnp.bfloat16), k_cache.astype(jnp.bfloat16),
        v_cache.astype(jnp.bfloat16), 9))
    np.testing.assert_allclose(got16, want16, rtol=2e-5, atol=2e-6)


def test_temperature_sampling_runs():
    cfg = llama.tiny(num_layers=1)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0,
                                cfg.vocab_size)
    out = gen.generate(params, prompt, cfg, 5, temperature=1.0,
                       key=jax.random.PRNGKey(7))
    assert out.shape == (1, 9)
    with pytest.raises(ValueError, match="PRNG key"):
        gen.generate(params, prompt, cfg, 2, temperature=0.5)


@pytest.mark.slow
def test_matches_hf_generate():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from apex_tpu.models import convert

    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(hf_cfg).eval()
    params, cfg = convert.llama_from_hf(hf, dtype=jnp.float32)

    prompt = np.random.default_rng(3).integers(0, 256, (2, 8))
    with torch.no_grad():
        want = hf.generate(
            torch.from_numpy(prompt), max_new_tokens=8, do_sample=False,
            pad_token_id=0).numpy()
    got = np.asarray(gen.greedy_generate(params, jnp.asarray(prompt),
                                         cfg, 8))
    np.testing.assert_array_equal(got, want)


def test_gpt2_greedy_matches_teacher_forcing():
    from apex_tpu.models import gpt2

    cfg = gpt2.tiny()
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    out = jax.jit(lambda p, t: gen.gpt2_generate(p, t, cfg, 6))(
        params, prompt)
    assert out.shape == (2, 14)

    logits = gpt2.forward(params, out, cfg, tp_axis=None, remat=False)
    preds = np.asarray(jnp.argmax(logits, axis=-1))
    got = np.asarray(out)
    for t in range(7, 13):
        np.testing.assert_array_equal(
            got[:, t + 1], preds[:, t],
            err_msg=f"gpt2 cached decode diverged at position {t + 1}")


@pytest.mark.slow
def test_gpt2_matches_hf_generate():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from apex_tpu.models import convert

    hf_cfg = transformers.GPT2Config(
        vocab_size=256, n_embd=64, n_layer=2, n_head=4, n_positions=64,
        attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0)
    torch.manual_seed(0)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    params, cfg = convert.gpt2_from_hf(hf, dtype=jnp.float32)

    prompt = np.random.default_rng(4).integers(0, 256, (2, 8))
    with torch.no_grad():
        want = hf.generate(
            torch.from_numpy(prompt), max_new_tokens=8, do_sample=False,
            pad_token_id=0).numpy()
    got = np.asarray(gen.gpt2_generate(params, jnp.asarray(prompt),
                                       cfg, 8))
    np.testing.assert_array_equal(got, want)
