"""chip_smoke.py's phases at tiny width on the CPU, kernels in interpret
mode, plus the compile-cache helper's placement policy. The script's own
entry still refuses a non-TPU backend — that is the point of it; these
tests keep the phase code from rotting between chip runs."""

import dataclasses
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke  # repo root is on sys.path via tests/conftest.py
from apex_tpu.models import gpt2, llama
from apex_tpu.ops import pallas_config
from apex_tpu.runtime import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_KERNELS = dict(
    flash=(2, 64, 4, 2, 16), flash_gpt2=(1, 64, 2, 2, 64), prefill_len=40,
    norm_rows=64, ln_hidden=128, rms_hidden=128,
    causal_softmax=(4, 32), masked_softmax=(8, 32),
    adam_n=5000, fp8=(64, 128), gmm=(80, 32, 128, 5))
# heads divide tp=2, the vocabulary divides tp x 8 chunks, batch divides dp=4
TINY_GPT2 = gpt2.tiny(vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=4, max_seq_len=32)


@pytest.fixture
def interpret():
    with pallas_config.force("interpret"):
        yield


def test_phase_kernels_tiny(interpret):
    assert chip_smoke.phase_kernels(TINY_KERNELS) == {"checks": 15}


def test_phase_train_then_mesh_tiny():
    with pallas_config.force("interpret"):
        train = chip_smoke.phase_train(cfg=TINY_GPT2, batch=4, steps=8)
    assert train["last_loss"] < train["first_loss"]
    # The mesh phase takes the kernels' jnp path here: the Pallas
    # interpreter evaluates kernel bodies primitive by primitive, and
    # under shard_map's check_vma that rejects a kernel's own constants
    # (invariant) meeting its operands (varying) — a limitation of the
    # interpreter, not of the compiled kernels the chip run uses. What
    # this keeps honest is the composition: sharded init, both gradient
    # reductions, placement, and the loss against one device's.
    mesh = chip_smoke.phase_mesh(train["first_loss"], cfg=TINY_GPT2, batch=4)
    assert set(mesh) == {"dp2_tp2", "ddp4"}


def test_phase_serve_tiny(interpret):
    out = chip_smoke.phase_serve(
        cfg=llama.tiny(), mix=((8, 8), (20, 6), (32, 4)), requests=5,
        max_batch=2)
    assert out["exact"] + out["near_ties"] == 5


def test_phase_serve_looped_tiny(interpret, monkeypatch):
    """Two layers run four times, float32: the engine's tokens are the plain
    reference's to round-off; with a pass left out of the program they are
    not."""
    tiny = dict(chip_smoke.LOOPED, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                vocab_size=256, torch_dtype="float32")
    kw = dict(mix=((8, 8), (20, 6), (32, 4)), max_batch=2, page_size=8)
    out = chip_smoke.phase_serve_looped(tiny, gap_limit=1e-3, **kw)
    assert 0 <= out["widest_gap"] <= 1e-3
    # the reference runs four passes, the program is told three
    real = llama.LlamaConfig
    monkeypatch.setattr(llama, "LlamaConfig", lambda **k: real(
        **{**k, "num_passes": k["num_passes"] - 1}))
    with pytest.raises(AssertionError, match="over the limit"):
        chip_smoke.phase_serve_looped(tiny, gap_limit=1e-3, **kw)


def test_phase_serve_afmoe_tiny(interpret, monkeypatch):
    """A dense lead and four expert layers holding 4 of 16 experts, a window
    of 12 under prompts of 24 and 32 (the windowed flash forward in interpret
    mode, a prefill cut by the window, decode across its edge), float32: the
    engine's tokens are the plain reference's to round-off; with the window
    left out of the program they are not."""
    tiny = dict(chip_smoke.AFMOE, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=48, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, sliding_window=12,
                num_experts=4,
                experts_held={"first": 4, "count": 4, "of": 16},
                num_experts_per_tok=2, vocab_size=256, torch_dtype="float32")
    kw = dict(mix=((24, 8), (8, 10), (32, 4)), max_batch=2, page_size=8)
    out = chip_smoke.phase_serve_afmoe(tiny, gap_limit=1e-3, **kw)
    assert 0 <= out["widest_gap"] <= 1e-3
    from perfbench.runners import serve_afmoe

    real = serve_afmoe.model_config
    monkeypatch.setattr(serve_afmoe, "model_config", lambda c: dataclasses.replace(
        real(c), layer_types=("full_attention",) * 5))
    with pytest.raises(AssertionError, match="over the limit"):
        chip_smoke.phase_serve_afmoe(tiny, gap_limit=1e-3, **kw)


def test_phase_serve_lfm2_tiny(interpret, monkeypatch):
    """The cut's nine layers at tiny widths (seven conv layers, two attention
    layers whose heads lie side by side in a page, a dense lead, eight
    experts all held), prompts that end inside their bucket and one shorter
    than the convolution, float32: the engine's tokens are the plain
    reference's to round-off; with the conv state taken at the bucket's end
    they are not."""
    from perfbench.references import lfm2_moe

    tiny = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
                head_dim=32, intermediate_size=128, moe_intermediate_size=48,
                num_hidden_layers=9, num_dense_layers=1,
                layer_types=["conv"] + ["full_attention", "conv", "conv",
                                        "conv"] * 2,
                num_experts=8, num_experts_per_tok=4, norm_eps=1e-5,
                rope_parameters={"rope_theta": 1000000}, norm_topk_prob=True,
                routed_scaling_factor=1, use_expert_bias=True, conv_L_cache=3,
                conv_bias=False, vocab_size=256, torch_dtype="float32",
                tie_word_embeddings=True, max_position_embeddings=256)
    monkeypatch.setattr(lfm2_moe, "QUERY_BLOCK", 8)
    kw = dict(mix=((21, 8), (8, 10), (30, 4), (2, 6)), max_batch=2,
              page_size=8)
    out = chip_smoke.phase_serve_lfm2(tiny, gap_limit=1e-3, **kw)
    assert 0 <= out["widest_gap"] <= 1e-3
    from apex_tpu.models import generate

    true = generate._prefill_attend
    monkeypatch.setattr(generate, "_prefill_attend",
                        lambda lp, cfg, length=None: true(lp, cfg))
    with pytest.raises(AssertionError, match="over the limit"):
        chip_smoke.phase_serve_lfm2(tiny, gap_limit=1e-3, **kw)


def test_phases_refuse_the_jnp_path():
    """Outside interpret mode on the CPU the kernels take their jnp path;
    the HLO check must catch that, not pass it."""
    with pytest.raises(AssertionError, match="Mosaic custom call"):
        chip_smoke.phase_train(cfg=TINY_GPT2, batch=4, steps=1)


def test_entry_refuses_a_non_tpu_backend(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO))
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout == ""          # no result line, no metric


# ------------------------------------------------- compile-cache placement


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


def test_compile_cache_env_set_code_sets_nothing(monkeypatch, tmp_path,
                                                 config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert config_updates == []


def test_compile_cache_env_unset_fixed_checkout_path(monkeypatch,
                                                     config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == os.path.join(_REPO, ".jax_compile_cache")
    assert ("jax_compilation_cache_dir", first) in config_updates
    # every program is cached, so a second run compiles nothing
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) \
        in config_updates
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_compile_cache_not_placed_on_the_cpu(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.default_backend() == "cpu"
    assert compile_cache.enable_compile_cache() is None
    assert config_updates == []
