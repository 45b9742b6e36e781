"""The sliding window of the flash attention forward (ISSUE 33): the kernel
body in interpret mode against a plainly masked softmax, at windows under,
at and over the sequence and off the block size; which key blocks the kernel
skips; and the backward kernels, which do not know a window, raising."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import pallas_config
from apex_tpu.ops.flash_attention import (
    _flash_fwd_pallas,
    _reference_attention,
    flash_attention,
)


def masked_softmax_attention(q, k, v, scale, window):
    """q [bh, s, d], k and v [bh_kv, s, d], numpy, float64: query i reads
    keys i - window < j <= i."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    rep = q.shape[0] // k.shape[0]
    k, v = k.repeat(rep, 0), v.repeat(rep, 0)
    s = scale * np.einsum("bqd,bkd->bqk", q, k)
    i, j = np.arange(q.shape[1])[:, None], np.arange(k.shape[1])[None, :]
    s = np.where((j <= i) & (j > i - window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bqk,bkd->bqd", p / p.sum(-1, keepdims=True), v)


def qkv(bh, bh_kv, s, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(s + d), 3)
    return (jax.random.normal(ks[0], (bh, s, d), dtype),
            jax.random.normal(ks[1], (bh_kv, s, d), dtype),
            jax.random.normal(ks[2], (bh_kv, s, d), dtype))


@pytest.mark.parametrize("window", [1, 7, 32, 40, 64, 100, 128, 500])
def test_the_windowed_kernel_is_the_masked_softmax(window):
    """Blocks of 32 over 128 positions: windows under a block (1, 7), at one
    (32), off the block size (40, 100), of whole blocks (64), at the sequence
    (128) and over it (500: the plain causal result)."""
    q, k, v = qkv(4, 2, 128, 16)
    want = masked_softmax_attention(q, k, v, 0.25, window)
    out, _ = _flash_fwd_pallas(q, k, v, True, 0.25, 32, 32, interpret=True,
                               window=window)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(_reference_attention(q, k, v, True, 0.25, window=window)),
        want, atol=2e-5)
    if window >= 128:
        plain, _ = _flash_fwd_pallas(q, k, v, True, 0.25, 32, 32,
                                     interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))


def test_a_sequence_no_block_divides_and_a_window_off_everything():
    """200 positions in blocks of 64 (padded to 256, the padded keys masked)
    under a window of 77."""
    q, k, v = qkv(2, 2, 200, 32)
    want = masked_softmax_attention(q, k, v, 0.125, 77)
    out, _ = _flash_fwd_pallas(q, k, v, True, 0.125, 64, 64, interpret=True,
                               window=77)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)


def test_key_blocks_wholly_below_the_window_change_nothing():
    """What a skipped block holds cannot matter: with the keys and values of
    every position below the last query block's window set to NaN, the last
    block of queries comes out as before."""
    q, k, v = qkv(2, 1, 128, 16)
    out, _ = _flash_fwd_pallas(q, k, v, True, 0.25, 32, 32, interpret=True,
                               window=32)
    # queries 96..127 read keys 65..127: blocks 0 and 1 (keys 0..63) are
    # below every one of their windows
    poison = jnp.arange(128)[None, :, None] < 64
    kp, vp = jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v)
    got, _ = _flash_fwd_pallas(q, kp, vp, True, 0.25, 32, 32, interpret=True,
                               window=32)
    np.testing.assert_array_equal(np.asarray(got)[:, 96:],
                                  np.asarray(out)[:, 96:])
    assert np.isfinite(np.asarray(got)[:, 96:]).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_takes_the_window_on_both_paths(dtype):
    b, s, h, hkv, d = 2, 96, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    ref = flash_attention(q, k, v, causal=True, window=20)
    with pallas_config.force("interpret"):
        out = flash_attention(q, k, v, causal=True, window=20)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    assert not np.allclose(
        np.asarray(ref, np.float32),
        np.asarray(flash_attention(q, k, v, causal=True), np.float32),
        atol=10 * tol)


def test_the_backward_kernels_raise_on_a_window():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 16))

    def loss(q):
        return jnp.sum(flash_attention(q, q, q, causal=True, window=16))

    with pallas_config.force("interpret"):
        with pytest.raises(NotImplementedError, match="sliding window"):
            jax.grad(loss)(q)
        jax.grad(lambda q: jnp.sum(flash_attention(q, q, q, causal=True)))(q)
    # the jnp path differentiates its own masked softmax
    assert np.isfinite(np.asarray(jax.grad(loss)(q))).all()


@pytest.mark.parametrize("kw", [
    dict(causal=False), dict(kv_lens=jnp.array([8])),
    dict(dropout_p=0.1, dropout_key=jax.random.PRNGKey(0)),
])
def test_a_window_goes_with_causal_self_attention_alone(kw):
    q = jnp.zeros((1, 16, 2, 8))
    kw = {"causal": True, **kw}
    with pytest.raises(ValueError, match="sliding window"):
        flash_attention(q, q, q, window=4, **kw)
