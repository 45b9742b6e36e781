"""Interpret-mode execution of every Pallas kernel body (VERDICT weak #2).

The CI mesh is CPU, so the compiled-Pallas path never runs here; these tests
force ``pallas_config.force('interpret')`` so the actual kernel bodies
(online-softmax flash attention, single-pass LN/RMS, causal/masked softmax)
execute through the Pallas interpreter and are checked for parity against
the jnp fallbacks (ref test model: tests/L0/run_fused_layer_norm in the
reference).
"""

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
from apex_tpu.ops.layer_norm import layer_norm, rms_norm
from apex_tpu.transformer.functional.fused_softmax import (
    scaled_masked_softmax,
    scaled_upper_triang_masked_softmax,
)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-6


# --------------------------------------------------------------- layer norm


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows", [48, 256, 300])  # 300 exercises row padding
@pytest.mark.parametrize("affine", [True, False])
def test_layer_norm_interpret(dtype, rows, affine):
    h = 128
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, h), dtype)
    w = b = None
    if affine:
        w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (h,), dtype)
        b = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (h,), dtype)
    ref = layer_norm(x, w, b, h)
    with pallas_config.force("interpret"):
        out = layer_norm(x, w, b, h)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("affine", [True, False])
def test_rms_norm_interpret(dtype, affine):
    rows, h = 96, 256
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, h), dtype)
    w = None
    if affine:
        w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (h,), dtype)
    ref = rms_norm(x, w, h)
    with pallas_config.force("interpret"):
        out = rms_norm(x, w, h)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_layer_norm_interpret_grads():
    """The Pallas fwd saves (mu, rstd) for the shared bwd — check the full
    custom_vjp chain matches autodiff through the jnp path."""
    h = 64
    x = jax.random.normal(jax.random.PRNGKey(0), (32, h), jnp.float32)
    w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (h,))
    b = jnp.zeros((h,))

    def f(x, w, b):
        return jnp.sum(jnp.sin(layer_norm(x, w, b, h)))

    ref = jax.grad(f, argnums=(0, 1, 2))(x, w, b)
    with pallas_config.force("interpret"):
        out = jax.grad(f, argnums=(0, 1, 2))(x, w, b)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)


@pytest.mark.parametrize("rows", [32, 13])  # 13 exercises bwd row padding
@pytest.mark.parametrize("affine", [True, False])
def test_rms_norm_interpret_grads(rows, affine):
    """The Pallas RMS bwd kernel (dx + grid-accumulated dw) vs autodiff
    through the jnp path."""
    h = 64
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, h), jnp.float32)
    w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (h,))

    if affine:
        def f(x, w):
            return jnp.sum(jnp.sin(rms_norm(x, w, h)))

        ref = jax.grad(f, argnums=(0, 1))(x, w)
        with pallas_config.force("interpret"):
            out = jax.grad(f, argnums=(0, 1))(x, w)
    else:
        def f(x):
            return jnp.sum(jnp.sin(rms_norm(x, None, h)))

        ref = (jax.grad(f)(x),)
        with pallas_config.force("interpret"):
            out = (jax.grad(f)(x),)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)


@pytest.mark.parametrize("rows", [32, 13])
def test_layer_norm_plain_interpret_grads(rows):
    h = 64
    x = jax.random.normal(jax.random.PRNGKey(2), (rows, h), jnp.float32)

    def f(x):
        return jnp.sum(jnp.cos(layer_norm(x, None, None, h)))

    ref = jax.grad(f)(x)
    with pallas_config.force("interpret"):
        out = jax.grad(f)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------- flash attention


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h_kv", [4, 2, 1])  # MHA, GQA, MQA
def test_flash_attention_interpret(causal, h_kv):
    b, s, h, d = 2, 64, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h_kv, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h_kv, d), jnp.float32)
    ref = flash_attention(q, k, v, causal=causal)
    with pallas_config.force("interpret"):
        out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_interpret_multiblock(causal):
    """Small blocks force a real k-sweep (online-softmax carry across k
    blocks) and a multi-row q grid, plus GQA block indexing."""
    bh, bh_kv, s, d = 4, 2, 128, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (bh, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (bh_kv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (bh_kv, s, d), jnp.float32)
    ref = _reference_attention(q, k, v, causal, 0.25)
    out, lse = _flash_fwd_pallas(q, k, v, causal, 0.25, 32, 32,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # lse parity vs explicit logsumexp
    s = 0.25 * np.einsum("bqd,bkd->bqk",
                         np.asarray(q), np.asarray(k).repeat(2, 0))
    if causal:
        qpos = np.arange(s.shape[1])[:, None]
        kpos = np.arange(s.shape[2])[None, :]
        s = np.where(kpos <= qpos, s, -1e30)
    ref_lse = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) + s.max(-1)
    np.testing.assert_allclose(np.asarray(lse), ref_lse, atol=1e-4)


def test_flash_attention_interpret_ragged():
    """sq != sk and sizes that don't hit the preferred block."""
    bh, sq, sk, d = 2, 48, 80, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (bh, sq, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, sk, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, sk, d), jnp.float32)
    ref = _reference_attention(q, k, v, False, 0.125)
    out, _ = _flash_fwd_pallas(q, k, v, False, 0.125, 32, 32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ------------------------------------------------- flash attention backward


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h_kv", [4, 2, 1])  # MHA, GQA, MQA
def test_flash_attention_bwd_interpret(causal, h_kv):
    """Pallas dq/dk/dv kernels vs autodiff through the jnp reference."""
    b, s, h, d = 2, 64, 4, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h_kv, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h_kv, d), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, causal=causal)
                               .astype(jnp.float32)))

    ref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    with pallas_config.force("interpret"):
        out = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for name, o, r in zip("q k v".split(), out, ref):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("s,causal", [(96, True), (80, True), (80, False)])
def test_flash_attention_bwd_interpret_multiblock(s, causal):
    """Small blocks: dq k-sweep and dk/dv q-sweep accumulate across a real
    grid; GQA rep accumulation across shared query heads. s=80 does not
    divide into 32-row blocks, so q/k/v ride padded to 96 and the padded
    keys must be masked out of dq/dk/dv."""
    from apex_tpu.ops.flash_attention import _flash_bwd_pallas

    bh, bh_kv, d = 4, 2, 16
    ks = [jax.random.normal(jax.random.PRNGKey(i), (bh, s, d)) for i in
          range(2)]
    q, do = ks
    k = jax.random.normal(jax.random.PRNGKey(2), (bh_kv, s, d))
    v = jax.random.normal(jax.random.PRNGKey(3), (bh_kv, s, d))

    o, vjp = jax.vjp(
        lambda q, k, v: _reference_attention(q, k, v, causal, 0.25), q, k, v)
    ref = vjp(do)
    _, lse = _flash_fwd_pallas(q, k, v, causal, 0.25, 32, 32, interpret=True)
    out = _flash_bwd_pallas(q, k, v, o, lse, do, causal, 0.25, 32, 32,
                            interpret=True)
    for name, got, want in zip("q k v".split(), out, ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, err_msg=f"d{name}")


def test_flash_attention_bwd_no_full_matrix():
    """The grad jaxpr must contain no [sq, sk] intermediate — the memory
    claim the docstring makes (VERDICT weak #5)."""
    bh, s, d = 2, 160, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (bh, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, s, d), jnp.float32)

    from apex_tpu.ops.flash_attention import _flash

    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v, True, 0.25))

    with pallas_config.force("interpret"):
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    bad = []

    def walk(jxp):
        for eqn in jxp.eqns:
            if "pallas" in eqn.primitive.name:
                continue  # kernel-internal VMEM blocks are the point
            for var in eqn.outvars:
                shape = getattr(var.aval, "shape", ())
                if len(shape) >= 2 and shape[-2:] == (s, s):
                    bad.append((eqn.primitive.name, shape))
            for param in eqn.params.values():
                if hasattr(param, "jaxpr"):
                    walk(param.jaxpr)
                elif hasattr(param, "eqns"):
                    walk(param)

    walk(jaxpr.jaxpr)
    assert not bad, f"full [sq, sk] intermediates in grad jaxpr: {bad}"


def test_flash_attention_interpret_bf16():
    b, s, h, d = 1, 64, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d), jnp.bfloat16)
    ref = flash_attention(q, k, v, causal=True)
    with pallas_config.force("interpret"):
        out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


# ------------------------------------------------------------ fused softmax


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_causal_softmax_interpret(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 48), dtype)
    ref = scaled_upper_triang_masked_softmax(x, None, 0.5)
    with pallas_config.force("interpret"):
        out = scaled_upper_triang_masked_softmax(x, None, 0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype))


def test_causal_softmax_interpret_rect():
    """sk > sq (cached/inference layout): triangle offset path."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64), jnp.float32)
    ref = scaled_upper_triang_masked_softmax(x, None, 1.3)
    with pallas_config.force("interpret"):
        out = scaled_upper_triang_masked_softmax(x, None, 1.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_softmax_interpret(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 16, 48), dtype)
    mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3, (2, 1, 16, 48))
    ref = scaled_masked_softmax(x, mask, 0.7)
    with pallas_config.force("interpret"):
        out = scaled_masked_softmax(x, mask, 0.7)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype))


def test_softmax_interpret_grads():
    """custom_vjp bwd consumes the Pallas fwd's saved y."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 24), jnp.float32)

    def f(x):
        return jnp.sum(scaled_upper_triang_masked_softmax(x, None, 0.9) ** 2)

    ref = jax.grad(f)(x)
    with pallas_config.force("interpret"):
        out = jax.grad(f)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ------------------------------------------------- fused softmax backward


def _probs_and_cotangent(kind, dtype):
    """Saved probabilities as the forward would hand them to the backward
    rule, and a cotangent of the same shape."""
    shape = {"causal_square": (3, 32, 32), "causal_rect": (2, 16, 64),
             "masked": (2, 2, 16, 48)}[kind]
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    if kind == "masked":
        mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3,
                                    (2, 1, 16, 48))
        y = scaled_masked_softmax(x, mask, 0.7)
    else:
        y = scaled_upper_triang_masked_softmax(x, None, 0.7)
    g = jax.random.normal(jax.random.PRNGKey(2), shape, dtype)
    return y, g


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", ["causal_square", "causal_rect", "masked"])
def test_softmax_bwd_kernel_matches_math(kind, dtype):
    """One kernel for both rules: no mask needed, y is 0 where masked."""
    from apex_tpu.transformer.functional import fused_softmax as fs

    y, g = _probs_and_cotangent(kind, dtype)
    ref = fs._softmax_bwd_math(0.7, y, g)
    with pallas_config.force("interpret"):
        out = fs._pallas_softmax_bwd(0.7, y, g)
    assert out.dtype == y.dtype and out.shape == y.shape
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-6 if dtype == jnp.float32 else 1e-3)


def test_softmax_bwd_kernel_takes_the_cotangent_as_handed_over():
    """bf16 probabilities with a float32 cotangent: dx comes back in the
    probabilities' dtype, computed from the unrounded cotangent."""
    from apex_tpu.transformer.functional import fused_softmax as fs

    y, g = _probs_and_cotangent("causal_square", jnp.bfloat16)
    g = g.astype(jnp.float32) * 1.001
    ref = fs._softmax_bwd_math(1.3, y, g)
    with pallas_config.force("interpret"):
        out = fs._pallas_softmax_bwd(1.3, y, g)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


def _calls_softmax_bwd_kernel(fn, *args):
    return "apex_softmax_bwd" in str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("kind", ["causal", "masked"])
def test_softmax_grad_through_kernel_matches_autodiff(kind):
    """jax.grad through the public ops in interpret mode (Pallas forward
    and backward) against autodiff of the plain jnp softmax."""
    if kind == "causal":
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 40),
                              jnp.float32)
        mask = jnp.arange(40)[None, :] > jnp.arange(24)[:, None] + 16
        fused = lambda x: scaled_upper_triang_masked_softmax(x, None, 0.9)
    else:
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 16, 48),
                              jnp.float32)
        mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3,
                                    (2, 1, 16, 48))
        fused = lambda x: scaled_masked_softmax(x, mask, 0.9)
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape, jnp.float32)

    def plain(x):
        return jax.nn.softmax(jnp.where(mask, -10000.0, x * 0.9), axis=-1)

    ref = jax.grad(lambda x: jnp.sum(plain(x) * w))(x)
    loss = lambda x: jnp.sum(fused(x) * w)
    with pallas_config.force("interpret"):
        assert _calls_softmax_bwd_kernel(jax.grad(loss), x)
        out = jax.grad(loss)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_softmax_bwd_long_rows_keep_the_jnp_math(monkeypatch):
    """sk beyond the whole-row limit: blocked forward kernels, and the
    backward stays _softmax_bwd_math (no kernel, same gradient)."""
    from apex_tpu.transformer.functional import fused_softmax as fs

    x = jax.random.normal(jax.random.PRNGKey(4), (2, 96, 96), jnp.float32)
    loss = lambda x: jnp.sum(
        scaled_upper_triang_masked_softmax(x, None, 0.9) ** 2)
    ref = jax.grad(loss)(x)
    with pallas_config.force("interpret"):
        assert _calls_softmax_bwd_kernel(jax.grad(loss), x)
        monkeypatch.setattr(fs, "_WHOLE_ROW_MAX_SK", 64)
        monkeypatch.setattr(fs, "_BLOCKED_BK", 32)
        assert not _calls_softmax_bwd_kernel(jax.grad(loss), x)
        out = jax.grad(loss)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_softmax_bwd_off_tpu_keeps_the_jnp_math():
    """'auto' on the CPU backend: neither direction takes a kernel."""
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 32), jnp.float32)
    loss = lambda x: jnp.sum(
        scaled_upper_triang_masked_softmax(x, None, 0.9) ** 2)
    assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(loss))(x))


@pytest.mark.multidevice(n=4)
def test_softmax_bwd_kernel_under_shard_map_check_vma():
    """The four-chip cell's shape of use: forward and backward kernels
    inside shard_map with check_vma on, rows split over dp. check_vma types
    the kernel's output while tracing (out_struct carries the inputs' vma),
    so the gradient is traced and lowered for TPU on the CPU mesh; the
    interpreter cannot run kernel bodies there (it binds a kernel's own
    constants against varying operands), the values are the tests' above."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    x = jnp.ones((8, 128, 256), jnp.bfloat16)

    def grad(x, w):
        return jax.grad(lambda x: jnp.sum(
            scaled_upper_triang_masked_softmax(x, None, 0.8) * w))(x)

    sharded = jax.jit(jax.shard_map(
        grad, mesh=mesh, in_specs=(P("dp"), P("dp")), out_specs=P("dp"),
        check_vma=True))
    with pallas_config.force("on"):
        traced = sharded.trace(x, x)
        assert "apex_softmax_bwd" in str(traced.jaxpr)
        assert traced.out_info.shape == x.shape
        traced.lower(lowering_platforms=("tpu",))


# -------------------------------------------------- k-blocked long softmax


def test_blocked_causal_softmax_matches(monkeypatch):
    """sk beyond the whole-row VMEM limit takes the two-pass k-blocked
    path (threshold lowered so interpret mode stays fast)."""
    from apex_tpu.transformer.functional import fused_softmax as fs

    monkeypatch.setattr(fs, "_WHOLE_ROW_MAX_SK", 64)
    monkeypatch.setattr(fs, "_BLOCKED_BK", 32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 96, 96), jnp.float32)
    ref = scaled_upper_triang_masked_softmax(x, None, 0.7)
    with pallas_config.force("interpret"):
        out = scaled_upper_triang_masked_softmax(x, None, 0.7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_blocked_causal_softmax_rect(monkeypatch):
    from apex_tpu.transformer.functional import fused_softmax as fs

    monkeypatch.setattr(fs, "_WHOLE_ROW_MAX_SK", 64)
    monkeypatch.setattr(fs, "_BLOCKED_BK", 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 128), jnp.float32)
    ref = scaled_upper_triang_masked_softmax(x, None, 1.1)
    with pallas_config.force("interpret"):
        out = scaled_upper_triang_masked_softmax(x, None, 1.1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_blocked_masked_softmax_matches(monkeypatch):
    from apex_tpu.transformer.functional import fused_softmax as fs

    monkeypatch.setattr(fs, "_WHOLE_ROW_MAX_SK", 64)
    monkeypatch.setattr(fs, "_BLOCKED_BK", 32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 16, 96),
                          jnp.bfloat16)
    mask = jax.random.bernoulli(jax.random.PRNGKey(3), 0.3, (2, 1, 16, 96))
    ref = scaled_masked_softmax(x, mask, 0.5)
    with pallas_config.force("interpret"):
        out = scaled_masked_softmax(x, mask, 0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)


def test_blocked_softmax_grads(monkeypatch):
    from apex_tpu.transformer.functional import fused_softmax as fs

    monkeypatch.setattr(fs, "_WHOLE_ROW_MAX_SK", 64)
    monkeypatch.setattr(fs, "_BLOCKED_BK", 32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 96, 96), jnp.float32)

    def f(x):
        return jnp.sum(scaled_upper_triang_masked_softmax(x, None, 0.9) ** 2)

    ref = jax.grad(f)(x)
    with pallas_config.force("interpret"):
        out = jax.grad(f)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_blocked_softmax_very_negative_rows(monkeypatch):
    """Rows whose true max is below the mask fill value (-10000) must still
    normalize — regression for seeding the running max with _MASK_FILL
    instead of -inf (which zeroed the denominator -> NaN)."""
    from apex_tpu.transformer.functional import fused_softmax as fs

    monkeypatch.setattr(fs, "_WHOLE_ROW_MAX_SK", 32)
    monkeypatch.setattr(fs, "_BLOCKED_BK", 16)
    x = jnp.full((1, 8, 64), -30000.0, jnp.float32)
    ref = scaled_masked_softmax(x, None, 1.0)  # uniform 1/64
    with pallas_config.force("interpret"):
        out = fs._pallas_blocked(x, None, 1.0, causal=False)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_blocked_softmax_awkward_sk_falls_back(monkeypatch):
    """A long sk with no usable block divisor (prime) must not take the
    blocked kernel (lane blocks of width 1); _pallas_ok rejects it and the
    masked dispatch lands on the identical-math jnp path."""
    from apex_tpu.transformer.functional import fused_softmax as fs

    assert not fs._pallas_ok(8, 16411)  # prime > _WHOLE_ROW_MAX_SK
    # exercise the actual dispatch: thresholds lowered so sk=97 (prime) is
    # "long"; the blocked kernel would need bk >= 128 (impossible) and a
    # broken fallback would send a degenerate grid into pallas_call
    monkeypatch.setattr(fs, "_WHOLE_ROW_MAX_SK", 64)
    monkeypatch.setattr(fs, "_BLOCKED_BK", 32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 4, 97))
    mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3, (1, 1, 4, 97))
    assert not fs._pallas_ok(4, 97)
    with pallas_config.force("interpret"):
        out = scaled_masked_softmax(x, mask, 1.0)
    # independent reference (not the function under test)
    ref = jax.nn.softmax(jnp.where(mask, -10000.0, x), axis=-1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_blocked_softmax_first_block_all_neg_inf(monkeypatch):
    """A row whose FIRST k-block is entirely -inf (additive -inf masks fold
    into scores) must recover once later blocks hold finite keys —
    regression for exp(-inf - -inf) = NaN in the running stats."""
    from apex_tpu.transformer.functional import fused_softmax as fs

    monkeypatch.setattr(fs, "_WHOLE_ROW_MAX_SK", 32)
    monkeypatch.setattr(fs, "_BLOCKED_BK", 16)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, 64), jnp.float32)
    x = x.at[:, :, :16].set(-jnp.inf)  # first block fully masked
    ref = jax.nn.softmax(x, axis=-1)
    with pallas_config.force("interpret"):
        out = fs._pallas_blocked(x, None, 1.0, causal=False)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ------------------------------------------------------ flat adam kernel


class TestFlatAdamKernel:
    """ops/fused_adam_kernel.py — the multi_tensor_adam.cu analog over the
    packed flat buffer."""

    @pytest.mark.parametrize("n", [100, 8192, 1024 * 520 + 7])
    @pytest.mark.parametrize("adam_w", [True, False])
    def test_matches_math(self, n, adam_w):
        from apex_tpu.ops.fused_adam_kernel import adam_flat_pallas
        from apex_tpu.optimizers import _math

        k = jax.random.PRNGKey(0)
        g = jax.random.normal(k, (n,), jnp.float32)
        p = jax.random.normal(jax.random.fold_in(k, 1), (n,), jnp.float32)
        m = jnp.zeros((n,), jnp.float32) + 0.1
        v = jnp.zeros((n,), jnp.float32) + 0.2
        kw = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01,
                  adam_w_mode=adam_w, bias_correction=True)
        d, mo, vo = adam_flat_pallas(
            g, p, m, v, jnp.float32(1e-3), jnp.float32(3.0),
            interpret=True, **kw)
        dw, mw, vw = _math.adam_step(
            g, p, m, v, lr=1e-3, step=3.0, **kw)
        # fp32 association differs between the interpreter's evaluation
        # and XLA's fused chain by ~1 ulp
        np.testing.assert_allclose(np.asarray(d), np.asarray(dw),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(mo), np.asarray(mw),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(vo), np.asarray(vw),
                                   rtol=1e-5, atol=1e-7)

    def test_bf16_params(self):
        from apex_tpu.ops.fused_adam_kernel import adam_flat_pallas

        n = 4096
        g = jnp.ones((n,), jnp.float32) * 1e-3
        p = jnp.ones((n,), jnp.bfloat16)
        m = jnp.zeros((n,), jnp.float32)
        v = jnp.zeros((n,), jnp.float32)
        d, mo, vo = adam_flat_pallas(
            g, p, m, v, jnp.float32(1e-3), jnp.float32(1.0),
            interpret=True, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
            adam_w_mode=True, bias_correction=True)
        assert d.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(d, np.float32)).all()

    def test_fused_adam_flat_kernel_path(self):
        """fused_adam(flat=True) with the kernel on (interpret) matches
        the XLA flat path step for step."""
        from apex_tpu.optimizers import fused_adam

        params = {"a": jax.random.normal(jax.random.PRNGKey(0), (300, 7)),
                  "b": jnp.ones((33,), jnp.bfloat16)}
        grads = jax.tree_util.tree_map(
            lambda p: jnp.full_like(p, 1e-2), params)
        with pallas_config.force("interpret"):
            txk = fused_adam(lr=1e-2, weight_decay=0.01, flat=True,
                             use_kernel=True)
            sk = txk.init(params)
            uk, sk = txk.update(grads, sk, params)
        txx = fused_adam(lr=1e-2, weight_decay=0.01, flat=True,
                         use_kernel=False)
        sx = txx.init(params)
        ux, sx = txx.update(grads, sx, params)
        for key in params:
            np.testing.assert_allclose(
                np.asarray(uk[key], np.float32),
                np.asarray(ux[key], np.float32), rtol=1e-3, atol=1e-6)
