"""The flash kernels at GPT-2's head width (d=64), in interpret mode: every
product takes its operands in the inputs' dtype and accumulates in float32,
so bf16 inputs are within bf16's rounding of the float32 reference and
float32 inputs stay as tight as before."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import pallas_config
from apex_tpu.ops.flash_attention import (
    _flash_bwd_pallas,
    _flash_fwd_pallas,
    _reference_attention,
    flash_attention,
)

D = 64
SCALE = D ** -0.5


def _inputs(s, dtype, h=2):
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    q, k, v, do = (jax.random.normal(key, (1, s, h, D), jnp.float32)
                   for key in ks)
    return tuple(x.astype(dtype) for x in (q, k, v)), do.astype(dtype)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return (float(np.linalg.norm(got - want) / np.linalg.norm(want)),
            float(np.abs(got - want).max() / np.abs(want).max()))


# s=256 is one block of each pass; 1024 is 2 x 2 forward blocks of 512 and
# 4 x 4 backward blocks of 256; 250 and 1000 are no multiple of a tile (one
# whole-extent block; blocks of 128 over keys padded to 1024 and masked)
@pytest.mark.parametrize("s", [256, 250, 1024, 1000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_causal_forward_and_backward_at_head_width_64(s, dtype):
    (q, k, v), do = _inputs(s, dtype)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=SCALE)

    with pallas_config.force("interpret"):
        out, vjp = jax.vjp(attend, q, k, v)
        grads = vjp(do)
    f32 = lambda x: x.astype(jnp.float32)
    want, ref_vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, causal=True, scale=SCALE),
        *map(f32, (q, k, v)))
    want_grads = ref_vjp(f32(do))
    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)
        for name, g, w in zip("qkv", grads, want_grads):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=2e-4, err_msg=f"d{name}")
        return
    # bf16: the output and each gradient within a few roundings of bf16
    # (2^-8 relative) of the float32 reference, by norm and elementwise
    for name, g, w in zip(("o", "dq", "dk", "dv"), (out, *grads),
                          (want, *want_grads)):
        by_norm, worst = _rel(g, w)
        assert by_norm < 6e-3 and worst < 1.6e-2, (name, by_norm, worst)


def _dots(jaxpr):
    """Every ``dot_general`` under ``jaxpr``, kernel bodies and the
    branches of ``pl.when`` included."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                found.append(eqn)
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (tuple, list))
                            else (param,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("p_drop", [0.0, 0.25])
def test_every_product_takes_the_inputs_dtype_and_accumulates_f32(dtype,
                                                                  p_drop):
    bh, s = 2, 256
    q = jax.ShapeDtypeStruct((bh, s, D), dtype)
    lse = jax.ShapeDtypeStruct((bh, s), jnp.float32)
    seed = jnp.zeros((), jnp.uint32)
    kw = dict(causal=True, scale=SCALE, block_q=128, block_k=128,
              interpret=True, p_drop=p_drop, seed=seed)
    fwd = jax.make_jaxpr(lambda q, k, v: _flash_fwd_pallas(q, k, v, **kw))(
        q, q, q)
    bwd = jax.make_jaxpr(lambda q, k, v, o, lse, do: _flash_bwd_pallas(
        q, k, v, o, lse, do, **kw))(q, q, q, q, lse, q)
    # forward: QK^T, PV; dq: QK^T, dP, dS K; dkv: QK^T, P^T dO, dP, dS^T Q
    for jaxpr, count in ((fwd, 2), (bwd, 3 + 4)):
        dots = _dots(jaxpr)
        assert len(dots) == count, [str(e) for e in dots]
        for eqn in dots:
            assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype], eqn
            assert eqn.params["preferred_element_type"] == jnp.float32, eqn
            assert eqn.outvars[0].aval.dtype == jnp.float32, eqn


def test_bf16_kernels_agree_with_the_reference_in_float32_directly():
    """The kernels themselves (no custom_vjp round them), bf16 in, against
    the float32 reference's output, log-sum-exp and gradients."""
    bh, s = 2, 1024
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v, do = (jax.random.normal(key, (bh, s, D), jnp.float32)
                   .astype(jnp.bfloat16) for key in ks)
    o, lse = _flash_fwd_pallas(q, k, v, True, SCALE, 512, 512,
                               interpret=True)
    f32 = lambda x: x.astype(jnp.float32)
    want, vjp = jax.vjp(lambda q, k, v: _reference_attention(
        q, k, v, True, SCALE), *map(f32, (q, k, v)))
    scores = SCALE * jnp.einsum("bqd,bkd->bqk", f32(q), f32(k))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(jax.nn.logsumexp(scores, -1)),
                               atol=2e-3)
    assert _rel(o, want)[0] < 6e-3
    grads = _flash_bwd_pallas(q, k, v, o, lse, do, True, SCALE, 256, 256,
                              interpret=True)
    for name, g, w in zip("qkv", grads, vjp(f32(do))):
        by_norm, worst = _rel(g, w)
        assert by_norm < 6e-3 and worst < 1.6e-2, (name, by_norm, worst)
