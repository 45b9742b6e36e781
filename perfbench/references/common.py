"""What the plain references share: seeded keys and matmuls at a stated precision.

A reference computes in float32 with every matmul at `highest` precision (on a
TPU a float32 matmul otherwise runs in bfloat16 passes). The controls of
"How `correct` is decided" put the same reference at a lower precision: each
matmul operand rounded to bfloat16, or to fp8 (e4m3, scaled by its largest
magnitude) before a float32 product. Nothing here imports the program.
"""

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "fp8")
_E4M3_MAX = 448.0


def seed_words(seed):
    """A seed of any size as two uint32 words, so that it can be an argument
    of a jitted function (and not a constant that changes the program)."""
    seed = int(seed)
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def key_from_words(lo, hi):
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _round(x, precision):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        rounded = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        scale = _E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
        rounded = (x * scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) / scale
    else:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    # straight through: the backward pass sees the rounded operands too
    return x + jax.lax.stop_gradient(rounded - x)


def matmul(a, b, precision):
    return jnp.matmul(_round(a.astype(jnp.float32), precision),
                      _round(b.astype(jnp.float32), precision),
                      precision=jax.lax.Precision.HIGHEST)


def einsum(spec, a, b, precision):
    return jnp.einsum(spec, _round(a.astype(jnp.float32), precision),
                      _round(b.astype(jnp.float32), precision),
                      precision=jax.lax.Precision.HIGHEST)
