"""AOT Mosaic lowering checks — no TPU device required.

``jit(...).trace(...).lower(lowering_platforms=('tpu',))`` runs the full
Pallas→Mosaic lowering on any host, which is where block-shape rules,
unsupported ops, and layout constraints reject a kernel (only the final
Mosaic→binary step needs a chip). Interpret-mode tests execute the kernel
BODIES; these pin the kernels' COMPILABILITY for the real target — the
round-2 gap ("kernels never Mosaic-compiled") made CI-checkable.

Found on first run: the flash lse output rode as a (1, bq) block over
[bh, sq], violating the last-two-dims rule; it now rides [bh, sq, 1].
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.ops import pallas_config
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.ops.layer_norm import layer_norm, rms_norm
from apex_tpu.transformer.functional.fused_softmax import (
    scaled_masked_softmax,
    scaled_upper_triang_masked_softmax,
)


def lowers_for_tpu(fn, *args, kernel=None):
    """Lower ``fn`` for TPU; with ``kernel``, the traced program must call
    the Pallas kernel of that name."""
    with pallas_config.force("on"):
        traced = jax.jit(fn).trace(*args)
        assert kernel is None or kernel in str(traced.jaxpr)
        traced.lower(lowering_platforms=("tpu",))


B, S, H, D = 2, 512, 4, 128


def _qkv(h_kv=H):
    q = jnp.ones((B, S, H, D), jnp.bfloat16)
    k = jnp.ones((B, S, h_kv, D), jnp.bfloat16)
    return q, k, k


class TestFlashLowering:
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd(self, causal):
        q, k, v = _qkv()
        lowers_for_tpu(
            functools.partial(flash_attention, causal=causal), q, k, v)

    @pytest.mark.parametrize("h_kv", [H, H // 2, 1])
    def test_fwd_bwd_gqa(self, h_kv):
        q, k, v = _qkv(h_kv)

        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32))

        lowers_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    def test_varlen_fwd_bwd(self):
        q, k, v = _qkv()
        lens = jnp.full((B,), S // 2, jnp.int32)

        def loss(q, k, v):
            o = flash_attention(q, k, v, kv_lens=lens)
            return jnp.sum(o.astype(jnp.float32))

        lowers_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    def test_dropout_fwd_bwd(self):
        q, k, v = _qkv()
        key = jax.random.PRNGKey(0)

        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, dropout_p=0.1,
                                dropout_key=key)
            return jnp.sum(o.astype(jnp.float32))

        lowers_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    def test_varlen_dropout_combo_fwd_bwd(self):
        # kvlen (3-D block) and seed (2-D block) in ONE pallas_call, all
        # three kernels — the densest ref configuration
        q, k, v = _qkv()
        lens = jnp.full((B,), S // 2, jnp.int32)
        key = jax.random.PRNGKey(1)

        def loss(q, k, v):
            o = flash_attention(q, k, v, kv_lens=lens, dropout_p=0.1,
                                dropout_key=key)
            return jnp.sum(o.astype(jnp.float32))

        lowers_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    def test_small_heads_and_blocks(self):
        # d=64, sq below the default block -> one full-extent block
        q = jnp.ones((4, 192, 2, 64), jnp.bfloat16)
        lowers_for_tpu(
            functools.partial(flash_attention, causal=True), q, q, q)


class TestFlatAdamLowering:
    def test_adam_kernel(self):
        from apex_tpu.ops.fused_adam_kernel import adam_flat_pallas

        n = 1024 * 520 + 7  # forces slab padding
        g = jnp.ones((n,), jnp.float32)
        p = jnp.ones((n,), jnp.bfloat16)
        m = jnp.zeros((n,), jnp.float32)
        v = jnp.zeros((n,), jnp.float32)

        def run(g, p, m, v):
            return adam_flat_pallas(
                g, p, m, v, jnp.float32(1e-3), jnp.float32(1.0),
                b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01,
                adam_w_mode=True, bias_correction=True)

        lowers_for_tpu(run, g, p, m, v)


class TestNormLowering:
    @pytest.mark.parametrize("rows", [4096, 13])  # 13 -> padding path
    def test_layer_norm_fwd_bwd(self, rows):
        h = 1024
        x = jnp.ones((rows, h), jnp.bfloat16)
        w = jnp.ones((h,), jnp.float32)
        b = jnp.zeros((h,), jnp.float32)

        def loss(x, w, b):
            return jnp.sum(layer_norm(x, w, b, (h,)).astype(jnp.float32))

        lowers_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), x, w, b)

    def test_rms_norm_fwd_bwd(self):
        h = 1024
        x = jnp.ones((256, h), jnp.bfloat16)
        w = jnp.ones((h,), jnp.float32)

        def loss(x, w):
            return jnp.sum(rms_norm(x, w, (h,)).astype(jnp.float32))

        lowers_for_tpu(jax.grad(loss, argnums=(0, 1)), x, w)


class TestRingFlashLowering:
    """The Pallas flash kernels INSIDE shard_map (ring attention over
    'cp') — collectives lower alongside Mosaic kernels."""

    def _mesh(self):
        import numpy as np
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:4]), ("cp",))

    def test_ring_fwd(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from apex_tpu.transformer.context_parallel import ring_attention

        q = jnp.ones((2, 1024, 4, 128), jnp.bfloat16)
        f = shard_map(
            lambda q: ring_attention(q, q, q, causal=True),
            mesh=self._mesh(), in_specs=P(None, "cp"),
            out_specs=P(None, "cp"), check_vma=False)
        lowers_for_tpu(f, q)

    def test_ring_fwd_bwd(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from apex_tpu.transformer.context_parallel import ring_attention

        q = jnp.ones((2, 1024, 4, 128), jnp.bfloat16)
        mesh = self._mesh()

        def loss(q):
            def inner(q):
                o = ring_attention(q, q, q, causal=True)
                return jax.lax.psum(jnp.sum(o.astype(jnp.float32)), "cp")

            return shard_map(inner, mesh=mesh, in_specs=P(None, "cp"),
                             out_specs=P(), check_vma=False)(q)

        lowers_for_tpu(jax.grad(loss), q)


class TestMoELowering:
    def test_ep_all_to_all(self):
        import numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu.transformer.moe import (
            MoEConfig,
            init_moe_params,
            moe_mlp,
            moe_param_specs,
        )

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ("dp", "ep"))
        cfg = MoEConfig(hidden_size=128, ffn_hidden_size=256,
                        num_experts=8, top_k=2)
        params = init_moe_params(jax.random.PRNGKey(0), cfg)
        x = jnp.ones((64, 128), jnp.bfloat16)

        def fn(params, x):
            y, aux = moe_mlp(params, x, cfg, ep_axis="ep")
            return y, jax.lax.pmean(jax.lax.pmean(aux, "ep"), "dp")

        f = shard_map(fn, mesh=mesh,
                      in_specs=(moe_param_specs(cfg),
                                P(("dp", "ep"), None)),
                      out_specs=(P(("dp", "ep"), None), P()))
        lowers_for_tpu(f, params, x)


class TestSoftmaxLowering:
    def test_causal(self):
        x = jnp.ones((8, 512, 512), jnp.bfloat16)
        lowers_for_tpu(
            lambda x: scaled_upper_triang_masked_softmax(x, None, 1.0), x)

    def test_causal_bwd(self):
        x = jnp.ones((8, 512, 512), jnp.bfloat16)

        def loss(x):
            y = scaled_upper_triang_masked_softmax(x, None, 1.0)
            return jnp.sum(y.astype(jnp.float32))

        lowers_for_tpu(jax.grad(loss), x, kernel="apex_softmax_bwd")

    def test_masked(self):
        x = jnp.ones((2, 4, 256, 256), jnp.bfloat16)
        mask = jnp.zeros((2, 1, 256, 256), bool)
        lowers_for_tpu(lambda x: scaled_masked_softmax(x, mask, 0.5), x)

    @pytest.mark.parametrize("cotangent", [jnp.bfloat16, jnp.float32])
    def test_masked_bwd(self, cotangent):
        """apex_softmax_bwd through the masked rule, rectangular, with the
        cotangent in the dtype its consumer hands over."""
        x = jnp.ones((2, 4, 256, 384), jnp.bfloat16)
        mask = jnp.zeros((2, 1, 256, 384), bool)

        def loss(x):
            y = scaled_masked_softmax(x, mask, 0.5)
            return jnp.sum(y.astype(cotangent) ** 2)

        lowers_for_tpu(jax.grad(loss), x, kernel="apex_softmax_bwd")

    def test_blocked_long_sk(self, monkeypatch):
        # force the two-pass k-blocked kernels
        import apex_tpu.transformer.functional.fused_softmax as fs

        monkeypatch.setattr(fs, "_BLOCKED_BK", 256)
        x = jnp.ones((4, 512, 2048), jnp.bfloat16)
        lowers_for_tpu(
            lambda x: scaled_upper_triang_masked_softmax(x, None, 1.0), x)
