"""Contrib module tests (mirrors ref apex/contrib/test/* strategy: parity
vs plain implementations on small shapes)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from apex_tpu.contrib.clip_grad import clip_grad_norm_
from apex_tpu.contrib.conv_bias_relu import ConvBias, ConvBiasMaskReLU, ConvBiasReLU
from apex_tpu.contrib.fmha import fmha, fmha_packed_qkv
from apex_tpu.contrib.focal_loss import focal_loss
from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC
from apex_tpu.contrib.layer_norm import FastLayerNorm, fast_layer_norm
from apex_tpu.contrib.multihead_attn import EncdecMultiheadAttn, SelfMultiheadAttn
from apex_tpu.contrib.peer_memory import halo_exchange_1d
from apex_tpu.contrib.sparsity import ASP, create_mask, mn_1d_mask
from apex_tpu.contrib.optimizers import distributed_fused_adam
from apex_tpu.contrib.transducer import TransducerJoint, transducer_loss
from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
from apex_tpu.optimizers import fused_adam


class TestXentropy:
    def test_matches_plain_ce(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
        labels = jax.random.randint(jax.random.PRNGKey(1), (8,), 1, 32)
        got = softmax_cross_entropy_loss(logits, labels)
        want = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                    labels[:, None], 1)[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5)

    def test_smoothing_and_padding(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        labels = jnp.array([0, 3, 5, 7])  # first = padding_idx
        loss = softmax_cross_entropy_loss(logits, labels, smoothing=0.1)
        assert float(loss[0]) == 0.0
        v = logits.shape[-1]
        lp = jax.nn.log_softmax(logits)
        want = -(0.9 * jnp.take_along_axis(lp, labels[:, None], 1)[:, 0]
                 + 0.1 * jnp.mean(lp, axis=-1))
        np.testing.assert_allclose(np.asarray(loss[1:]), np.asarray(want[1:]),
                                   rtol=1e-5)

    def test_grad_matches_autodiff(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        labels = jnp.array([2, 3, 0, 7])

        def fused(lg):
            return jnp.sum(softmax_cross_entropy_loss(lg, labels,
                                                      smoothing=0.2))

        def plain(lg):
            lp = jax.nn.log_softmax(lg)
            nll = -jnp.take_along_axis(lp, labels[:, None], 1)[:, 0]
            sm = -jnp.mean(lp, axis=-1)
            per = 0.8 * nll + 0.2 * sm
            return jnp.sum(jnp.where(labels == 0, 0.0, per))

        np.testing.assert_allclose(np.asarray(jax.grad(fused)(logits)),
                                   np.asarray(jax.grad(plain)(logits)),
                                   rtol=1e-4, atol=1e-5)


class TestClipFocal:
    def test_clip_grad_norm(self):
        g = {"a": jnp.full((4,), 3.0), "b": jnp.full((9,), 4.0)}
        clipped, norm = clip_grad_norm_(g, 5.0)
        np.testing.assert_allclose(float(norm), np.sqrt(4 * 9 + 9 * 16),
                                   rtol=1e-5)
        total = jnp.sqrt(sum(jnp.sum(x ** 2) for x in
                             jax.tree_util.tree_leaves(clipped)))
        np.testing.assert_allclose(float(total), 5.0, rtol=1e-4)

    def test_focal_loss_reduces_to_weighted_ce_at_gamma0(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (6, 10))
        targets = jnp.array([0, 1, 2, -1, 4, 5])
        lf = focal_loss(logits, targets, jnp.asarray(5.0), 10, alpha=0.25,
                        gamma=0.0)
        onehot = jax.nn.one_hot(jnp.maximum(targets, 0), 10)
        onehot = jnp.where((targets >= 0)[:, None], onehot, 0.0)
        a = 0.25 * onehot + 0.75 * (1 - onehot)
        bce = a * (jnp.maximum(logits, 0) - logits * onehot
                   + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        np.testing.assert_allclose(float(lf), float(jnp.sum(bce) / 5.0),
                                   rtol=1e-5)

    def test_focal_gamma_downweights_easy(self):
        logits = jnp.array([[8.0, -8.0]])  # confidently correct for class 0
        t = jnp.array([0])
        easy = focal_loss(logits, t, jnp.asarray(1.0), 2, 0.5, 2.0)
        hard = focal_loss(-logits, t, jnp.asarray(1.0), 2, 0.5, 2.0)
        assert float(easy) < float(hard) / 100


class TestLayerNormConv:
    def test_fast_layer_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        g, b = jnp.ones(64) * 1.5, jnp.full((64,), 0.25)
        got = fast_layer_norm(x, g, b)
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        want = (x - mu) / jnp.sqrt(var + 1e-5) * g + b
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        ln = FastLayerNorm(64)
        v = ln.init(jax.random.PRNGKey(1), x)
        np.testing.assert_allclose(np.asarray(ln.apply(v, x)),
                                   np.asarray(fast_layer_norm(
                                       x, jnp.ones(64), jnp.zeros(64))),
                                   rtol=1e-4, atol=1e-5)

    def test_conv_bias_relu(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 3))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 5)) * 0.2
        b = jnp.linspace(-1, 1, 5)
        y = ConvBiasReLU(x, w, b, padding=1, stride=1)
        assert y.shape == (2, 8, 8, 5)
        assert float(jnp.min(y)) >= 0.0
        y2 = ConvBias(x, w, b, padding=1, stride=2)
        assert y2.shape == (2, 4, 4, 5)
        mask = jnp.zeros((2, 8, 8, 5)).at[:, :4].set(1.0)
        y3 = ConvBiasMaskReLU(x, w, b, mask, padding=1, stride=1)
        np.testing.assert_allclose(np.asarray(y3[:, 4:]), 0.0)

    def test_groupbn_fuse_relu_and_addrelu(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 8))
        bn = BatchNorm2d_NHWC(8, fuse_relu=True, bn_group=1)
        v = bn.init(jax.random.PRNGKey(1), x)
        y = bn.apply(v, x, mutable=["batch_stats"])[0]
        assert float(jnp.min(y)) >= 0.0
        z = jnp.ones_like(x)
        bn2 = BatchNorm2d_NHWC(8)
        v2 = bn2.init(jax.random.PRNGKey(1), x)
        y2 = bn2.apply(v2, x, z, mutable=["batch_stats"])[0]
        assert float(jnp.min(y2)) >= 0.0  # add+relu path


class TestAttention:
    def test_fmha_matches_softmax_attention(self):
        b, s, h, d = 2, 64, 4, 32
        q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d))
        got = fmha(q, k, v, causal=True)
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        mask = jnp.tril(jnp.ones((s, s), bool))
        s_ = jnp.where(mask, s_, -1e30)
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s_), v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def test_fmha_gqa_matches_repeat(self):
        b, s, h, hkv, d = 2, 32, 8, 2, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, d))
        got = fmha(q, k, v, causal=True)
        kr = jnp.repeat(k, h // hkv, axis=2)
        vr = jnp.repeat(v, h // hkv, axis=2)
        want = fmha(q, kr, vr, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_self_mha_key_padding_excludes_keys(self):
        """Changing a PADDED key must not change any output; semantics match
        a manual pre-softmax key mask."""
        s, b, h = 8, 2, 16
        x = jax.random.normal(jax.random.PRNGKey(0), (s, b, h))
        mask = jnp.zeros((b, s), bool).at[:, 6:].set(True)
        m = SelfMultiheadAttn(hidden_dim=h, heads=2)
        v = m.init(jax.random.PRNGKey(1), x)
        y1 = m.apply(v, x, key_padding_mask=mask)
        x2 = x.at[7].add(100.0)  # perturb a padded position's input...         # (its QUERY row changes, but other rows must not)
        y2 = m.apply(v, x2, key_padding_mask=mask)
        np.testing.assert_allclose(np.asarray(y1[:6]), np.asarray(y2[:6]),
                                   rtol=1e-4, atol=1e-5)

    def test_self_mha_bool_attn_mask_matches_manual(self):
        """Causal bool attn_mask (True = masked) must match manually-masked
        softmax attention (ref self_multihead_attn.py:144 mask support)."""
        s, b, h, heads = 8, 2, 16, 2
        d = h // heads
        x = jax.random.normal(jax.random.PRNGKey(0), (s, b, h))
        causal = jnp.triu(jnp.ones((s, s), bool), k=1)
        m = SelfMultiheadAttn(hidden_dim=h, heads=heads)
        var = m.init(jax.random.PRNGKey(1), x)
        got = m.apply(var, x, attn_mask=causal)

        # manual reference: same params, explicit masked softmax
        qkv = x @ var["params"]["qkv_proj"]["kernel"]
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def hf(t):
            return t.transpose(1, 0, 2).reshape(b, s, heads, d)

        q, k, v = hf(q), hf(k), hf(v)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        scores = jnp.where(causal[None, None], -jnp.inf, scores)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        want = (o.reshape(b, s, h).transpose(1, 0, 2)
                @ var["params"]["out_proj"]["kernel"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def test_self_mha_additive_attn_mask(self):
        """A -inf additive float mask behaves like the bool mask."""
        s, b, h = 8, 2, 16
        x = jax.random.normal(jax.random.PRNGKey(0), (s, b, h))
        causal_bool = jnp.triu(jnp.ones((s, s), bool), k=1)
        causal_add = jnp.where(causal_bool, -jnp.inf, 0.0).astype(x.dtype)
        m = SelfMultiheadAttn(hidden_dim=h, heads=2)
        var = m.init(jax.random.PRNGKey(1), x)
        y_bool = m.apply(var, x, attn_mask=causal_bool)
        y_add = m.apply(var, x, attn_mask=causal_add)
        np.testing.assert_allclose(np.asarray(y_bool), np.asarray(y_add),
                                   rtol=1e-5, atol=1e-6)

    def test_self_mha_int_attn_mask_treated_as_bool(self):
        """torch-style byte masks (1 = masked) must behave like bool masks,
        not be added to the scores."""
        s, b, h = 8, 2, 16
        x = jax.random.normal(jax.random.PRNGKey(0), (s, b, h))
        causal_bool = jnp.triu(jnp.ones((s, s), bool), k=1)
        causal_int = causal_bool.astype(jnp.uint8)
        m = SelfMultiheadAttn(hidden_dim=h, heads=2)
        var = m.init(jax.random.PRNGKey(1), x)
        np.testing.assert_allclose(
            np.asarray(m.apply(var, x, attn_mask=causal_int)),
            np.asarray(m.apply(var, x, attn_mask=causal_bool)),
            rtol=1e-6, atol=1e-7)

    def test_self_mha_attn_mask_with_key_padding(self):
        """attn_mask composes with key_padding_mask."""
        s, b, h = 8, 2, 16
        x = jax.random.normal(jax.random.PRNGKey(0), (s, b, h))
        causal = jnp.triu(jnp.ones((s, s), bool), k=1)
        pad = jnp.zeros((b, s), bool).at[:, 6:].set(True)
        m = SelfMultiheadAttn(hidden_dim=h, heads=2)
        var = m.init(jax.random.PRNGKey(1), x)
        y1 = m.apply(var, x, key_padding_mask=pad, attn_mask=causal)
        x2 = x.at[7].add(100.0)  # padded key perturbation is invisible
        y2 = m.apply(var, x2, key_padding_mask=pad, attn_mask=causal)
        np.testing.assert_allclose(np.asarray(y1[:6]), np.asarray(y2[:6]),
                                   rtol=1e-4, atol=1e-5)

    def test_fmha_packed(self):
        qkv = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 3, 4, 8))
        out = fmha_packed_qkv(qkv)
        assert out.shape == (2, 16, 4, 8)

    def test_fmha_varlen_masks_padding(self):
        """cu_seqlens/seqlens must exclude padded keys (ref fmha varlen):
        output for the valid prefix equals attention over the truncated
        sequence, and padded query rows are zeroed."""
        from apex_tpu.contrib.fmha import FMHAFun

        b, s, h, d = 2, 12, 2, 8
        qkv = jax.random.normal(jax.random.PRNGKey(0), (b, s, 3, h, d))
        seqlens = jnp.array([12, 7])
        cu = jnp.array([0, 12, 19])
        out = FMHAFun.apply(qkv, cu_seqlens=cu)
        out2 = FMHAFun.apply(qkv, seqlens=seqlens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                                   rtol=1e-6)
        # batch 1, valid rows == attention over the 7-token slice
        want = fmha(qkv[1:2, :7, 0], qkv[1:2, :7, 1], qkv[1:2, :7, 2])
        np.testing.assert_allclose(np.asarray(out[1, :7]),
                                   np.asarray(want[0]), rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(out[1, 7:]), 0.0)
        # full-length batch 0 matches the unmasked kernel
        full = fmha(qkv[0:1, :, 0], qkv[0:1, :, 1], qkv[0:1, :, 2])
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(full[0]),
                                   rtol=1e-4, atol=1e-5)

    def test_self_mha_shapes_and_norm_add(self):
        s, b, h = 12, 2, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (s, b, h))
        for norm_add in (False, True):
            m = SelfMultiheadAttn(hidden_dim=h, heads=4,
                                  include_norm_add=norm_add)
            v = m.init(jax.random.PRNGKey(1), x)
            y = m.apply(v, x)
            assert y.shape == (s, b, h)

    def test_encdec_mha(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (6, 2, 16))
        kv = jax.random.normal(jax.random.PRNGKey(1), (9, 2, 16))
        m = EncdecMultiheadAttn(hidden_dim=16, heads=2)
        v = m.init(jax.random.PRNGKey(2), q, kv)
        y = m.apply(v, q, kv)
        assert y.shape == (6, 2, 16)


class TestSparsity:
    def test_mn_1d_mask_density_and_selection(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
        m = mn_1d_mask(w, 4, 2)
        assert float(jnp.mean(m.astype(jnp.float32))) == 0.5
        groups = jnp.abs(w).reshape(16, 8, 4)
        kept = jnp.abs(w * m).reshape(16, 8, 4)
        # the kept magnitudes are the top-2 of each group
        np.testing.assert_allclose(
            np.asarray(jnp.sort(kept, -1)[..., 2:]),
            np.asarray(jnp.sort(groups, -1)[..., 2:]), rtol=1e-6)

    def test_asp_masked_training_preserves_sparsity(self):
        params = {"dense": {"w": jax.random.normal(jax.random.PRNGKey(0),
                                                   (8, 16))}}
        params, masks = ASP.init_model_for_pruning(params)
        tx = ASP.init_optimizer_for_pruning(fused_adam(lr=0.1), masks)
        state = tx.init(params)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))

        def loss(p):
            return jnp.mean((x @ p["dense"]["w"] - 1.0) ** 2)

        import optax
        for _ in range(3):
            g = jax.grad(loss)(params)
            u, state = tx.update(g, state, params)
            params = optax.apply_updates(params, u)
        w = params["dense"]["w"]
        density = float(jnp.mean((w != 0).astype(jnp.float32)))
        assert density <= 0.5 + 1e-6

    def test_2d_pattern(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (16, 16))
        m = create_mask(w, "m4n2_2d_best")
        assert float(jnp.mean(m.astype(jnp.float32))) <= 0.5

    def test_permutation_search_beats_naive(self):
        # adversarial layout (ref permutation_lib.py's motivating case):
        # all big channels packed into the same m=4 groups, so naive m4n2
        # must drop half of them; a permutation spreads them out
        from apex_tpu.contrib.sparsity import (
            find_channel_permutation,
            permuted_mn_mask,
            retained_magnitude,
        )

        rng = np.random.default_rng(0)
        big = rng.normal(size=(8, 8)) * 10.0
        small = rng.normal(size=(8, 24)) * 0.1
        w = jnp.asarray(np.concatenate([big, small], axis=1))

        naive = mn_1d_mask(w, 4, 2)
        mask, perm = permuted_mn_mask(w, 4, 2)
        r_naive = retained_magnitude(w, naive)
        r_perm = retained_magnitude(w, mask)
        assert r_perm > r_naive, (r_perm, r_naive)
        # permuted mask is still 2-of-4 under the found permutation
        perm_mask = np.asarray(mask)[:, perm].reshape(8, 8, 4)
        assert (perm_mask.sum(-1) == 2).all()
        assert sorted(perm.tolist()) == list(range(32))

    def test_permutation_identity_on_uniform(self):
        # permutation can never LOSE magnitude vs naive
        from apex_tpu.contrib.sparsity import (
            permuted_mn_mask,
            retained_magnitude,
        )

        w = jax.random.normal(jax.random.PRNGKey(3), (16, 32))
        naive = mn_1d_mask(w, 4, 2)
        mask, _ = permuted_mn_mask(w, 4, 2)
        assert (retained_magnitude(w, mask)
                >= retained_magnitude(w, naive) - 1e-6)

    def test_asp_allow_permutation(self):
        params = {"w": jax.random.normal(jax.random.PRNGKey(4), (8, 16))}
        masks = ASP.compute_sparse_masks(params, allow_permutation=True)
        dens = float(jnp.mean(masks["w"].astype(jnp.float32)))
        assert dens == 0.5


class TestDistributedFusedAdam:
    def test_matches_plain_adam(self):
        """ZeRO-sharded update == replicated fused adam update."""
        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (37,)),
                  "b": jnp.ones((5,))}
        grads = {"w": jnp.full((37,), 0.5), "b": jnp.full((5,), -0.25)}

        tx = distributed_fused_adam(lr=1e-2, axis_name="dp")

        def run(params, grads):
            state = tx.init(params)
            updates, _ = tx.update(grads, state, params)
            return updates

        got = shard_map(run, mesh=mesh, in_specs=(P(), P()), out_specs=P())(params, grads)

        ref_tx = fused_adam(lr=1e-2)
        st = ref_tx.init(params)
        want, _ = ref_tx.update(grads, st, params)
        for k in params:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]), rtol=1e-5,
                                       atol=1e-6)


class TestTransducer:
    def test_joint(self):
        f = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 8))
        g = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 8))
        h = TransducerJoint()(f, g)
        assert h.shape == (2, 5, 3, 8)
        np.testing.assert_allclose(np.asarray(h[0, 2, 1]),
                                   np.asarray(f[0, 2] + g[0, 1]), rtol=1e-6)
        hr = TransducerJoint(relu=True)(f, g)
        assert float(jnp.min(hr)) >= 0.0

    def test_loss_matches_bruteforce(self):
        rng = np.random.RandomState(0)
        B, T, U, V = 3, 6, 4, 8
        logits = rng.randn(B, T, U + 1, V).astype(np.float32)
        targets = rng.randint(1, V, (B, U))
        f_len = np.array([6, 5, 4])
        y_len = np.array([4, 3, 2])
        got = np.asarray(transducer_loss(
            jnp.asarray(logits), jnp.asarray(targets), jnp.asarray(f_len),
            jnp.asarray(y_len)))

        lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))

        def brute(lp, tg, T, U):
            NEG = -1e30
            alpha = np.full((T, U + 1), NEG)
            alpha[0, 0] = 0.0
            for t in range(T):
                for u in range(U + 1):
                    c = []
                    if t > 0:
                        c.append(alpha[t - 1, u] + lp[t - 1, u, 0])
                    if u > 0:
                        c.append(alpha[t, u - 1] + lp[t, u - 1, tg[u - 1]])
                    if c:
                        m = max(c)
                        if m > NEG / 2:
                            alpha[t, u] = m + np.log(
                                sum(np.exp(x - m) for x in c))
            return -(alpha[T - 1, U] + lp[T - 1, U, 0])

        want = np.array([brute(lp[b], targets[b], f_len[b], y_len[b])
                         for b in range(B)])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_loss_grad_finite(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 4, 6))
        targets = jnp.array([[1, 2, 3], [2, 3, 1]])
        g = jax.grad(lambda lg: jnp.sum(transducer_loss(
            lg, targets, jnp.array([5, 4]), jnp.array([3, 2]))))(logits)
        assert np.isfinite(np.asarray(g)).all()

    @staticmethod
    def _pack(padded, f_len, g_len):
        """Reference packed layout: each batch's valid [f_len, g_len]
        block, row-major, concatenated."""
        rows = [np.asarray(padded[b, :f_len[b], :g_len[b]]).reshape(
            f_len[b] * g_len[b], -1) for b in range(padded.shape[0])]
        return np.concatenate(rows, axis=0)

    def test_joint_pack_output_matches_reference_layout(self):
        f = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 8))
        g = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 8))
        f_len = jnp.array([5, 3, 4])
        g_len = jnp.array([4, 2, 3])
        batch_offset = jnp.cumsum(f_len * g_len)
        packed_batch = int(batch_offset[-1])
        packed = TransducerJoint(pack_output=True)(
            f, g, f_len, g_len, batch_offset=batch_offset,
            packed_batch=packed_batch)
        assert packed.shape == (packed_batch, 8)
        padded = TransducerJoint()(f, g)
        want = self._pack(padded, np.asarray(f_len), np.asarray(g_len))
        np.testing.assert_allclose(np.asarray(packed), want, rtol=1e-6)

    def test_packed_loss_matches_padded(self):
        rng = np.random.RandomState(1)
        B, T, U, V = 3, 6, 4, 8
        logits = rng.randn(B, T, U + 1, V).astype(np.float32)
        targets = jnp.asarray(rng.randint(1, V, (B, U)))
        f_len = jnp.array([6, 5, 4])
        y_len = jnp.array([4, 3, 2])
        want = transducer_loss(jnp.asarray(logits), targets, f_len, y_len)
        g_len = y_len + 1
        batch_offset = jnp.cumsum(f_len * g_len)
        packed = jnp.asarray(self._pack(
            logits, np.asarray(f_len), np.asarray(g_len)))
        got = transducer_loss(
            packed, targets, f_len, y_len, packed_input=True,
            batch_offset=batch_offset, max_f_len=T)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # grads flow back through the unpack gather to the packed rows
        grad = jax.grad(lambda x: jnp.sum(transducer_loss(
            x, targets, f_len, y_len, packed_input=True,
            batch_offset=batch_offset, max_f_len=T)))(packed)
        assert np.isfinite(np.asarray(grad)).all()
        assert float(jnp.abs(grad).sum()) > 0


class TestHaloExchange:
    def test_halo_rows_move_to_neighbours(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("spatial",))
        hh = 1
        # global map [N=1, H=16, W=2, C=3], H sharded 4 ways (4 rows/rank)
        x = jnp.arange(16 * 2 * 3, dtype=jnp.float32).reshape(1, 16, 2, 3)

        def run(x_local):
            pad = [(0, 0)] * x_local.ndim
            pad[1] = (hh, hh)
            y = jnp.pad(x_local, pad)
            y = halo_exchange_1d(y, hh, "spatial", h_dim=1)
            return y[None]  # stack per-rank padded slabs on a new axis

        got = shard_map(run, mesh=mesh, in_specs=P(None, "spatial"),
                        out_specs=P("spatial"))(x)
        got = np.asarray(got)          # [4, 1, 6, 2, 3]
        slabs = np.asarray(x).reshape(4, 4, 2, 3)
        # rank r's top margin row == rank r-1's last row; bottom == r+1's first
        for r in range(1, 4):
            np.testing.assert_allclose(got[r, 0, 0], slabs[r - 1, -1])
        for r in range(0, 3):
            np.testing.assert_allclose(got[r, 0, -1], slabs[r + 1, 0])


def test_fmha_varlen_empty_sequence_grads_finite():
    """A zero-length sequence (legal in reference varlen batching) must
    give finite (zero) grads, not NaN."""
    from apex_tpu.contrib.fmha import fmha_packed_qkv

    qkv = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 3, 2, 4))
    seqlens = jnp.array([8, 0])

    def loss(qkv):
        return jnp.sum(fmha_packed_qkv(qkv, seqlens=seqlens) ** 2)

    g = jax.grad(loss)(qkv)
    assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_allclose(np.asarray(g[1]), 0.0)  # empty seq: no grad


def _varlen_reference(q, k, v, seqlens):
    """Independent dense reference for varlen attention."""
    b, s, h, d = q.shape
    if k.shape[2] != h:
        k = jnp.repeat(k, h // k.shape[2], axis=2)
        v = jnp.repeat(v, h // v.shape[2], axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    ok = jnp.arange(s)[None, :] < seqlens[:, None]
    scores = jnp.where(ok[:, None, None, :], scores, -1e30)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return jnp.where(ok[:, :, None, None], out, 0.0)


def test_fmha_varlen_gqa_matches_reference():
    from apex_tpu.ops.flash_attention import flash_attention

    b, s, h, d = 2, 8, 4, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h // 2, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h // 2, d))
    seqlens = jnp.array([8, 5])
    got = flash_attention(q, k, v, kv_lens=seqlens)
    want = _varlen_reference(q, k, v, seqlens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_fmha_varlen_pallas_kernel_matches():
    """The in-kernel kv_lens bound (interpret mode) must match the jnp
    fallback, forward and backward, including an empty sequence."""
    from apex_tpu.ops import pallas_config
    from apex_tpu.ops.flash_attention import flash_attention

    b, s, h, d = 2, 64, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d))
    seqlens = jnp.array([64, 0])

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, kv_lens=seqlens) ** 2)

    ref_out = flash_attention(q, k, v, kv_lens=seqlens)
    ref_g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    with pallas_config.force("interpret"):
        out = flash_attention(q, k, v, kv_lens=seqlens)
        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-5)
    for name, a, bb in zip("qkv", g, ref_g):
        assert np.isfinite(np.asarray(a)).all(), f"d{name} not finite"
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")
    # ragged middle length through the blocked kernel too
    seqlens2 = jnp.array([37, 64])

    with pallas_config.force("interpret"):
        out2 = flash_attention(q, k, v, kv_lens=seqlens2)
    want2 = _varlen_reference(q, k, v, seqlens2)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want2),
                               rtol=1e-4, atol=1e-5)


class TestMaskSoftmaxDropout:
    """ref contrib/multihead_attn/mask_softmax_dropout_func.py — the
    standalone fused mask+softmax+dropout op."""

    def test_bool_and_additive_masks_agree(self):
        from apex_tpu.contrib.multihead_attn import (MaskSoftmaxDropout,
                                                     mask_softmax_dropout)

        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16))
        pm = jnp.zeros((2, 1, 16), bool).at[:, :, 12:].set(True)
        out = mask_softmax_dropout(x, pm, heads=2)
        assert out.shape == (4, 8, 16)
        # masked keys get zero probability; rows renormalize
        assert float(jnp.abs(out[:, :, 12:]).sum()) == 0.0
        np.testing.assert_allclose(np.asarray(out.sum(-1)), 1.0, rtol=1e-5)
        additive = jnp.where(pm, -1e9, 0.0)
        out2 = mask_softmax_dropout(x, additive, heads=2,
                                    mask_additive=True)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                                   atol=1e-5)
        # Function.apply-shaped class wrapper
        out3 = MaskSoftmaxDropout()(True, 2, x, pm, False, 0.0)
        np.testing.assert_allclose(np.asarray(out3), np.asarray(out),
                                   atol=1e-6)

    def test_dropout_and_grads(self):
        from apex_tpu.contrib.multihead_attn import mask_softmax_dropout

        x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 8))
        rng = jax.random.PRNGKey(2)
        out = mask_softmax_dropout(x, None, heads=2, dropout_prob=0.5,
                                   dropout_rng=rng)
        zeros = float((out == 0).mean())
        assert 0.2 < zeros < 0.8  # ~half dropped
        # eval mode: dropout off regardless of prob
        out_eval = mask_softmax_dropout(x, None, heads=2,
                                        dropout_prob=0.5,
                                        is_training=False)
        np.testing.assert_allclose(np.asarray(out_eval.sum(-1)), 1.0,
                                   rtol=1e-5)
        g = jax.grad(lambda x: jnp.sum(mask_softmax_dropout(
            x, None, heads=2) ** 2))(x)
        assert np.isfinite(np.asarray(g)).all()
        with pytest.raises(ValueError, match="divisible"):
            mask_softmax_dropout(x, None, heads=3)


class TestHaloExchangers:
    """ref contrib/bottleneck/halo_exchangers.py: every transport must
    produce the same neighbor shift."""

    def test_sendrecv_allgather_agree(self):
        from apex_tpu.contrib.halo_exchangers import (
            HaloExchangerAllGather, HaloExchangerNoComm,
            HaloExchangerPeer, HaloExchangerSendRecv)

        mesh = Mesh(np.array(jax.devices()[:4]), ("spatial",))
        # per-rank distinct edges: [4, rows, C]
        left = jnp.arange(4 * 2 * 3, dtype=jnp.float32).reshape(4, 2, 3)
        right = left + 100.0

        def run(exchanger):
            def f(le, re):
                li, ri = exchanger.left_right_halo_exchange(le[0], re[0])
                return li[None], ri[None]
            return shard_map(f, mesh=mesh,
                             in_specs=(P("spatial"), P("spatial")),
                             out_specs=(P("spatial"), P("spatial")))(
                                 left, right)

        li_sr, ri_sr = run(HaloExchangerSendRecv())
        li_ag, ri_ag = run(HaloExchangerAllGather())
        li_peer, ri_peer = run(HaloExchangerPeer())
        np.testing.assert_allclose(np.asarray(li_sr), np.asarray(li_ag))
        np.testing.assert_allclose(np.asarray(ri_sr), np.asarray(ri_ag))
        np.testing.assert_allclose(np.asarray(li_sr), np.asarray(li_peer))
        # rank r's left input = rank r-1's right edge; rank 0 zeros
        np.testing.assert_allclose(np.asarray(li_sr[0]), 0.0)
        np.testing.assert_allclose(np.asarray(li_sr[1:]),
                                   np.asarray(right[:-1]))
        # rank r's right input = rank r+1's left edge; last rank zeros
        np.testing.assert_allclose(np.asarray(ri_sr[:-1]),
                                   np.asarray(left[1:]))
        np.testing.assert_allclose(np.asarray(ri_sr[-1]), 0.0)
        # no-comm: swapped self-edges, no collective
        li_nc, ri_nc = run(HaloExchangerNoComm())
        np.testing.assert_allclose(np.asarray(li_nc), np.asarray(right))
        np.testing.assert_allclose(np.asarray(ri_nc), np.asarray(left))


def test_frozen_batchnorm2d():
    """ref bottleneck.py FrozenBatchNorm2d: fixed stats fold to one
    scale/bias affine."""
    from apex_tpu.contrib.bottleneck import FrozenBatchNorm2d

    bn = FrozenBatchNorm2d(3)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 3))
    v = bn.init(jax.random.PRNGKey(1), x)
    # identity up to eps at default buffers
    np.testing.assert_allclose(np.asarray(bn.apply(v, x)), np.asarray(x),
                               rtol=1e-4, atol=1e-4)
    v2 = {"frozen": {"weight": jnp.full((3,), 2.0),
                     "bias": jnp.ones((3,)),
                     "running_mean": jnp.full((3,), 0.5),
                     "running_var": jnp.full((3,), 4.0)}}
    y = bn.apply(v2, x)
    want = (np.asarray(x) - 0.5) / np.sqrt(4.0 + 1e-5) * 2.0 + 1.0
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5)
    scale, bias = bn.apply(v2, method="get_scale_bias", nhwc=True)
    assert scale.shape == (1, 1, 1, 3)
    np.testing.assert_allclose(np.asarray(scale[0, 0, 0]),
                               2.0 / np.sqrt(4.0 + 1e-5), rtol=1e-6)
    # NCHW layout broadcast
    xc = jnp.moveaxis(x, -1, 1)
    yc = bn.apply(v2, xc, nhwc=False)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(yc, 1, -1)), want,
                               rtol=1e-5)
