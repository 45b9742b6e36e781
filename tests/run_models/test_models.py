"""Model-zoo tests: single-device forward/loss, tp-sharded parity vs
unsharded, cp ring-attention parity, short training-loss decrease."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from apex_tpu.models import bert, dcgan, gpt2, llama, mlp, resnet
import optax

from apex_tpu.optimizers import fused_adam


def tp_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("tp",))


# ------------------------------------------------------------------- llama


class TestLlama:
    def test_forward_shape(self):
        cfg = llama.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        logits = llama.forward(params, tokens, cfg, tp_axis=None, cp_axis=None)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_tp_parity(self):
        cfg = llama.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        ref = llama.forward(params, tokens, cfg, tp_axis=None, cp_axis=None)

        mesh = tp_mesh(2)
        pspecs = llama.param_specs(cfg)
        fwd = shard_map(
            functools.partial(llama.forward, cfg=cfg, tp_axis="tp",
                              cp_axis=None),
            mesh=mesh, in_specs=(pspecs, P()), out_specs=P(None, None, "tp"),
        )
        out = fwd(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_tp_sp_parity(self):
        cfg = llama.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        ref = llama.loss_fn(params, (tokens, tokens), cfg, tp_axis=None,
                            cp_axis=None)
        mesh = tp_mesh(2)
        loss = shard_map(
            functools.partial(llama.loss_fn, cfg=cfg, tp_axis="tp",
                              cp_axis=None, sequence_parallel=True),
            mesh=mesh, in_specs=(llama.param_specs(cfg), (P(), P())),
            out_specs=P(),
        )(params, (tokens, tokens))
        np.testing.assert_allclose(float(loss), float(ref), atol=2e-4,
                                   rtol=2e-4)

    def test_cp_parity(self):
        cfg = llama.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                    cfg.vocab_size)
        ref = llama.forward(params, tokens, cfg, tp_axis=None, cp_axis=None)

        mesh = Mesh(np.array(jax.devices()[:4]), ("cp",))
        fwd = shard_map(
            functools.partial(llama.forward, cfg=cfg, tp_axis=None,
                              cp_axis="cp"),
            mesh=mesh, in_specs=(P(), P(None, "cp")),
            out_specs=P(None, "cp", None),
        )
        out = fwd(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_train_loss_decreases(self):
        cfg = llama.tiny(num_layers=1)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                    cfg.vocab_size)
        tx = fused_adam(lr=1e-2)
        state = tx.init(params)
        lfn = functools.partial(llama.loss_fn, cfg=cfg, tp_axis=None,
                                cp_axis=None)

        @jax.jit
        def step(params, state):
            loss, grads = jax.value_and_grad(lfn)(params, (tokens, tokens))
            updates, state = tx.update(grads, state, params)
            return optax.apply_updates(params, updates), state, loss

        first = None
        for _ in range(10):
            params, state, loss = step(params, state)
            first = loss if first is None else first
        assert float(loss) < float(first)

    def test_stage_split_roundtrip(self):
        cfg = llama.tiny(num_layers=4)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        staged = llama.split_stages(params, 2)
        assert staged["wq"].shape[0] == 2 and staged["wq"].shape[1] == 2


# -------------------------------------------------------------------- gpt2


class TestGPT2:
    def test_forward_and_loss(self):
        cfg = gpt2.tiny()
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        logits = gpt2.forward(params, tokens, cfg, tp_axis=None)
        assert logits.shape == (2, 16, cfg.vocab_size)
        loss = gpt2.loss_fn(params, (tokens, tokens), cfg, tp_axis=None)
        assert np.isfinite(float(loss))

    def test_tp_parity(self):
        cfg = gpt2.tiny()
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        ref = gpt2.loss_fn(params, (tokens, tokens), cfg, tp_axis=None)
        mesh = tp_mesh(2)
        loss = shard_map(
            functools.partial(gpt2.loss_fn, cfg=cfg, tp_axis="tp"),
            mesh=mesh, in_specs=(gpt2.param_specs(cfg), (P(), P())),
            out_specs=P(),
        )(params, (tokens, tokens))
        np.testing.assert_allclose(float(loss), float(ref), atol=2e-4,
                                   rtol=2e-4)

    def test_causality(self):
        cfg = gpt2.tiny()
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                                cfg.vocab_size)
        t2 = t1.at[0, 10].set((t1[0, 10] + 1) % cfg.vocab_size)
        l1 = gpt2.forward(params, t1, cfg, tp_axis=None)
        l2 = gpt2.forward(params, t2, cfg, tp_axis=None)
        np.testing.assert_allclose(np.asarray(l1[0, :10]),
                                   np.asarray(l2[0, :10]), atol=1e-5)
        assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))

    @pytest.mark.parametrize("kernels", ["off", "interpret"])
    def test_flash_attention_matches_the_materialized_causal_softmax(
            self, monkeypatch, kernels):
        """The loss and every gradient through the flash core (its jnp
        path, and the Pallas kernels' bodies) against the composition the
        model ran before: scores, the causal fused softmax, PV."""
        from apex_tpu.ops import pallas_config
        from apex_tpu.transformer.functional.fused_softmax import (
            scaled_upper_triang_masked_softmax,
        )

        def materialized(q, k, v):
            b, s, n, d = q.shape
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
            probs = scaled_upper_triang_masked_softmax(
                scores.reshape(b * n, s, s), None, d ** -0.5)
            return jnp.einsum("bhqk,bkhd->bqhd",
                              probs.reshape(b, n, s, s).astype(v.dtype), v)

        cfg = gpt2.tiny()
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                                    cfg.vocab_size)
        grad = jax.value_and_grad(
            lambda p: gpt2.loss_fn(p, (tokens, jnp.roll(tokens, -1, -1)),
                                   cfg, tp_axis=None))
        with pallas_config.force(kernels):
            loss, grads = grad(params)
        monkeypatch.setattr(gpt2, "_causal_attend", materialized)
        with pallas_config.force("off"):
            want, want_grads = grad(params)
        np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
        for path, g in jax.tree_util.tree_leaves_with_path(grads):
            w = functools.reduce(lambda t, k: t[k.key], path, want_grads)
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4,
                atol=2e-5 * float(np.abs(np.asarray(w)).max()),
                err_msg=jax.tree_util.keystr(path))


# -------------------------------------------------------------------- bert


class TestBert:
    def test_forward_and_loss(self):
        cfg = bert.tiny()
        params = bert.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        mask = jnp.zeros((2, 16), bool).at[:, 12:].set(True)
        hidden = bert.forward(params, tokens, cfg, pad_mask=mask,
                              tp_axis=None)
        assert hidden.shape == (2, 16, cfg.hidden_size)
        loss_mask = jnp.zeros((2, 16)).at[:, 3:6].set(1.0)
        loss = bert.loss_fn(params, (tokens, tokens, loss_mask), cfg,
                            tp_axis=None)
        assert np.isfinite(float(loss))

    def test_tp_parity(self):
        cfg = bert.tiny()
        params = bert.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        loss_mask = jnp.ones((2, 16))
        ref = bert.loss_fn(params, (tokens, tokens, loss_mask), cfg,
                           tp_axis=None)
        mesh = tp_mesh(2)
        loss = shard_map(
            functools.partial(bert.loss_fn, cfg=cfg, tp_axis="tp"),
            mesh=mesh, in_specs=(bert.param_specs(cfg), (P(), P(), P())),
            out_specs=P(),
        )(params, (tokens, tokens, loss_mask))
        np.testing.assert_allclose(float(loss), float(ref), atol=2e-4,
                                   rtol=2e-4)

    def test_attention_is_the_padding_masked_softmax_composition(self):
        """BERT's attention core is what it was: scores, the padding-masked
        fused softmax, PV, with q, k, v and the output projection spelled
        out here."""
        from apex_tpu.transformer.functional.fused_softmax import (
            scaled_masked_softmax,
        )

        cfg = bert.tiny()
        params = bert.init_params(jax.random.PRNGKey(0), cfg)
        lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
        b, s, h, n = 2, 16, cfg.hidden_size, cfg.num_heads
        d = h // n
        x = jax.random.normal(jax.random.PRNGKey(2), (b, s, h), cfg.dtype)
        mask = jnp.zeros((b, s), bool).at[1, 11:].set(True)
        got = bert._attention(x, lp, cfg, mask, None)

        qkv = x @ lp["wqkv"].reshape(h, 3 * h) + lp["bqkv"].reshape(-1)
        q, k, v = (t.reshape(b, s, n, d) for t in jnp.split(qkv, 3, -1))
        probs = scaled_masked_softmax(
            jnp.einsum("bqhd,bkhd->bhqk", q, k), mask[:, None, None, :],
            d ** -0.5)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
        want = o.reshape(b, s, h) @ lp["wo"] + lp["bo"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_bidirectional(self):
        """Unlike GPT-2, early positions DO see later-token changes."""
        cfg = bert.tiny()
        params = bert.init_params(jax.random.PRNGKey(0), cfg)
        t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                                cfg.vocab_size)
        t2 = t1.at[0, 10].set((t1[0, 10] + 1) % cfg.vocab_size)
        h1 = bert.forward(params, t1, cfg, tp_axis=None)
        h2 = bert.forward(params, t2, cfg, tp_axis=None)
        assert not np.allclose(np.asarray(h1[0, :10]), np.asarray(h2[0, :10]))


# ----------------------------------------------------------- resnet / dcgan


class TestVision:
    def test_resnet_forward(self):
        model = resnet.tiny()
        x = jnp.ones((2, 32, 32, 3))
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        logits = model.apply(variables, x, train=False)
        assert logits.shape == (2, 10)

    def test_resnet_train_updates_stats(self):
        model = resnet.tiny()
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        variables = model.init(jax.random.PRNGKey(0), x, train=True)
        _, new_state = model.apply(variables, x, train=True,
                                   mutable=["batch_stats"])
        old = jax.tree_util.tree_leaves(variables["batch_stats"])
        new = jax.tree_util.tree_leaves(new_state["batch_stats"])
        assert any(not np.allclose(np.asarray(a), np.asarray(b))
                   for a, b in zip(old, new))

    def test_dcgan_shapes(self):
        g = dcgan.Generator(width=8)
        d = dcgan.Discriminator(width=8)
        z = jax.random.normal(jax.random.PRNGKey(0), (2, 100))
        gv = g.init(jax.random.PRNGKey(1), z, train=False)
        img = g.apply(gv, z, train=False)
        assert img.shape == (2, 32, 32, 3)
        assert float(jnp.max(jnp.abs(img))) <= 1.0
        dv = d.init(jax.random.PRNGKey(2), img, train=False)
        logit = d.apply(dv, img, train=False)
        assert logit.shape == (2,)


# --------------------------------------------------------------------- mlp


class TestMLP:
    def test_train_loss_decreases(self):
        cfg = mlp.MLPConfig(sizes=(16, 32, 4))
        params = mlp.init_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
        y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 4)
        tx = fused_adam(lr=1e-2)
        state = tx.init(params)

        @jax.jit
        def step(params, state):
            loss, grads = jax.value_and_grad(mlp.loss_fn)(params, (x, y), cfg)
            updates, state = tx.update(grads, state, params)
            return optax.apply_updates(params, updates), state, loss

        first = None
        for _ in range(20):
            params, state, loss = step(params, state)
            first = loss if first is None else first
        assert float(loss) < float(first)


# --------------------------------------------------------------- llama MoE


class TestLlamaMoE:
    """Mixtral-style routed experts in the flagship (cfg.num_experts > 0;
    experts over 'ep', orthogonal to tp)."""

    def _cfg(self, **over):
        kw = dict(num_experts=4, moe_top_k=2, moe_capacity_factor=8.0)
        kw.update(over)
        return llama.tiny(**kw)

    def test_forward_shape_and_aux(self):
        cfg = self._cfg()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        assert params["layers"]["wg"].shape == (
            cfg.num_layers, 4, cfg.hidden_size, cfg.intermediate_size)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        logits, aux = llama.forward_with_aux(
            params, tokens, cfg, tp_axis=None, cp_axis=None, ep_axis=None)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert float(aux) > 0

    def test_train_loss_decreases(self):
        cfg = self._cfg()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                    cfg.vocab_size)
        batch = (tokens, jnp.roll(tokens, -1, -1))
        tx = fused_adam(lr=3e-3)
        state = tx.init(params)

        @jax.jit
        def step(params, state):
            loss, grads = jax.value_and_grad(llama.loss_fn)(
                params, batch, cfg, tp_axis=None, cp_axis=None,
                ep_axis=None)
            updates, state = tx.update(grads, state, params)
            return optax.apply_updates(params, updates), state, loss

        first = None
        for _ in range(10):
            params, state, loss = step(params, state)
            first = loss if first is None else first
        assert float(loss) < float(first)

    def test_ep_parity(self):
        """dp=1 x ep=4 expert-parallel loss == single-device loss (generous
        capacity so nothing drops)."""
        cfg = self._cfg(num_experts=8)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        ref = llama.loss_fn(params, (tokens, jnp.roll(tokens, -1, -1)),
                            cfg, tp_axis=None, cp_axis=None, ep_axis=None)

        mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
        pspecs = llama.param_specs(cfg, tp_axis=None)

        def fn(params, tokens):
            loss = llama.loss_fn(params, (tokens, jnp.roll(tokens, -1, -1)),
                                 cfg, tp_axis=None, cp_axis=None,
                                 ep_axis="ep")
            return jax.lax.pmean(loss, "ep")

        loss = shard_map(
            fn, mesh=mesh, in_specs=(pspecs, P()), out_specs=P(),
        )(params, tokens)
        np.testing.assert_allclose(float(loss), float(ref), rtol=2e-4)
