"""The expert layer as it is deployed (`transformer/moe.route`,
`dropless_experts`; ISSUE 33): against a plain loop over experts; the bias in
the selection only; `route_norm` and `route_scale`; every token on one expert
and none dropped; the softmax mode against `generate._moe_router_weights`;
the experts of several layers end to end; and the add-up test: the shares of
an expert-parallel deployment, each computed alone, with the shared expert
counted once, give the uncut layer of the plain reference. Every test runs
twice (ISSUE 36): over `jax.lax.ragged_dot`, the grouped product off the TPU,
and over the body of the Pallas kernel `apex_gmm`, interpreted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import generate as gen, llama
from apex_tpu.ops import pallas_config
from apex_tpu.transformer.moe import dropless_experts, route
from perfbench.references import afmoe as ref

T, H, F, E, K = 24, 16, 12, 8, 2


@pytest.fixture(autouse=True, params=["ragged_dot", "apex_gmm"])
def grouped_product(request):
    with pallas_config.force(
            "interpret" if request.param == "apex_gmm" else "off"):
        yield


def weights(seed=0, e=E):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"x": jax.random.normal(ks[0], (T, H)),
            "router": jax.random.normal(ks[1], (H, e)) * 0.5,
            "bias": jax.random.normal(ks[2], (e,)) * 0.3,
            "wg": jax.random.normal(ks[3], (e, H, F)) * H ** -0.5,
            "wu": jax.random.normal(ks[4], (e, H, F)) * H ** -0.5,
            "wd": jax.random.normal(ks[5], (e, F, H)) * F ** -0.5}


def loop_over_experts(p, w, idx, first=0, count=None):
    """sum_k w[t, k] Expert_idx[t, k](x[t]), in numpy, a token at a time."""
    x = np.asarray(p["x"], np.float64)
    count = p["wg"].shape[0] if count is None else count
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for k in range(idx.shape[1]):
            e = int(idx[t, k]) - first
            if 0 <= e < count:
                g = x[t] @ np.asarray(p["wg"][e], np.float64)
                u = x[t] @ np.asarray(p["wu"][e], np.float64)
                out[t] += float(w[t, k]) * (
                    (g / (1 + np.exp(-g)) * u)
                    @ np.asarray(p["wd"][e], np.float64))
    return out


def experts_of(p):
    return {n: p[n] for n in ("wg", "wu", "wd")}


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_the_layer_is_the_loop_over_experts(score):
    p = weights()
    w, idx = route(p["x"], p["router"], p["bias"], top_k=K, score=score,
                   route_scale=2.448)
    y, counts = dropless_experts(p["x"], w, idx, experts_of(p))
    np.testing.assert_allclose(np.asarray(y), loop_over_experts(
        p, np.asarray(w), np.asarray(idx)), atol=1e-5)
    assert int(counts[0]) == T * K           # every assignment is held
    assert int(counts[1]) == len(np.unique(np.asarray(idx)))


def test_the_bias_enters_the_selection_and_not_the_weight():
    p = weights()
    scores = np.asarray(jax.nn.sigmoid(p["x"] @ p["router"]))
    w, idx = route(p["x"], p["router"], p["bias"], top_k=K, score="sigmoid",
                   route_norm=False)
    want = np.argsort(-(scores + np.asarray(p["bias"])), axis=-1)[:, :K]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want, -1)).all()
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(scores, np.asarray(idx), -1),
        rtol=1e-6)
    # and it does change who is chosen
    _, plain = route(p["x"], p["router"], None, top_k=K, score="sigmoid")
    assert (np.sort(np.asarray(plain), -1) != np.sort(want, -1)).any()


def test_route_norm_and_route_scale():
    p = weights()
    raw, idx = route(p["x"], p["router"], top_k=K, score="sigmoid",
                     route_norm=False)
    normed, idx2 = route(p["x"], p["router"], top_k=K, score="sigmoid",
                         route_scale=2.448)
    assert (np.asarray(idx) == np.asarray(idx2)).all()
    raw = np.asarray(raw)
    np.testing.assert_allclose(
        np.asarray(normed), raw / (raw.sum(-1, keepdims=True) + 1e-20)
        * 2.448, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(normed).sum(-1), 2.448, rtol=1e-6)


def test_the_softmax_mode_is_generates_mixtral_gate():
    p = weights()
    for k in (2, 3):
        cfg = llama.tiny(num_experts=E, moe_top_k=k)
        want_w, want_idx = gen._moe_router_weights(
            p["x"], {"router": p["router"]}, cfg)
        w, idx = route(p["x"], p["router"], top_k=k, score="softmax")
        assert (np.asarray(idx) == np.asarray(want_idx)).all()
        np.testing.assert_array_equal(np.asarray(w), np.asarray(want_w))


def test_every_token_on_one_expert_and_none_dropped():
    """No capacity: an expert takes all T x k assignments if they come."""
    p = weights()
    idx = jnp.full((T, K), 5, jnp.int32)
    w = jnp.full((T, K), 0.5)
    y, counts = dropless_experts(p["x"], w, idx, experts_of(p))
    np.testing.assert_allclose(np.asarray(y), loop_over_experts(
        p, np.asarray(w), np.asarray(idx)), atol=1e-5)
    assert (np.abs(np.asarray(y)).sum(-1) > 0).all()
    assert [int(c) for c in counts] == [T * K, 1]


def test_held_experts_valid_rows_and_counts():
    """A share of 3 experts from 2 on: only its assignments are computed and
    counted, and a row marked not valid routes nowhere."""
    p = weights()
    w, idx = route(p["x"], p["router"], p["bias"], top_k=K, score="sigmoid")
    share = {n: p[n][2:5] for n in ("wg", "wu", "wd")}
    valid = jnp.arange(T) % 3 != 0
    y, counts = dropless_experts(p["x"], w, idx, share, (2, 3), valid)
    keep = np.asarray(valid)
    want = loop_over_experts({**p, **share}, np.asarray(w), np.asarray(idx),
                             first=2, count=3) * keep[:, None]
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    held = (np.asarray(idx) >= 2) & (np.asarray(idx) < 5) & keep[:, None]
    assert int(counts[0]) == held.sum()
    assert int(counts[1]) == len(np.unique(np.asarray(idx)[held]))
    assert (np.asarray(y)[~keep] == 0).all()


def test_the_experts_of_several_layers_end_to_end():
    """`at`: this layer's experts lie from a group on in a longer stack, the
    other layers' groups empty; traced, as a scan step gives it."""
    p, other = weights(0), weights(1)
    w, idx = route(p["x"], p["router"], top_k=K, score="sigmoid")
    alone, counts = dropless_experts(p["x"], w, idx, experts_of(p))
    stack = {n: jnp.concatenate([other[n], p[n], other[n]])
             for n in ("wg", "wu", "wd")}
    got, counts2 = jax.jit(lambda at: dropless_experts(
        p["x"], w, idx, stack, (0, E), None, at))(jnp.int32(E))
    np.testing.assert_allclose(np.asarray(got), np.asarray(alone), atol=1e-6)
    assert (np.asarray(counts) == np.asarray(counts2)).all()


def test_a_tokens_result_is_made_of_its_own_rows_alone():
    """The same token among other tokens, at another place of the batch:
    the same bits (the grouped products on the CPU; PERF.md says what the
    chip was asked)."""
    p = weights()
    w, idx = route(p["x"], p["router"], p["bias"], top_k=K, score="sigmoid")
    y, _ = dropless_experts(p["x"], w, idx, experts_of(p))
    perm = np.random.default_rng(0).permutation(T)
    y2, _ = dropless_experts(p["x"][perm], w[perm], idx[perm], experts_of(p))
    np.testing.assert_array_equal(np.asarray(y)[perm], np.asarray(y2))
    y3, _ = dropless_experts(p["x"][:5], w[:5], idx[:5], experts_of(p))
    np.testing.assert_array_equal(np.asarray(y)[:5], np.asarray(y3))


# ------------------------------------------------------------ the add-up test

CFG = {"hidden_size": 32, "moe_intermediate_size": 24, "num_experts": 32,
       "num_experts_per_tok": 4, "num_shared_experts": 1, "route_norm": True,
       "route_scale": 2.448, "score_func": "sigmoid"}


def layer_weights():
    h, f, e = 32, 24, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 9)
    n = lambda k, *s: jax.random.normal(k, s) * s[-2] ** -0.5
    return {"router": jax.random.normal(ks[0], (h, e)) * 0.3,
            "router_bias": jax.random.normal(ks[1], (e,)) * 0.05,
            "wg": n(ks[2], e, h, f), "wu": n(ks[3], e, h, f),
            "wd": n(ks[4], e, f, h), "shared_wg": n(ks[5], h, f),
            "shared_wu": n(ks[6], h, f), "shared_wd": n(ks[7], f, h),
            "expert_at": jnp.int32(0)}, jax.random.normal(ks[8], (2, 20, h))


def test_the_shares_add_up_to_the_uncut_layer():
    """32 experts in 8 shares of 4. Each share is the program's layer told
    which experts it holds: it routes over all 32 and computes its own. The
    shares' routed parts, with the shared expert, which every chip computes
    alike, counted once, are the reference's uncut layer."""
    lp, y = layer_weights()
    whole = np.asarray(ref.expert_ffn(y.reshape(-1, 32), lp, CFG)
                       ).reshape(y.shape)
    base = dict(num_experts=32, moe_top_k=4, moe_capacity_factor=None,
                hidden_size=32, moe_intermediate_size=24,
                router_score="sigmoid", route_scale=2.448, router_bias=True)
    routed, total = np.zeros_like(whole), 0
    for first in range(0, 32, 4):
        cfg = llama.tiny(**base, experts_held=(first, 4))
        share = {n: lp[n][first:first + 4] for n in ("wg", "wu", "wd")}
        part, counts = llama.moe_ffn(y, lp, cfg, share)
        # against the reference given the same share
        np.testing.assert_allclose(np.asarray(part), np.asarray(
            ref.routed_experts(y.reshape(-1, 32), {**lp, **share}, {
                **CFG, "num_experts": 4, "experts_held": {
                    "first": first, "count": 4, "of": 32}}, "float32")
        ).reshape(y.shape), atol=1e-5)
        routed += np.asarray(part)
        total += int(counts[0])
    cfg = llama.tiny(**base, num_shared_experts=1, experts_held=(0, 32))
    with_shared, _ = llama.moe_ffn(y, lp, cfg, {n: lp[n] for n in
                                               ("wg", "wu", "wd")})
    shared = np.asarray(with_shared) - np.asarray(llama.moe_ffn(
        y, lp, llama.tiny(**base, experts_held=(0, 32)),
        {n: lp[n] for n in ("wg", "wu", "wd")})[0])
    assert total == 2 * 20 * 4               # every assignment, once
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5)
    np.testing.assert_allclose(np.asarray(with_shared), whole, atol=2e-5)
