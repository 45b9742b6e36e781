"""One Llama decoder block (ISSUE 31): `llama.block` is the only wording of
the layer, so the teacher-forced `forward`, `generate` and the serving engine
give the same greedy tokens, and a change to the block changes all of them.

Since every path now shares the block, a fault in it moves them together:
what they are held to is a wording of the model that shares nothing with
`apex_tpu`, written here in numpy float64 (dense attention under a mask,
K and V repeated for grouped queries, every expert's FFN written out)."""

import dataclasses
from unittest import mock

import jax
import numpy as np
import pytest

from apex_tpu import observability as obs
from apex_tpu.models import generate as gen
from apex_tpu.models import llama
from apex_tpu.serving import ServingEngine

STACKS = {
    "plain": llama.tiny(num_kv_heads=4),
    "gqa": llama.tiny(),
    "sandwich_norm": llama.tiny(sandwich_norm=True),
    "num_passes=4": llama.tiny(num_passes=4),
    # capacity for every token: training's router drops none either
    "moe": llama.tiny(num_experts=4, moe_capacity_factor=8.0)}
PROMPT, NEW = 7, 9


def rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w


def softmax(x):
    e = np.exp(x - x.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


def silu(x):
    return x / (1.0 + np.exp(-x))


def reference_logits(params, tokens, cfg, drop=()):
    """Logits `[s, vocab]` of one sequence. `drop` names the sub-layer
    output norms to leave out."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    s, d = len(tokens), cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    angle = (np.arange(s)[:, None]
             * cfg.rope_theta ** (-np.arange(0, d, 2) / d))
    cos = np.cos(np.concatenate([angle, angle], axis=-1))[:, None]
    sin = np.sin(np.concatenate([angle, angle], axis=-1))[:, None]

    def rope(x):                                     # [s, n, d]
        turned = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
        return x * cos + turned * sin

    def post(y, lp, name):
        if cfg.sandwich_norm and name not in drop:
            return rms(y, lp[name], cfg.rms_eps)
        return y

    def ffn(h, lp):
        if not cfg.moe:
            return (silu(h @ lp["wg"]) * (h @ lp["wu"])) @ lp["wd"]
        probs = softmax(h @ lp["router"])
        y = np.zeros_like(h)
        for row, pr in enumerate(probs):
            best = np.argsort(-pr, kind="stable")[:cfg.moe_top_k]
            gate = pr[best] / (pr[best].sum() if cfg.moe_top_k > 1 else 1.0)
            for g, e in zip(gate, best):
                y[row] += g * ((silu(h[row] @ lp["wg"][e])
                                * (h[row] @ lp["wu"][e])) @ lp["wd"][e])
        return y

    x = p["embed"][np.asarray(tokens)]
    for t in range(cfg.num_passes):
        if t:
            x = rms(x, p["final_norm"], cfg.rms_eps)
        for layer in range(cfg.num_layers):
            lp = {name: w[layer] for name, w in p["layers"].items()}
            h = rms(x, lp["attn_norm"], cfg.rms_eps)
            q = rope((h @ lp["wq"]).reshape(s, nq, d))
            k = rope((h @ lp["wk"]).reshape(s, nkv, d))
            v = (h @ lp["wv"]).reshape(s, nkv, d)
            k, v = (np.repeat(a, nq // nkv, axis=1) for a in (k, v))
            scores = np.einsum("snd,tnd->nst", q, k) / np.sqrt(d)
            scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
            o = np.einsum("nst,tnd->snd", softmax(scores), v)
            x = x + post(o.reshape(s, nq * d) @ lp["wo"], lp,
                         "attn_post_norm")
            x = x + post(ffn(rms(x, lp["mlp_norm"], cfg.rms_eps), lp), lp,
                         "mlp_post_norm")
    return rms(x, p["final_norm"], cfg.rms_eps) @ p["lm_head"]


def reference_tokens(params, prompt, cfg, drop=()):
    seq = list(prompt)
    for _ in range(NEW):
        seq.append(int(np.argmax(
            reference_logits(params, seq, cfg, drop)[-1])))
    return np.asarray(seq[PROMPT:])


def forward_tokens(params, prompt, cfg, teacher):
    """Teacher-forced: the training forward's choice at every position of
    the sequence the teacher chose."""
    seq = np.concatenate([prompt, teacher[:-1]])[None]
    logits = llama.forward(params, seq, cfg, tp_axis=None, cp_axis=None,
                           ep_axis=None, remat=False)
    return np.asarray(logits[0, PROMPT - 1:].argmax(axis=-1))


def generate_tokens(params, prompt, cfg, teacher):
    return np.asarray(gen.generate(params, prompt[None], cfg, NEW)[0, PROMPT:])


def served_tokens(params, prompt, cfg, teacher):
    engine = ServingEngine(params, cfg, page_size=4, max_batch=2, num_pages=8,
                           max_prompt_len=8, max_new_cap=NEW,
                           registry=obs.MetricRegistry())
    rid = engine.submit(prompt, NEW)
    return np.asarray(engine.run()[rid]["tokens"])


PATHS = {"forward": forward_tokens, "generate": generate_tokens,
         "serving_native": served_tokens}


def seeded(cfg):
    params = llama.init_params(jax.random.PRNGKey(31), cfg)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (PROMPT,), 0, cfg.vocab_size), np.int32)
    return params, prompt


@pytest.mark.parametrize("stack,path", [
    (stack, path) for stack in STACKS for path in PATHS
    if not (stack == "moe" and path == "serving_native")])  # dense-only
def test_every_path_gives_the_plain_wordings_greedy_tokens(stack, path):
    cfg = STACKS[stack]
    params, prompt = seeded(cfg)
    want = reference_tokens(params, prompt, cfg)
    np.testing.assert_array_equal(PATHS[path](params, prompt, cfg, want),
                                  want)


def test_a_change_to_the_block_changes_every_path(monkeypatch):
    """`attn_post_norm` is left out inside `llama.block` and nowhere else:
    a path with a wording of its own would keep its tokens."""
    cfg = dataclasses.replace(STACKS["sandwich_norm"], num_passes=2)
    params, prompt = seeded(cfg)
    block, post_norm = llama.block, llama.post_norm

    def altered(*args, **kw):
        with mock.patch.object(
                llama, "post_norm",
                lambda y, lp, name, cfg: y if name == "attn_post_norm"
                else post_norm(y, lp, name, cfg)):
            return block(*args, **kw)

    before = reference_tokens(params, prompt, cfg)
    after = reference_tokens(params, prompt, cfg, drop=("attn_post_norm",))
    assert (before != after).any()
    monkeypatch.setattr(llama, "block", altered)
    for name, tokens_of in PATHS.items():
        np.testing.assert_array_equal(
            tokens_of(params, prompt, cfg, after), after, name)
        assert (tokens_of(params, prompt, cfg, before) != before).any(), name
