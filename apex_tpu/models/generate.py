"""KV-cache autoregressive decoding for the llama family.

No reference analog (apex is a training toolkit); provided because the
HF checkpoint import (models/convert.py) makes the model zoo hold real
weights, and the natural smoke test of real weights is sampling. The
design is decode-native rather than a re-run of the training forward:

- static shapes throughout: the cache is ``[L, b, max_len, nkv, d]``
  (``L = cfg.cache_layers``: a looped llama stack keeps K and V of every
  pass, pass-major) and a position mask (``idx <= pos``) replaces dynamic
  slicing, so the whole generation loop is ONE ``lax.scan`` under jit;
- prefill is a single full-sequence pass (flash attention) that also
  emits every layer's rotated k / v — the prompt costs one step, not
  one step per token;
- decode attends one query token against the cache with a plain fp32
  softmax (a [b, nq, max_len] score row — no S×S anything).

Greedy (``temperature=0``) or temperature sampling. Works on any
backend; sharded serving is out of scope (single-host batch decode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.models import llama as _llama
from apex_tpu.ops.flash_attention import flash_attention

__all__ = ["greedy_generate", "generate", "gpt2_generate"]


def _decode_attention(q, k_cache, v_cache, pos, start=None):
    """q [b, 1, nq, d] vs cache [b, max_len, nkv, d], valid idx <= pos
    (and ``start <= idx`` where a sliding layer gives its window's start,
    shaped like ``pos``).

    GQA contracts grouped: q reshapes to [b, nkv, rep, d] (query head
    n = kv * rep + r) and both einsums run against the nkv-head cache
    directly, so the rep× cache copy a ``jnp.repeat`` to nq heads would
    materialize every decode step never exists. ``pos`` is a scalar for
    the batch-uniform generate()/gpt2 loops, or any shape broadcastable
    against [b, nq, max_len] (e.g. [b, 1, 1] per-row positions for the
    serving scheduler's packed batches).
    """
    b, _, nq, d = q.shape
    nkv = k_cache.shape[2]
    rep = nq // nkv
    qg = q.astype(jnp.float32).reshape(b, nkv, rep, d)
    scores = jnp.einsum("bkrd,btkd->bkrt", qg,
                        k_cache.astype(jnp.float32)) * (d ** -0.5)
    scores = scores.reshape(b, nq, -1)            # [b, nq, T]
    idx = jnp.arange(k_cache.shape[1])
    seen = idx[None, None, :] <= pos
    if start is not None:
        seen = seen & (idx[None, None, :] >= start)
    scores = jnp.where(seen, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkrt,btkd->bkrd", probs.reshape(b, nkv, rep, -1),
                   v_cache.astype(jnp.float32))
    return o.reshape(b, 1, nq * d)


def _moe_router_weights(xt, lp, cfg):
    """Top-k combine weights on [T, h] tokens, matching the training
    router's selection and normalization (transformer/moe.py
    router_gates) — minus the capacity drop, which is a training
    throughput artifact inference should never apply."""
    logits = jnp.matmul(xt.astype(jnp.float32),
                        lp["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.moe_top_k)        # [T, k]
    if cfg.moe_top_k > 1:  # GShard/Mixtral renorm; top-1 keeps raw prob
        gate = gate / jnp.maximum(
            jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    return gate, idx


def _moe_decode_ffn(hm, lp, cfg):
    """Routed SwiGLU for ONE decode token per batch row ([b, 1, h]):
    gather the top-k experts' weights per token and run only those —
    at decode batch sizes the k weight gathers beat the training path's
    dispatch/combine einsums, and no token is ever capacity-dropped.
    Closes the MoE hole in generation (VERDICT r4 missing #3)."""
    b, _, h = hm.shape
    xt = hm.reshape(b, h)
    gate, idx = _moe_router_weights(xt, lp, cfg)
    wg = jnp.take(lp["wg"], idx, axis=0).astype(xt.dtype)  # [b, k, h, f]
    wu = jnp.take(lp["wu"], idx, axis=0).astype(xt.dtype)
    wd = jnp.take(lp["wd"], idx, axis=0).astype(xt.dtype)  # [b, k, f, h]
    g = jnp.einsum("bh,bkhf->bkf", xt, wg)
    u = jnp.einsum("bh,bkhf->bkf", xt, wu)
    y = jnp.einsum("bkf,bkfh->bkh", jax.nn.silu(g) * u, wd)
    out = jnp.einsum("bk,bkh->bh", gate.astype(xt.dtype), y)
    return out.reshape(b, 1, h)


def _moe_prefill_ffn(hm, lp, cfg):
    """Routed SwiGLU on the full prompt [b, s, h]: run EVERY expert on
    every token and mask with the combine weights. Exact (no capacity
    drops), static-shaped, MXU-friendly; compute-inflated by E/k vs the
    training dispatch — acceptable for a one-shot prefill pass."""
    b, s, h = hm.shape
    xt = hm.reshape(-1, h)
    gate, idx = _moe_router_weights(xt, lp, cfg)
    w = jnp.sum(jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)
                * gate[..., None], axis=1)                 # [T, E]
    wg, wu = lp["wg"].astype(xt.dtype), lp["wu"].astype(xt.dtype)
    g = jnp.einsum("th,ehf->tef", xt, wg)
    u = jnp.einsum("th,ehf->tef", xt, wu)
    y = jnp.einsum("tef,efh->teh", jax.nn.silu(g) * u,
                   lp["wd"].astype(xt.dtype))
    out = jnp.einsum("te,teh->th", w.astype(xt.dtype), y)
    return out.reshape(b, s, h)


def _routed(ffn, cfg):
    """``llama.block``'s ``ffn`` here: the routed form ``ffn`` for an MoE
    config, the block's own dense SwiGLU otherwise."""
    return functools.partial(ffn, cfg=cfg) if cfg.moe else None


def _block(x, lp, params, cfg, positions, attend, ffn):
    """``llama.block`` as this module calls it: a dropless expert model's
    layers go through ``llama.routed_block`` (its counts are a server's to
    read), every other through ``block`` with the Mixtral form ``ffn``."""
    if cfg.dropless:
        return _llama.routed_block(x, lp, _llama.expert_stack(params), cfg,
                                   positions, attend)[:2]
    return _llama.block(x, lp, cfg, positions, attend,
                        ffn=_routed(ffn, cfg))


def _flash_keeping_kv(q, k, v):
    """The prefills' ``attend`` (``llama.block``): causal flash attention
    over the whole prompt, the rotated K and V kept for the cache."""
    o = flash_attention(q, k, v, causal=True, scale=q.shape[-1] ** -0.5)
    return o, (k, v)


def _prefill_attend(lp, cfg, length=None):
    """The prefills' ``attend`` for this layer: :func:`_flash_keeping_kv`,
    within the window where the layer is a sliding one; on a conv layer the
    whole sequence's window, its state kept at ``length`` (the prompt's true
    length under a padded bucket; the sequence's end without one)."""
    if _llama.operator_of(lp) == "conv":
        return _llama.conv_window(cfg, length=length)
    if not cfg.windowed:
        return _flash_keeping_kv
    return lambda q, k, v: (_llama.causal_attention(q, k, v, lp, cfg),
                            (k, v))


def _attend_cache_at(pos, k_cache, v_cache, start=None):
    """The decode loop's ``attend``: one position's K and V written into
    the contiguous ``[b, max_len, nkv, d]`` cache at ``pos``, the query
    attending to everything up to it (from ``start`` on, where a sliding
    layer gives one); the updated cache is kept."""

    def attend(q, k, v):
        kc = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), pos, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), pos, axis=1)
        return (_decode_attention(q, kc, vc, pos, start).astype(q.dtype),
                (kc, vc))

    return attend


def _logits(params, x, cfg):
    with jax.named_scope("llama/head"):
        x = _llama._rmsnorm(x, params["final_norm"], cfg.rms_eps)
        w = _llama.lm_head_weight(params, cfg)
        return jnp.matmul(x, w.astype(x.dtype)).astype(jnp.float32)


def _sample(logits, temperature, key):
    if temperature:
        return jax.random.categorical(key, logits / temperature)
    return jnp.argmax(logits, axis=-1)


def _autoregress(embed_step, decode_stack, logits_fn,
                 caches, logits0, prompt_tokens,
                 max_new_tokens, temperature, key):
    """The shared decode loop: max_new-1 scan steps, each consuming the
    previous token and emitting the next (the final token needs no
    decode pass). ``decode_stack(x, caches, pos) -> (x, caches)`` is the
    model's whole depth for one token; ``caches`` is what it keeps between
    steps (K and V; a stack with conv layers, their states beside them)."""
    key, key0 = jax.random.split(key)
    first = _sample(logits0, temperature, key0)[:, None]

    def step(carry, key_t):
        token, kept, pos = carry
        x, kept = decode_stack(embed_step(token, pos), kept, pos)
        nxt = _sample(logits_fn(x)[:, 0], temperature, key_t)
        return (nxt[:, None], kept, pos + 1), nxt

    p = prompt_tokens.shape[1]
    keys = jax.random.split(key, max_new_tokens - 1)
    _, toks = jax.lax.scan(step, (first, caches, jnp.int32(p)), keys)
    new = jnp.concatenate([first, toks.T], axis=1)  # [b, max_new]
    return jnp.concatenate([prompt_tokens, new], axis=1)


def _check_sampling_args(temperature, key):
    if temperature and key is None:
        raise ValueError("temperature sampling needs a PRNG key")
    return key if key is not None else jax.random.PRNGKey(0)


def generate(params, prompt_tokens, cfg, max_new_tokens: int,
             temperature: float = 0.0,
             key: Optional[jax.Array] = None):
    """Llama autoregressive decode: prompt [b, p] → tokens [b, p + new].

    Greedy at ``temperature=0`` (default); otherwise softmax sampling
    with ``key``. The prompt must be dense (no padding); cache length is
    ``p + max_new_tokens``. MoE configs route every token through its
    top-k experts with NO capacity drop (the training path's drops are a
    throughput artifact, not an inference semantic); a dropless expert
    model runs the layer it is served by (``llama.moe_ffn``), and sliding
    layers read their window of the cache. A conv layer has no K and V: it
    keeps ``[b, conv_L_cache - 1, h]`` of state, rewritten every step.
    """
    b, p = prompt_tokens.shape
    key = _check_sampling_args(temperature, key)

    # ---- prefill: one full pass, caches for every layer
    positions = jnp.broadcast_to(jnp.arange(p), (b, p))
    x = _llama.embed(params, prompt_tokens, cfg, tp_axis=None)

    def pre_body(h, lp, _):
        return _block(h, lp, params, cfg, positions,
                      _prefill_attend(lp, cfg), _moe_prefill_ffn)

    x, kept = _llama.scan_passes(x, params, cfg, pre_body,
                                 _llama.stacks(params, cfg))
    pad = [(0, 0), (0, 0), (0, max_new_tokens), (0, 0), (0, 0)]
    # [T*L, b, max_len, ...] of K and of V
    caches = tuple(jnp.pad(a.astype(cfg.dtype), pad) for a in (
        kept["attention"] if cfg.hybrid else kept))
    if cfg.hybrid:
        caches = {"attention": caches, "conv": kept["conv"]}
    logits0 = _logits(params, x[:, -1:], cfg)[:, 0]

    def decode_stack(x, caches, pos):
        def layer(h, lp, cache):
            if _llama.operator_of(lp) == "conv":
                attend = _llama.conv_window(cfg, state=cache)
            else:
                attend = _attend_cache_at(
                    pos, *cache, _llama.window_start(lp, cfg, pos))
            return _block(h, lp, params, cfg,
                          jnp.full((b, 1), pos, jnp.int32), attend,
                          _moe_decode_ffn)

        return _llama.scan_passes(x, params, cfg, layer,
                                  _llama.stacks(params, cfg), caches)

    return _autoregress(
        lambda token, pos: _llama.embed(params, token, cfg, tp_axis=None),
        decode_stack,
        lambda x: _logits(params, x, cfg),
        caches, logits0, prompt_tokens,
        max_new_tokens, temperature, key)


def greedy_generate(params, prompt_tokens, cfg, max_new_tokens: int):
    return generate(params, prompt_tokens, cfg, max_new_tokens,
                    temperature=0.0)


# ------------------------------------------------------------------- gpt2


def _gpt2_qkv(x, lp, cfg):
    from apex_tpu.models import gpt2 as _gpt2

    b, s, h = x.shape
    n, d = cfg.num_heads, cfg.head_dim
    qkv = (jnp.matmul(x, lp["wqkv"].reshape(h, -1).astype(x.dtype))
           + lp["bqkv"].reshape(-1))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    return (q.reshape(b, s, n, d), k.reshape(b, s, n, d),
            v.reshape(b, s, n, d))


def _gpt2_mlp(x, lp):
    y = jnp.matmul(x, lp["wfc"].astype(x.dtype)) + lp["bfc"]
    y = jax.nn.gelu(y, approximate=True)
    return jnp.matmul(y, lp["wproj"].astype(x.dtype)) + lp["bproj"]


def _gpt2_prefill_layer(x, lp, cfg):
    from apex_tpu.models._common import layer_norm as _ln
    from apex_tpu.ops.flash_attention import flash_attention

    b, s = x.shape[:2]
    h = _ln(x, lp["ln1_w"], lp["ln1_b"], cfg.ln_eps)
    q, k, v = _gpt2_qkv(h, lp, cfg)
    o = flash_attention(q, k, v, causal=True, scale=cfg.head_dim ** -0.5)
    x = x + (jnp.matmul(o.reshape(b, s, -1), lp["wo"].astype(x.dtype))
             + lp["bo"])
    h = _ln(x, lp["ln2_w"], lp["ln2_b"], cfg.ln_eps)
    return x + _gpt2_mlp(h, lp), k, v


def _gpt2_decode_layer(x, lp, cfg, k_cache, v_cache, pos):
    from apex_tpu.models._common import layer_norm as _ln

    h = _ln(x, lp["ln1_w"], lp["ln1_b"], cfg.ln_eps)
    q, k, v = _gpt2_qkv(h, lp, cfg)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), pos, axis=1)
    o = _decode_attention(q, k_cache, v_cache, pos).astype(x.dtype)
    x = x + jnp.matmul(o, lp["wo"].astype(x.dtype)) + lp["bo"]
    h = _ln(x, lp["ln2_w"], lp["ln2_b"], cfg.ln_eps)
    return x + _gpt2_mlp(h, lp), k_cache, v_cache


def gpt2_generate(params, prompt_tokens, cfg, max_new_tokens: int,
                  temperature: float = 0.0,
                  key: Optional[jax.Array] = None):
    """GPT-2 decode (learned positions, packed qkv, tied head)."""
    from apex_tpu.models._common import layer_norm as _ln

    b, p = prompt_tokens.shape
    max_len = p + max_new_tokens
    if max_len > cfg.max_seq_len:
        raise ValueError(f"prompt + new tokens ({max_len}) exceeds "
                         f"max_seq_len {cfg.max_seq_len}")
    key = _check_sampling_args(temperature, key)

    def embed(tokens, pos0):
        x = jnp.take(params["embed"], tokens, axis=0)
        s = tokens.shape[1]
        wpe = jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos0, s)
        return (x + wpe[None]).astype(cfg.dtype)

    def logits_fn(x):
        x = _ln(x, params["lnf_w"], params["lnf_b"], cfg.ln_eps)
        return jnp.matmul(
            x, params["embed"].T.astype(x.dtype)).astype(jnp.float32)

    x = embed(prompt_tokens, 0)

    def pre_body(h, lp):
        h, k, v = _gpt2_prefill_layer(h, lp, cfg)
        return h, (k, v)

    x, (ks, vs) = jax.lax.scan(pre_body, x, params["layers"])
    pad = [(0, 0), (0, 0), (0, max_new_tokens), (0, 0), (0, 0)]
    k_cache = jnp.pad(ks.astype(cfg.dtype), pad)
    v_cache = jnp.pad(vs.astype(cfg.dtype), pad)
    logits0 = logits_fn(x[:, -1:])[:, 0]

    def decode_stack(x, caches, pos):
        def body(h, layer):
            lp, k1, v1 = layer
            h, k1, v1 = _gpt2_decode_layer(h, lp, cfg, k1, v1, pos)
            return h, (k1, v1)

        return jax.lax.scan(body, x, (params["layers"],) + tuple(caches))

    return _autoregress(
        lambda token, pos: embed(token, pos), decode_stack,
        logits_fn, (k_cache, v_cache), logits0,
        prompt_tokens, max_new_tokens, temperature, key)
