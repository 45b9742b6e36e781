"""Plain reference of a dense Llama-architecture decoder, as Mistral-7B-v0.3
publishes it (`modeling_mistral.py`): RMSNorm before attention and MLP, rotary
positions on halves of the head (`rotate_half`), grouped-query attention in
which query head n reads key/value head n // (heads / kv_heads), SwiGLU MLP,
untied head, no sliding window. Straightforward `jax.numpy` in float32: one
full causal forward over a whole sequence, no cache, no paging, no batching.
Attention runs a key/value group at a time so that long sequences fit.

The parameter tree has the layout the program's `models/llama.py` uses, so that
the benchmark can hand the same seeded weights to both; `init` makes them, on
the device and in the type they are served in (`torch_dtype`), from the seed.
"""

import jax
import jax.numpy as jnp

from perfbench.references.common import einsum, key_from_words, matmul


def dims(cfg):
    h = cfg["hidden_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h, nq, nkv, cfg.get("head_dim") or h // nq


def init(lo, hi, cfg):
    """Seeded bfloat16 weights, N(0, 1/fan_in), made a layer at a time so
    that no float32 copy of the whole model ever exists."""
    h, nq, nkv, d = dims(cfg)
    inter, layers = cfg["intermediate_size"], cfg["num_hidden_layers"]
    vocab, dt = cfg["vocab_size"], jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    key = key_from_words(lo, hi)

    def normal(k, *shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def layer(k):
        ks = jax.random.split(k, 7)
        return {
            "attn_norm": jnp.ones((h,), dt), "mlp_norm": jnp.ones((h,), dt),
            "wq": normal(ks[0], h, nq * d, fan_in=h),
            "wk": normal(ks[1], h, nkv * d, fan_in=h),
            "wv": normal(ks[2], h, nkv * d, fan_in=h),
            "wo": normal(ks[3], nq * d, h, fan_in=nq * d),
            "wg": normal(ks[4], h, inter, fan_in=h),
            "wu": normal(ks[5], h, inter, fan_in=h),
            "wd": normal(ks[6], inter, h, fan_in=inter),
        }

    ks = jax.random.split(key, 3)
    params = {
        "embed": normal(ks[0], vocab, h, fan_in=h),
        "layers": jax.lax.map(layer, jax.random.split(ks[1], layers)),
        "final_norm": jnp.ones((h,), dt),
    }
    if not cfg.get("tie_word_embeddings", False):
        params["lm_head"] = normal(ks[2], h, vocab, fan_in=h)
    return params


def _rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rotate(x, positions, theta):
    """x [seq, heads, d]: rotary embedding on halves, as `rotate_half`."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv[None, :]
    angles = jnp.concatenate([angles, angles], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(angles) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angles)


def _layer(x, lp, cfg, precision):
    seq = x.shape[0]
    _, nq, nkv, d = dims(cfg)
    rep = nq // nkv
    positions = jnp.arange(seq)
    y = _rms_norm(x, lp["attn_norm"], cfg["rms_norm_eps"])
    q = matmul(y, lp["wq"], precision).reshape(seq, nq, d)
    k = matmul(y, lp["wk"], precision).reshape(seq, nkv, d)
    v = matmul(y, lp["wv"], precision).reshape(seq, nkv, d)
    q = _rotate(q, positions, cfg["rope_theta"])
    k = _rotate(k, positions, cfg["rope_theta"])
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def group(qkv):
        qg, kg, vg = qkv                       # [seq, rep, d], [seq, d] x 2
        s = einsum("qrd,kd->rqk", qg, kg, precision) * d ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return einsum("rqk,kd->qrd", p, vg, precision)

    o = jax.lax.map(group, (q.reshape(seq, nkv, rep, d).transpose(1, 0, 2, 3),
                            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(seq, nq * d)
    x = x + matmul(o, lp["wo"], precision)
    y = _rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(matmul(y, lp["wg"], precision))
    return x + matmul(gate * matmul(y, lp["wu"], precision), lp["wd"],
                      precision)


def row_logits(params, tokens, rows, cfg, precision="float32"):
    """Logits [len(rows), vocab] at positions `rows` of one causal forward
    over `tokens` [seq]."""
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(
        lambda h, lp: (_layer(h, lp, cfg, precision), None), x,
        params["layers"])
    x = _rms_norm(x[rows], params["final_norm"], cfg["rms_norm_eps"])
    head = params["embed"].T if cfg.get("tie_word_embeddings", False) \
        else params["lm_head"]
    return matmul(x, head, precision)


def served_gaps(ref_logits, served):
    """For each served token, how far its reference logit lies below the
    reference's best at that position."""
    picked = jnp.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(ref_logits, axis=-1) - picked
