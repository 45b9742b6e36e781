"""Plain reference of Ouro's looped decoder (ByteDance/Ouro-2.6B,
`modeling_ouro.py`): one stack of L layers applied `total_ut_steps` = T times
over the same weights. A layer norms each sub-layer's input and its output
(four RMSNorms: g1 before attention, g2 on its output, g3 before the MLP, g4
on its output), attention is full multi-head with rotary positions on halves
of the head (`rotate_half`) at the token's own position in every pass, the
MLP is SwiGLU, the head is untied. After every pass the stream goes through
the final norm; the next pass starts from that, and an exit gate
`Linear(hidden, 1)` reads it. Straightforward `jax.numpy` in float32: one full
causal forward over a whole sequence, no cache, no paging, no batching.

    h = Embed[tokens]
    for t in 1..T:
        for l in 1..L:
            h = h + RMSNorm(Attn_l(RMSNorm(h; g1_l)); g2_l)
            h = h + RMSNorm(MLP_l(RMSNorm(h; g3_l)); g4_l)
        h = RMSNorm(h; g_final);  lam_t = sigmoid(w_exit . h + b_exit)
    logits = W_head h                       # of the last pass

Departures from the source, each on purpose. (1) The exit rule is not run:
the source leaves at the first pass whose cumulative exit probability reaches
`early_exit_threshold`; at the published threshold of 1 that is the last pass,
so the logits are the last pass's and `exit_distribution` only reports the
gate. (2) No cache: the source keeps keys and values of pass t of layer l in
cache layer (t-1) L + l; a full forward has nothing to keep, and that is the
point of a reference. (3) Weights are random from the seed, not trained: norm
gains are 1 + 0.1 N(0, 1) rather than all ones, so that a gain applied in the
wrong place is seen. (4) Attention runs a head at a time so that long
sequences fit.

The parameter tree has the layout the program's `models/llama.py` uses for a
looped, sandwich-normed configuration, so that the benchmark can hand the same
seeded weights to both; `init` makes them, on the device and in the type they
are served in (`torch_dtype`), from the seed. Nothing here imports the program.
"""

import jax
import jax.numpy as jnp

from perfbench.references.common import einsum, key_from_words, matmul

NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


def dims(cfg):
    h, nq, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    return h, nq, nkv, cfg.get("head_dim") or h // nq


def init(lo, hi, cfg):
    """Seeded weights in `torch_dtype`: matrices N(0, 1/fan_in), gains
    1 + 0.1 N(0, 1), made a layer at a time so that no float32 copy of the
    whole model ever exists."""
    h, nq, nkv, d = dims(cfg)
    inter, layers = cfg["intermediate_size"], cfg["num_hidden_layers"]
    vocab, dt = cfg["vocab_size"], jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    key = key_from_words(lo, hi)

    def normal(k, *shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def gain(k):
        return (1.0 + 0.1 * jax.random.normal(k, (h,), jnp.float32)).astype(dt)

    def layer(k):
        ks = jax.random.split(k, 11)
        out = {name: gain(ks[7 + i]) for i, name in enumerate(NORMS)}
        out.update(
            wq=normal(ks[0], h, nq * d, fan_in=h),
            wk=normal(ks[1], h, nkv * d, fan_in=h),
            wv=normal(ks[2], h, nkv * d, fan_in=h),
            wo=normal(ks[3], nq * d, h, fan_in=nq * d),
            wg=normal(ks[4], h, inter, fan_in=h),
            wu=normal(ks[5], h, inter, fan_in=h),
            wd=normal(ks[6], inter, h, fan_in=inter))
        return out

    ks = jax.random.split(key, 6)
    params = {
        "embed": normal(ks[0], vocab, h, fan_in=h),
        "layers": jax.lax.map(layer, jax.random.split(ks[1], layers)),
        "final_norm": gain(ks[3]),
        "exit_gate": {"w": normal(ks[4], h, 1, fan_in=h),
                      "b": normal(ks[5], 1, fan_in=1)},
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
    seq, eps = x.shape[0], cfg["rms_norm_eps"]
    _, nq, nkv, d = dims(cfg)
    rep = nq // nkv
    positions = jnp.arange(seq)
    y = _rms_norm(x, lp["attn_norm"], eps)
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
    x = x + _rms_norm(matmul(o, lp["wo"], precision), lp["attn_post_norm"],
                      eps)
    y = _rms_norm(x, lp["mlp_norm"], eps)
    gate = jax.nn.silu(matmul(y, lp["wg"], precision))
    m = matmul(gate * matmul(y, lp["wu"], precision), lp["wd"], precision)
    return x + _rms_norm(m, lp["mlp_post_norm"], eps)


def _passes(params, tokens, cfg, precision):
    """The normed stream after every pass, [T, seq, hidden]."""

    def one_pass(x, _):
        x, _ = jax.lax.scan(
            lambda h, lp: (_layer(h, lp, cfg, precision), None), x,
            params["layers"])
        x = _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        return x, x

    x = params["embed"][tokens].astype(jnp.float32)
    _, normed = jax.lax.scan(one_pass, x, None,
                             length=cfg["total_ut_steps"])
    return normed


def row_logits(params, tokens, rows, cfg, precision="float32"):
    """Logits [len(rows), vocab] at positions `rows` of one causal forward
    over `tokens` [seq]: the head on the last pass's normed stream."""
    x = _passes(params, tokens, cfg, precision)[-1][rows]
    head = params["embed"].T if cfg.get("tie_word_embeddings", False) \
        else params["lm_head"]
    return matmul(x, head, precision)


def exit_distribution(params, tokens, cfg, precision="float32"):
    """(lam, p), both [T, seq]: the exit gate after every pass, and the
    probability of leaving after pass t: lam_t times the share that has not
    left before, and at the last pass all that is left, so p sums to 1."""
    normed = _passes(params, tokens, cfg, precision)
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(matmul(normed, gate["w"], precision)[..., 0]
                         + gate["b"].astype(jnp.float32))
    left, p = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return lam, jnp.stack(p + [left])


def served_gaps(ref_logits, served):
    """For each served token, how far its reference logit lies below the
    reference's best at that position."""
    picked = jnp.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(ref_logits, axis=-1) - picked
