"""Plain reference of the `lfm2_moe` decoder (LiquidAI/LFM2-24B-A2B,
`modeling_lfm2_moe.py`): gated short-convolution layers beside grouped-query
attention layers in one stack, a dense lead, then sigmoid-routed experts with
none shared. Straightforward `jax.numpy` in float32: one full causal forward
over a whole sequence, no cache, no state, no paging, no batching, no sort and
no grouped product.

    x = Embed[tokens]
    for every layer l:
        x = x + Op_l(RMSNorm(x; g_op_l))
        x = x + FFN_l(RMSNorm(x; g_ffn_l))
    logits = Embed^T RMSNorm(x; g_final)                   # tied head

`Op` of a `conv` layer, on the normed stream h (K = `conv_L_cache` taps):
[B | C | X] = h W_in, three equal parts in this order; u_t = B_t * X_t, per
channel; c_t = sum_{j=0..K-1} w[:, j] * u_{t-(K-1)+j}, depthwise and causal, u
before position 0 being 0; Op(h)_t = (C_t * c_t) W_out. Written as the sum
over j it is.

`Op` of a `full_attention` layer: q = h Wq, k = h Wk, v = h Wv, no biases; q
and k each head RMS-normed over its own `head_dim` with a learned gain; rotary
positions on halves of the head (`rotate_half`, `rope_theta`) on every
attention layer; causal softmax attention at scale head_dim^-0.5, grouped
(several query heads a key/value head); o Wo. No output gate, no norm on a
sub-layer's output.

`FFN` of the first `num_dense_layers` layers: SwiGLU of `intermediate_size`.
Of every other, per token h: s = sigmoid(float32(h Wr)) over the
`num_experts`; sel = top-k(s + expert_bias), the bias entering the selection
only; w = s[sel] / (sum(s[sel]) + 1e-6) (`norm_topk_prob`), times
`routed_scaling_factor`; FFN(h) = sum_{e in sel} w_e Expert_e(h), every expert
a SwiGLU of `moe_intermediate_size`. No token is dropped, whatever the load.
Leaving the 1e-6 out moves a weight by a part in four million of itself (the
four scores sum to about 2): under every tolerance here, and under float32's
own rounding of the sum; it is kept because the source has it.

Departures from the source, each on purpose. (1) The auxiliary balance loss
and the bias's update are training's and are not computed. (2) No cache and
no conv state: a full forward has nothing to keep. (3) Weights are random from
the seed: norm gains 1 + 0.1 N(0, 1) rather than ones, so that a gain in the
wrong place is seen; the router's weight N(0, 0.02^2) so that scores spread
over (0, 1); the bias N(0, 0.01^2) so that it is not silently zero; the taps
N(0, 1/K). (4) Attention runs a key/value head and a block of queries at a
time, and the experts one at a time over all tokens under a mask, so that
long sequences fit beside the weights. (5) The head is tied to the embedding
and the source's `embedding_norm` is the final norm before it (both assumed:
the catalog's config has no key for either).

The parameter tree has the layout the program's `models/llama.py` uses for a
stack with conv layers: `runs`, one stack of layers a run of consecutive
layers of one weight shape, and `experts`, the routed experts of all expert
layers in one stack; so the benchmark hands the same seeded weights to both.
`init` makes them on the device in `torch_dtype`. Nothing here imports the
program.
"""

import jax
import jax.numpy as jnp

from perfbench.references.common import einsum, key_from_words, matmul

QUERY_BLOCK = 512            # queries attended at a time
ROUTE_NORM_EPS = 1e-6
EXPERT_WEIGHTS = ("wg", "wu", "wd")


def dims(cfg):
    h, nq, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    return h, nq, nkv, cfg.get("head_dim") or h // nq


def layer_kinds(cfg):
    """(operator, routed) of every layer: `conv` or `attention`, and whether
    its FFN is an expert layer."""
    return [("conv" if t == "conv" else "attention",
             at >= cfg.get("num_dense_layers", 0))
            for at, t in enumerate(cfg["layer_types"])]


def runs(cfg):
    """[(operator, routed, layers)]: consecutive layers of one weight shape."""
    out = []
    for kind in layer_kinds(cfg):
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [kind + (n,) for kind, n in out]


def expert_shapes(cfg):
    """{name: shape} of one expert layer's routed experts."""
    h, e, f = (cfg["hidden_size"], cfg["num_experts"],
               cfg["moe_intermediate_size"])
    return {"wg": (e, h, f), "wu": (e, h, f), "wd": (e, f, h)}


def parameter_shapes(cfg, operator, routed):
    """{name: shape of one layer} of a layer of this kind, but for its routed
    experts (`expert_shapes`)."""
    h, nq, nkv, d = dims(cfg)
    out = {"attn_norm": (h,), "mlp_norm": (h,)}
    if operator == "conv":
        out.update(conv_in=(h, 3 * h), conv_w=(h, cfg["conv_L_cache"]),
                   conv_out=(h, h))
    else:
        out.update(wq=(h, nq * d), wk=(h, nkv * d), wv=(h, nkv * d),
                   wo=(nq * d, h), q_norm=(d,), k_norm=(d,))
    if routed:
        e = cfg["num_experts"]
        out.update(router=(h, e), router_bias=(e,))
    else:
        inter = cfg["intermediate_size"]
        out.update(wg=(h, inter), wu=(h, inter), wd=(inter, h))
    return out


def init(lo, hi, cfg):
    """Seeded weights in `torch_dtype`: matrices N(0, 1/fan_in), gains
    1 + 0.1 N(0, 1), the router N(0, 0.02^2), its bias N(0, 0.01^2) in
    float32, the taps N(0, 1/K); made a layer at a time so that no float32
    copy of the whole model ever exists."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    key = key_from_words(lo, hi)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def one(k, name, shape):
        if name.endswith("norm"):
            return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                    ).astype(dt)
        if name == "router":
            return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dt)
        if name == "router_bias":
            return 0.01 * jax.random.normal(k, shape, jnp.float32)
        return normal(k, shape, shape[-1] if name == "conv_w" else shape[-2])

    def layer_of(shapes):
        names = sorted(shapes)

        def layer(k):
            ks = jax.random.split(k, len(names))
            return {n: one(ks[i], n, shapes[n]) for i, n in enumerate(names)}
        return layer

    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("the lfm2_moe reference ties its head to the "
                         "embedding")
    ks = jax.random.split(key, 4)
    params = {"embed": normal(ks[0], (vocab, h), h),
              "final_norm": one(ks[2], "final_norm", (h,)),
              "runs": tuple(
                  jax.lax.map(layer_of(parameter_shapes(cfg, operator, routed)),
                              jax.random.split(k, n))
                  for k, (operator, routed, n) in zip(
                      jax.random.split(ks[1], len(runs(cfg))), runs(cfg)))}
    n_routed = sum(routed for _, routed in layer_kinds(cfg))
    if n_routed:     # one stack from the start: no second copy to join them
        params["experts"] = jax.lax.map(layer_of(expert_shapes(cfg)),
                                        jax.random.split(ks[3], n_routed))
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


def rope_theta(cfg):
    return (cfg.get("rope_parameters") or {}).get("rope_theta",
                                                   cfg.get("rope_theta"))


def short_conv(y, lp, cfg, precision):
    """`Op` of a conv layer on the normed stream `y` [seq, hidden]."""
    seq, taps = y.shape[0], cfg["conv_L_cache"]
    gate_in, gate_out, x = jnp.split(matmul(y, lp["conv_in"], precision), 3,
                                     axis=-1)
    u = jnp.pad(gate_in * x, ((taps - 1, 0), (0, 0)))    # u before 0 is 0
    w = lp["conv_w"].astype(jnp.float32)
    c = sum(w[:, j] * u[j:j + seq] for j in range(taps))
    return matmul(gate_out * c, lp["conv_out"], precision)


def attention(y, lp, cfg, precision):
    """`Op` of a full-attention layer on the normed stream `y` [seq, hidden]."""
    seq, eps = y.shape[0], cfg["norm_eps"]
    _, nq, nkv, d = dims(cfg)
    rep = nq // nkv
    positions = jnp.arange(seq)
    q = matmul(y, lp["wq"], precision).reshape(seq, nq, d)
    k = matmul(y, lp["wk"], precision).reshape(seq, nkv, d)
    v = matmul(y, lp["wv"], precision).reshape(seq, nkv, d)
    q = _rotate(_rms_norm(q, lp["q_norm"], eps), positions, rope_theta(cfg))
    k = _rotate(_rms_norm(k, lp["k_norm"], eps), positions, rope_theta(cfg))
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        raise ValueError(f"sequence of {seq} is not whole blocks of {block}")

    def group(qkv):
        qg, kg, vg = qkv             # [seq, rep, d], [seq, d], [seq, d]

        def queries(at):
            qb = jax.lax.dynamic_slice_in_dim(qg, at, block)
            seen = positions[None, :] <= at + jnp.arange(block)[:, None]
            s = einsum("qrd,kd->rqk", qb, kg, precision) * d ** -0.5
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return einsum("rqk,kd->qrd", p, vg, precision)

        return jax.lax.map(queries, jnp.arange(0, seq, block)).reshape(
            seq, rep, d)

    o = jax.lax.map(group, (q.reshape(seq, nkv, rep, d).transpose(1, 0, 2, 3),
                            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return matmul(o.transpose(1, 0, 2, 3).reshape(seq, nq * d), lp["wo"],
                  precision)


def _swiglu(y, wg, wu, wd, precision):
    return matmul(jax.nn.silu(matmul(y, wg, precision))
                  * matmul(y, wu, precision), wd, precision)


def route(y, lp, cfg, precision):
    """[seq, num_experts] float32: each token's weight on every expert, zero
    on those it did not choose."""
    s = jax.nn.sigmoid(matmul(y, lp["router"], precision))
    chosen = s + lp["router_bias"].astype(jnp.float32) \
        if cfg.get("use_expert_bias", True) else s
    _, sel = jax.lax.top_k(chosen, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTE_NORM_EPS)
    w = w * cfg.get("routed_scaling_factor", 1.0)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], sel].set(w)


def expert_ffn(y, lp, experts, at, cfg, precision="float32"):
    """`FFN` of an expert layer on the normed stream `y` [seq, hidden]: sum
    over the experts e of weight[:, e] * Expert_e(y), one expert at a time
    over every token. `experts` holds every expert layer's (`[expert layers,
    num_experts, ...]`), this layer's at `at`: an expert's matrices are read
    where they lie, one at a time."""
    weight = route(y, lp, cfg, precision)

    def add(acc, expert):
        e, w = expert
        wg, wu, wd = (experts[n][at, e] for n in EXPERT_WEIGHTS)
        return acc + w[:, None] * _swiglu(y, wg, wu, wd, precision), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(y),
                          (jnp.arange(cfg["num_experts"]), weight.T))
    return out


def _layer(x, lp, experts, at, cfg, precision, chosen=None):
    eps = cfg["norm_eps"]
    y = _rms_norm(x, lp["attn_norm"], eps)
    x = x + (short_conv(y, lp, cfg, precision) if "conv_in" in lp
             else attention(y, lp, cfg, precision))
    y = _rms_norm(x, lp["mlp_norm"], eps)
    if "router" not in lp:
        return x + _swiglu(y, lp["wg"], lp["wu"], lp["wd"], precision)
    if chosen is not None:
        chosen.append(route(y, lp, cfg, precision) > 0)
    return x + expert_ffn(y, lp, experts, at, cfg, precision)


def hidden(params, tokens, cfg, precision="float32", chosen=None):
    """The stream after the last layer, [seq, hidden], before the final
    norm: the layers one after another, each of its own kind. A list given
    as `chosen` gets, an expert layer, which experts each position chose
    (bool [seq, num_experts]): what a count of the routing is made from."""
    x = params["embed"][tokens].astype(jnp.float32)
    routed = 0
    for stack in params["runs"]:
        for i in range(stack["attn_norm"].shape[0]):
            lp = jax.tree_util.tree_map(lambda w: w[i], stack)
            x = _layer(x, lp, params.get("experts"), routed, cfg, precision,
                       chosen)
            routed += "router" in lp
    return x


def row_logits(params, tokens, rows, cfg, precision="float32"):
    """Logits [len(rows), vocab] at positions `rows` of one causal forward
    over `tokens` [seq]."""
    x = _rms_norm(hidden(params, tokens, cfg, precision)[rows],
                  params["final_norm"], cfg["norm_eps"])
    return matmul(x, params["embed"].T, precision)


def served_gaps(ref_logits, served):
    """For each served token, how far its reference logit lies below the
    reference's best at that position."""
    picked = jnp.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(ref_logits, axis=-1) - picked
