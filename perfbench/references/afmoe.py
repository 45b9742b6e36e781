"""Plain reference of the `afmoe` decoder (arcee-ai/Trinity-Large-Preview,
`modeling_afmoe.py`) as one chip of an expert-parallel deployment holds it.
Straightforward `jax.numpy` in float32: one full causal forward over a whole
sequence, no cache, no paging, no batching, no sort and no grouped product.

    x = Embed[tokens] * sqrt(hidden)                      # mup_enabled
    for every layer l:
        x = x + RMSNorm(Attn_l(RMSNorm(x; g1_l)); g2_l)
        x = x + RMSNorm(FFN_l(RMSNorm(x; g3_l)); g4_l)
    logits = W_head RMSNorm(x; g_final)

`Attn(h)`: q = h Wq, k = h Wk, v = h Wv, gate = h Wgate, no biases; q and k
each head RMS-normed over its own `head_dim` with a learned gain; on a
`sliding_attention` layer only, rotary positions on halves of the head
(`rotate_half`, `rope_theta`), a `full_attention` layer has no position
signal; causal softmax attention at scale head_dim^-0.5, grouped (several
query heads a key/value head); on a sliding layer query i sees keys j with
i - sliding_window < j <= i, on a full layer j <= i; then
o = (heads * sigmoid(gate)) Wo.

`FFN` of the first `num_dense_layers` layers: SwiGLU of `intermediate_size`.
Of every other, per token h: s = sigmoid(h Wr) over ALL the experts the router
was published with; sel = top-k(s + bias), the bias entering the selection
only; w = s[sel] / (sum(s[sel]) + 1e-20) * route_scale;
FFN(h) = Shared(h) + sum_{e in sel, e held} w_e Expert_e(h), every expert a
SwiGLU of `moe_intermediate_size`. No token is dropped, whatever the load.

The share (model-configs guide, section 4). `num_experts` in the configuration
file counts the experts HELD here; `experts_held` says which (`first`,
`count`) of how many the router routes over (`of`). The router keeps its
published width; what the experts that are not held would add is left out,
and that partial stream goes on to the next layer, here as in the program.
`vocab_size` is the slice of the vocabulary held: a smaller vocabulary.

Departures from the source, each on purpose. (1) The auxiliary balance loss
and the bias's update are training's and are not computed. (2) No cache: a
full forward has nothing to keep. (3) Weights are random from the seed: norm
gains 1 + 0.1 N(0, 1) rather than ones, so that a gain in the wrong place is
seen; the router's weight N(0, 0.02^2) so that scores spread over (0, 1); the
bias N(0, 0.01^2) so that it is not silently zero. (4) Attention runs a
key/value head and a block of queries at a time, and the experts one at a
time over all tokens under a mask, so that long sequences fit beside the
weights.

The parameter tree has the layout the program's `models/llama.py` uses for a
dropless expert model (`dense_layers`, then `layers`), so that the benchmark
hands the same seeded weights to both; `init` makes them on the device in
`torch_dtype`. Nothing here imports the program.
"""

import jax
import jax.numpy as jnp

from perfbench.references.common import einsum, key_from_words, matmul

NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")
QUERY_BLOCK = 512            # queries attended at a time


def dims(cfg):
    h, nq, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    return h, nq, nkv, cfg.get("head_dim") or h // nq


def held(cfg):
    """(first, count, of): the experts held, and how many are routed over."""
    share = cfg.get("experts_held") or {}
    count = share.get("count", cfg["num_experts"])
    return share.get("first", 0), count, share.get("of", count)


def parameter_shapes(cfg):
    """{stack: {name: shape of one layer}} and the three top-level arrays:
    what `init` makes, for counting."""
    h, nq, nkv, d = dims(cfg)
    f, _, count, of = (cfg["moe_intermediate_size"],) + held(cfg)
    fs = f * cfg.get("num_shared_experts", 0)
    attn = {"wq": (h, nq * d), "wk": (h, nkv * d), "wv": (h, nkv * d),
            "wo": (nq * d, h), "wgate": (h, nq * d), "q_norm": (d,),
            "k_norm": (d,), **{n: (h,) for n in NORMS}}
    inter = cfg["intermediate_size"]
    dense = {**attn, "wg": (h, inter), "wu": (h, inter), "wd": (inter, h)}
    expert = {**attn, "router": (h, of), "router_bias": (of,),
              "wg": (count, h, f), "wu": (count, h, f), "wd": (count, f, h)}
    if fs:
        expert.update(shared_wg=(h, fs), shared_wu=(h, fs),
                      shared_wd=(fs, h))
    return {"dense_layers": dense, "layers": expert}


def init(lo, hi, cfg):
    """Seeded weights in `torch_dtype`: matrices N(0, 1/fan_in), gains
    1 + 0.1 N(0, 1), the router N(0, 0.02^2), its bias N(0, 0.01^2) in
    float32; made a layer at a time so that no float32 copy of the whole
    model ever exists."""
    h = cfg["hidden_size"]
    vocab, dt = cfg["vocab_size"], jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    n_dense = cfg.get("num_dense_layers", 0)
    shapes = parameter_shapes(cfg)
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
        return normal(k, shape, shape[-2])

    def layer_of(kind):
        names = sorted(shapes[kind])

        def layer(k):
            ks = jax.random.split(k, len(names))
            return {n: one(ks[i], n, shapes[kind][n])
                    for i, n in enumerate(names)}
        return layer

    ks = jax.random.split(key, 5)
    params = {
        "embed": normal(ks[0], (vocab, h), h),
        "layers": jax.lax.map(layer_of("layers"), jax.random.split(
            ks[1], cfg["num_hidden_layers"] - n_dense)),
        "final_norm": one(ks[3], "final_norm", (h,)),
    }
    if n_dense:
        params["dense_layers"] = jax.lax.map(
            layer_of("dense_layers"), jax.random.split(ks[4], n_dense))
    if not cfg.get("tie_word_embeddings", False):
        params["lm_head"] = normal(ks[2], (h, vocab), h)
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


def attention(y, lp, cfg, sliding, precision):
    """`Attn` of the docstring on the normed stream `y` [seq, hidden];
    `sliding` says which kind of layer this is."""
    seq, eps = y.shape[0], cfg["rms_norm_eps"]
    _, nq, nkv, d = dims(cfg)
    rep = nq // nkv
    positions = jnp.arange(seq)
    q = matmul(y, lp["wq"], precision).reshape(seq, nq, d)
    k = matmul(y, lp["wk"], precision).reshape(seq, nkv, d)
    v = matmul(y, lp["wv"], precision).reshape(seq, nkv, d)
    gate = matmul(y, lp["wgate"], precision)
    q = _rms_norm(q, lp["q_norm"], eps)
    k = _rms_norm(k, lp["k_norm"], eps)
    if sliding:
        q = _rotate(q, positions, cfg["rope_theta"])
        k = _rotate(k, positions, cfg["rope_theta"])
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        raise ValueError(f"sequence of {seq} is not whole blocks of {block}")

    def group(qkv):
        qg, kg, vg = qkv             # [seq, rep, d], [seq, d], [seq, d]

        def queries(at):
            qb = jax.lax.dynamic_slice_in_dim(qg, at, block)
            i = at + jnp.arange(block)[:, None]
            seen = positions[None, :] <= i
            if sliding:
                seen = seen & (positions[None, :] > i - cfg["sliding_window"])
            s = einsum("qrd,kd->rqk", qb, kg, precision) * d ** -0.5
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return einsum("rqk,kd->qrd", p, vg, precision)

        return jax.lax.map(queries, jnp.arange(0, seq, block)).reshape(
            seq, rep, d)

    o = jax.lax.map(group, (q.reshape(seq, nkv, rep, d).transpose(1, 0, 2, 3),
                            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(seq, nq * d)
    return matmul(o * jax.nn.sigmoid(gate), lp["wo"], precision)


def _swiglu(y, wg, wu, wd, precision):
    return matmul(jax.nn.silu(matmul(y, wg, precision))
                  * matmul(y, wu, precision), wd, precision)


def route(y, lp, cfg, precision):
    """[seq, of] float32: each token's weight on every expert the router
    routes over, zero on those it did not choose."""
    s = jax.nn.sigmoid(matmul(y, lp["router"], precision))
    _, sel = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg.get("route_scale", 1.0)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], sel].set(w)


def routed_experts(y, lp, cfg, precision):
    """The held experts' part: sum over the held experts e of
    weight[:, e] * Expert_e(y), one expert at a time over every token."""
    first, count, _ = held(cfg)
    weight = route(y, lp, cfg, precision)[:, first:first + count]

    def add(acc, expert):
        wg, wu, wd, w = expert
        return acc + w[:, None] * _swiglu(y, wg, wu, wd, precision), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(y),
                          (lp["wg"], lp["wu"], lp["wd"], weight.T))
    return out


def expert_ffn(y, lp, cfg, precision="float32"):
    """`FFN` of an expert layer on the normed stream `y` [seq, hidden]."""
    out = routed_experts(y, lp, cfg, precision)
    if cfg.get("num_shared_experts", 0):
        out = out + _swiglu(y, lp["shared_wg"], lp["shared_wu"],
                            lp["shared_wd"], precision)
    return out


def _layer(x, lp, cfg, sliding, precision, chosen=None):
    eps = cfg["rms_norm_eps"]
    a = attention(_rms_norm(x, lp["attn_norm"], eps), lp, cfg, sliding,
                  precision)
    x = x + _rms_norm(a, lp["attn_post_norm"], eps)
    y = _rms_norm(x, lp["mlp_norm"], eps)
    if "router" in lp and chosen is not None:
        chosen.append(route(y, lp, cfg, precision) > 0)
    m = expert_ffn(y, lp, cfg, precision) if "router" in lp else _swiglu(
        y, lp["wg"], lp["wu"], lp["wd"], precision)
    return x + _rms_norm(m, lp["mlp_post_norm"], eps)


def hidden(params, tokens, cfg, precision="float32", chosen=None):
    """The stream after the last layer, [seq, hidden], before the final
    norm: the layers one after another, each of its own kind. A list given
    as `chosen` gets, an expert layer, which of all the experts each position
    chose (bool [seq, of]): what a count of the routing is made from."""
    x = params["embed"][tokens].astype(jnp.float32)
    if cfg.get("mup_enabled", False):
        x = x * cfg["hidden_size"] ** 0.5
    kinds = [t == "sliding_attention" for t in cfg["layer_types"]]
    at = 0
    for name in ("dense_layers", "layers"):
        stack = params.get(name)
        if stack is None:
            continue
        for i in range(stack["attn_norm"].shape[0]):
            lp = jax.tree_util.tree_map(lambda w: w[i], stack)
            x = _layer(x, lp, cfg, kinds[at], precision, chosen)
            at += 1
    return x


def row_logits(params, tokens, rows, cfg, precision="float32"):
    """Logits [len(rows), vocab] at positions `rows` of one causal forward
    over `tokens` [seq]."""
    x = _rms_norm(hidden(params, tokens, cfg, precision)[rows],
                  params["final_norm"], cfg["rms_norm_eps"])
    head = params["embed"].T if cfg.get("tie_word_embeddings", False) \
        else params["lm_head"]
    return matmul(x, head, precision)


def served_gaps(ref_logits, served):
    """For each served token, how far its reference logit lies below the
    reference's best at that position."""
    picked = jnp.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(ref_logits, axis=-1) - picked
