"""Plain reference of the GPT-2 training step (Radford et al. 2019; the 345M
shape of Megatron-LM): pre-LayerNorm decoder, learned positions, tanh GELU,
tied head, next-token cross entropy, Adam with bias correction and no weight
decay. Straightforward `jax.numpy` in float32; no kernels, no loss scaling (it
changes nothing in exact arithmetic), gradients accumulated over blocks of rows
so that the whole batch fits. The parameter tree has the layout the program's
`models/gpt2.py` uses, so that the benchmark can hand the same seeded weights
to both; `init` makes them, on the device, from the seed.
"""

import functools

import jax
import jax.numpy as jnp

from perfbench.references.common import einsum, key_from_words, matmul

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def init(lo, hi, cfg):
    """Seeded float32 weights: N(0, 1/fan_in) matrices, unit LayerNorm
    gains, zero biases."""
    h, layers = cfg["hidden_size"], cfg["num_layers"]
    keys = jax.random.split(key_from_words(lo, hi), 6)

    def normal(key, *shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5

    ones = lambda *s: jnp.ones(s, jnp.float32)
    zeros = lambda *s: jnp.zeros(s, jnp.float32)
    return {
        "embed": normal(keys[0], cfg["vocab_size"], h, fan_in=h),
        "pos_embed": normal(keys[1], cfg["max_seq_len"], h, fan_in=h),
        "layers": {
            "ln1_w": ones(layers, h), "ln1_b": zeros(layers, h),
            "wqkv": normal(keys[2], layers, h, 3, h, fan_in=h),
            "bqkv": zeros(layers, 3, h),
            "wo": normal(keys[3], layers, h, h, fan_in=h),
            "bo": zeros(layers, h),
            "ln2_w": ones(layers, h), "ln2_b": zeros(layers, h),
            "wfc": normal(keys[4], layers, h, 4 * h, fan_in=h),
            "bfc": zeros(layers, 4 * h),
            "wproj": normal(keys[5], layers, 4 * h, h, fan_in=4 * h),
            "bproj": zeros(layers, h),
        },
        "lnf_w": ones(h), "lnf_b": zeros(h),
    }


def compare_leaves(tree):
    """The leaves whose norms are compared: the tree's own, with the packed
    q, k and v of `wqkv` and `bqkv` apart (the key's bias has no gradient
    under softmax, and would hide in the leaf it is packed into)."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = ".".join(str(getattr(p, "key", p)) for p in path)
        if name.endswith("wqkv"):
            for i, part in enumerate("qkv"):
                out[f"{name}.{part}"] = leaf[:, :, i]
        elif name.endswith("bqkv"):
            for i, part in enumerate("qkv"):
                out[f"{name}.{part}"] = leaf[:, i]
        else:
            out[name] = leaf
    return out


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in compare_leaves(tree).items()}


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _layer(x, lp, cfg, precision):
    rows, seq, h = x.shape
    heads = cfg["num_heads"]
    d = h // heads
    y = _layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg["ln_eps"])
    qkv = matmul(y, lp["wqkv"].reshape(h, 3 * h), precision) \
        + lp["bqkv"].reshape(-1)
    q, k, v = (t.reshape(rows, seq, heads, d) for t in jnp.split(qkv, 3, -1))
    scores = einsum("bqhd,bkhd->bhqk", q, k, precision) * d ** -0.5
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = einsum("bhqk,bkhd->bqhd", probs, v, precision).reshape(rows, seq, h)
    x = x + matmul(attn, lp["wo"], precision) + lp["bo"]
    y = _layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg["ln_eps"])
    y = jax.nn.gelu(matmul(y, lp["wfc"], precision) + lp["bfc"],
                    approximate=True)
    return x + matmul(y, lp["wproj"], precision) + lp["bproj"]


def loss_sum(params, tokens, targets, cfg, precision="float32"):
    """Sum over the block's tokens of the next-token cross entropy."""
    seq = tokens.shape[1]
    x = params["embed"][tokens] + params["pos_embed"][None, :seq]
    body = jax.checkpoint(
        lambda h, lp: (_layer(h, lp, cfg, precision), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _layer_norm(x, params["lnf_w"], params["lnf_b"], cfg["ln_eps"])
    logits = matmul(x, params["embed"].T, precision)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)


def adam(params, grads, m, v, step, lr):
    """One Adam step (bias-corrected, eps outside the root); `step` from 1."""
    def one(p, g, m_, v_):
        m_ = ADAM_B1 * m_ + (1 - ADAM_B1) * g
        v_ = ADAM_B2 * v_ + (1 - ADAM_B2) * g * g
        m_hat = m_ / (1 - ADAM_B1 ** step)
        v_hat = v_ / (1 - ADAM_B2 ** step)
        return p - lr * m_hat / (jnp.sqrt(v_hat) + ADAM_EPS), m_, v_

    out = jax.tree_util.tree_map(one, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def train_readings(params, batches, cfg, lr, precision="float32",
                   block_rows=4, rows=None, put=None):
    """Follow the first `len(batches)` optimizer steps. Returns the loss of
    each step, the norm of each compared leaf of the first gradient, and the
    norm of each compared leaf of the parameters' change over the steps.

    `rows` (a slice) keeps only part of every batch and takes the mean over
    that part: the planted fault of a step that drops rows. `put` places a
    block of rows on the devices (a sharding over several chips, or None)."""
    grad_block = jax.jit(jax.value_and_grad(
        functools.partial(loss_sum, cfg=cfg, precision=precision)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    scale = jax.jit(lambda t, s: jax.tree_util.tree_map(lambda x: x * s, t),
                    donate_argnums=0)
    update = jax.jit(functools.partial(adam, lr=lr), donate_argnums=(0, 2, 3))
    norms = jax.jit(leaf_norms)
    delta_norms = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))

    start = jax.tree_util.tree_map(jnp.copy, params)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v = zeros(), zeros()
    losses, grad_norms = [], None
    for step, (tokens, targets) in enumerate(batches, start=1):
        if rows is not None:
            tokens, targets = tokens[rows], targets[rows]
        total, grads = 0.0, None
        for at in range(0, tokens.shape[0], block_rows):
            blk = (tokens[at:at + block_rows], targets[at:at + block_rows])
            if put is not None:
                blk = put(blk)
            loss, g = grad_block(params, *blk)
            total += float(loss)
            grads = g if grads is None else add(grads, g)
        count = tokens.shape[0] * tokens.shape[1]
        grads = scale(grads, 1.0 / count)
        losses.append(total / count)
        if step == 1:
            grad_norms = {k: float(x) for k, x in norms(grads).items()}
        params, m, v = update(params, grads, m, v, float(step))
    delta = {k: float(x) for k, x in delta_norms(params, start).items()}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}
