"""Operations and bytes that each algorithm needs at its shapes.

These count what the mathematics asks for, whatever implements it: attention
counts the causal half, a padded row counts nothing, a recomputed forward
counts once. So a later kernel swap is read against the same work, and a share
of a roofline cannot pass 100% by a kinder count. Configurations arrive as the
dicts of `perfbench/configs/*.json`.
"""


def gpt2_forward_flops_per_token(cfg, seq_len):
    """Matmul FLOPs of one forward pass for one token of a length-`seq_len`
    causal sequence: 12 h^2 weights a layer (qkv 3, out 1, mlp 8) at 2 FLOPs a
    weight, QK^T and PV over the (seq_len + 1) / 2 keys a position sees on
    average, and the tied head."""
    h, layers, vocab = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"]
    per_layer = 2 * 12 * h * h + 2 * 2 * h * (seq_len + 1) / 2
    return layers * per_layer + 2 * h * vocab


def gpt2_train_flops_per_token(cfg, seq_len):
    """Forward plus backward (twice the forward); recomputation not counted."""
    return 3 * gpt2_forward_flops_per_token(cfg, seq_len)


def llama_layer_weights(cfg):
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nq * d * 2 + h * nkv * d * 2 + 3 * h * inter


def llama_weight_count(cfg):
    """Weights a decode step has to read: the layers and the head (the
    embedding is read one row a token; norms are noise)."""
    return (cfg["num_hidden_layers"] * llama_layer_weights(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def _attn_dims(cfg):
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], d


def llama_prefill_flops(cfg, prompt_len):
    """Forward of one prompt of `prompt_len` true tokens: layer matmuls for
    every token, causal attention, the head for the last position only."""
    nq, _, d = _attn_dims(cfg)
    layers = cfg["num_hidden_layers"]
    matmul = 2 * layers * llama_layer_weights(cfg) * prompt_len
    attn = layers * flash_causal_flops(1, prompt_len, nq, d)
    return matmul + attn + 2 * cfg["hidden_size"] * cfg["vocab_size"]


def llama_decode_flops(cfg, context_len):
    """One new token that attends to `context_len` cached positions."""
    nq, _, d = _attn_dims(cfg)
    attn = cfg["num_hidden_layers"] * 4 * nq * d * context_len
    return 2 * llama_weight_count(cfg) + attn


def kv_bytes_per_token(cfg, itemsize=2):
    _, nkv, d = _attn_dims(cfg)
    return 2 * cfg["num_hidden_layers"] * nkv * d * itemsize


def llama_decode_bytes(cfg, context_lens, itemsize=2):
    """Bytes one decode step needs: every weight once, and the K and V of
    each active row's live context once."""
    return (llama_weight_count(cfg) * itemsize
            + kv_bytes_per_token(cfg, itemsize) * sum(context_lens))


def flash_causal_flops(batch, seq_len, num_heads, head_dim):
    """QK^T and PV over the causal half: seq (seq + 1) / 2 pairs a head."""
    pairs = seq_len * (seq_len + 1) / 2
    return 2 * 2 * batch * num_heads * head_dim * pairs


def flash_causal_bytes(batch, seq_len, num_heads, num_kv_heads, head_dim,
                       itemsize=2):
    """q and o once at the query heads, k and v once at the kv heads."""
    return batch * seq_len * head_dim * itemsize * (2 * num_heads
                                                    + 2 * num_kv_heads)


def layer_norm_bwd_bytes(rows, hidden, itemsize=2):
    """x and dy read, dx written (weight, bias and row statistics are noise)."""
    return 3 * rows * hidden * itemsize


def causal_softmax_bytes(batch_heads, seq_len, itemsize=2):
    """The causal half of the scores read and of the probabilities written."""
    return 2 * batch_heads * seq_len * (seq_len + 1) / 2 * itemsize


def roofline_seconds(flops, nbytes, peak):
    """Least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
