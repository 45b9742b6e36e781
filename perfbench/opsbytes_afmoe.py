"""Operations and bytes of an `afmoe` decoder as one chip of an
expert-parallel deployment holds it: a dense lead, expert layers whose routed
experts are held in part, sliding-window and full attention layers.

What the mathematics asks for, whatever implements it (see `opsbytes.py`), and
what depends on the data is an argument: a decode step multiplies by the
weights of the experts its tokens were routed to, not by all that are held,
and a sliding layer attends to the positions inside its window, not to the
whole context. Both are counted as the least a step can do, so that a program
that reads less cannot read over 100% of a roofline. Configurations arrive as
the dicts of `perfbench/configs/*.json`: `num_experts` counts the experts
held, `experts_held.of` those routed over.
"""

from perfbench import opsbytes


def _dims(cfg):
    nq, nkv, d = opsbytes._attn_dims(cfg)
    return cfg["hidden_size"], nq, nkv, d


def routed_over(cfg):
    return (cfg.get("experts_held") or {}).get("of", cfg["num_experts"])


def attention_weights(cfg):
    """wq, wk, wv, wo and the output gate of one layer."""
    h, nq, nkv, d = _dims(cfg)
    return 3 * h * nq * d + 2 * h * nkv * d


def attention_parameters(cfg):
    """The matrices and the two head norms' gains."""
    return attention_weights(cfg) + 2 * cfg["head_dim"]


def expert_weights(cfg):
    """One SwiGLU expert: three matrices of hidden x moe_intermediate_size."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_layer_parameters(cfg):
    h = cfg["hidden_size"]
    return attention_parameters(cfg) + 4 * h + 3 * h * cfg["intermediate_size"]


def expert_layer_unrouted_weights(cfg):
    """Matrices of an expert layer that every token multiplies by: attention,
    the shared experts, the router over all experts."""
    return (attention_weights(cfg)
            + cfg.get("num_shared_experts", 0) * expert_weights(cfg)
            + cfg["hidden_size"] * routed_over(cfg))


def expert_layer_parameters(cfg):
    """An expert layer as held here: what is not routed, the four norms and
    two head norms, and the held experts (the router's bias, `routed_over`
    float32 numbers a layer, is counted by `router_bias_count`)."""
    return (expert_layer_unrouted_weights(cfg) + 2 * cfg["head_dim"]
            + 4 * cfg["hidden_size"] + cfg["num_experts"] * expert_weights(cfg))


def dense_layers(cfg):
    return cfg.get("num_dense_layers", 0)


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - dense_layers(cfg)


def sliding_layers(cfg):
    return sum(t == "sliding_attention" for t in cfg["layer_types"])


def parameter_count(cfg):
    """Every parameter held here but the routers' biases: the layers, the
    embedding and the head over the vocabulary held, the final norm."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    tables = 1 if cfg.get("tie_word_embeddings", False) else 2
    return (dense_layers(cfg) * dense_layer_parameters(cfg)
            + expert_layers(cfg) * expert_layer_parameters(cfg)
            + tables * h * vocab + h)


def router_bias_count(cfg):
    return expert_layers(cfg) * routed_over(cfg)


def unrouted_weight_reads(cfg):
    """Weights every decoded token multiplies by, whatever it is routed to:
    the dense layers' matrices, the expert layers' unrouted ones, the head
    (the embedding is read one row a token; norms are noise)."""
    h = cfg["hidden_size"]
    dense = attention_weights(cfg) + 3 * h * cfg["intermediate_size"]
    return (dense_layers(cfg) * dense
            + expert_layers(cfg) * expert_layer_unrouted_weights(cfg)
            + h * cfg["vocab_size"])


def attended(cfg, context_len):
    """Positions one query at the end of `context_len` reads, summed over the
    layers: the window's on a sliding layer, all on a full one."""
    n_sliding = sliding_layers(cfg)
    near = min(context_len, cfg["sliding_window"]) if n_sliding else 0
    return n_sliding * near + (cfg["num_hidden_layers"] - n_sliding) \
        * context_len


def decode_flops(cfg, rows, attended_positions, expert_assignments):
    """A decode step of `rows` tokens that between them attend to
    `attended_positions` (each row's `attended`, summed) and of whose
    routings `expert_assignments` fell on held experts (over all expert
    layers): 2 a weight passed, 4 x query heads x head_dim a position."""
    _, nq, _, d = _dims(cfg)
    return (2 * unrouted_weight_reads(cfg) * rows
            + 2 * expert_weights(cfg) * expert_assignments
            + 4 * nq * d * attended_positions)


def kv_bytes_per_position(cfg, itemsize=2):
    """K and V of one position in ONE layer."""
    _, _, nkv, d = _dims(cfg)
    return 2 * nkv * d * itemsize


def decode_bytes(cfg, attended_positions, experts_hit, itemsize=2):
    """Bytes one decode step needs: the unrouted weights once, the weights of
    the `experts_hit` held experts that got a token (summed over the expert
    layers) once, K and V of the positions attended (`attended`, summed over
    the rows) once."""
    return ((unrouted_weight_reads(cfg) + experts_hit * expert_weights(cfg))
            * itemsize
            + kv_bytes_per_position(cfg, itemsize) * attended_positions)
