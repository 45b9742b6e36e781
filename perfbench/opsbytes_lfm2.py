"""Operations and bytes of an `lfm2_moe` decoder: gated short-convolution
layers beside grouped-query attention layers, a dense lead, then expert layers
whose experts are all held.

What the mathematics asks for, whatever implements it (see `opsbytes.py`), and
what depends on the data is an argument: a prefill counts its true tokens,
never the padding up to its bucket; a decode step multiplies by the weights of
the experts its tokens were routed to, not by all 64 of a layer; K and V are
read on the attention layers alone, the conv layers read and write their state.
Each is the least a step can do, so that a program that does less cannot read
over 100% of a roofline. Configurations arrive as the dicts of
`perfbench/configs/*.json`.
"""

from perfbench import opsbytes


def _dims(cfg):
    nq, nkv, d = opsbytes._attn_dims(cfg)
    return cfg["hidden_size"], nq, nkv, d


def conv_layers(cfg):
    return sum(t == "conv" for t in cfg["layer_types"])


def attention_layers(cfg):
    return cfg["num_hidden_layers"] - conv_layers(cfg)


def dense_layers(cfg):
    return cfg.get("num_dense_layers", 0)


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - dense_layers(cfg)


def conv_weights(cfg):
    """W_in [h, 3h] and W_out [h, h] of one conv operator."""
    h = cfg["hidden_size"]
    return 4 * h * h


def conv_taps(cfg):
    """The depthwise kernel of one conv operator, [h, conv_L_cache]."""
    return cfg["hidden_size"] * cfg["conv_L_cache"]


def attention_weights(cfg):
    """wq, wk, wv, wo of one attention layer."""
    h, nq, nkv, d = _dims(cfg)
    return 2 * h * nq * d + 2 * h * nkv * d


def expert_weights(cfg):
    """One SwiGLU expert: three matrices of hidden x moe_intermediate_size."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_ffn_weights(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_weights(cfg):
    return cfg["hidden_size"] * cfg["num_experts"]


def parameter_count(cfg):
    """Every parameter held here but the routers' biases: the operators (an
    attention layer's two head norms with it), two norms a layer, the dense
    FFNs, every expert and router, the tied embedding once, the final norm."""
    h, (_, _, _, d) = cfg["hidden_size"], _dims(cfg)
    return (conv_layers(cfg) * (conv_weights(cfg) + conv_taps(cfg))
            + attention_layers(cfg) * (attention_weights(cfg) + 2 * d)
            + cfg["num_hidden_layers"] * 2 * h
            + dense_layers(cfg) * dense_ffn_weights(cfg)
            + expert_layers(cfg) * (cfg["num_experts"] * expert_weights(cfg)
                                    + router_weights(cfg))
            + h * cfg["vocab_size"] + h)


def router_bias_count(cfg):
    return expert_layers(cfg) * cfg["num_experts"]


def unrouted_layer_weights(cfg):
    """Matrices of all layers that every token multiplies by, whatever it is
    routed to: the conv and attention operators, the dense FFNs, the routers
    (the taps and norms are elementwise: noise)."""
    return (conv_layers(cfg) * conv_weights(cfg)
            + attention_layers(cfg) * attention_weights(cfg)
            + dense_layers(cfg) * dense_ffn_weights(cfg)
            + expert_layers(cfg) * router_weights(cfg))


def token_flops(cfg):
    """Matmul and convolution FLOPs one token costs in every layer: 2 a
    weight it multiplies by, its `num_experts_per_tok` experts a layer among
    them, and 2 a tap and channel; attention and the head apart."""
    routed = expert_layers(cfg) * cfg["num_experts_per_tok"] \
        * expert_weights(cfg)
    return 2 * (unrouted_layer_weights(cfg) + routed
                + conv_layers(cfg) * conv_taps(cfg))


def prefill_flops(cfg, prompt_len):
    """Forward of one prompt of `prompt_len` true tokens: every layer's
    products for every token, causal attention on the attention layers, the
    head for the last position only."""
    _, nq, _, d = _dims(cfg)
    return (token_flops(cfg) * prompt_len
            + attention_layers(cfg) * opsbytes.flash_causal_flops(
                1, prompt_len, nq, d)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def decode_flops(cfg, rows, positions, expert_assignments):
    """A decode step of `rows` tokens that between them attend to `positions`
    cached positions (each row's context, summed; read on every attention
    layer) and of whose routings `expert_assignments` were made (over all
    expert layers)."""
    h, nq, _, d = _dims(cfg)
    return (2 * (unrouted_layer_weights(cfg) + conv_layers(cfg)
                 * conv_taps(cfg) + h * cfg["vocab_size"]) * rows
            + 2 * expert_weights(cfg) * expert_assignments
            + 4 * nq * d * attention_layers(cfg) * positions)


def kv_bytes_per_position(cfg, itemsize=2):
    """K and V of one position in ONE attention layer."""
    _, _, nkv, d = _dims(cfg)
    return 2 * nkv * d * itemsize


def state_bytes_per_row(cfg, itemsize=2):
    """The conv state of one row over all conv layers."""
    return conv_layers(cfg) * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] \
        * itemsize


def decode_bytes(cfg, rows, positions, experts_hit, itemsize=2):
    """Bytes one decode step needs: the unrouted weights and the head once,
    the weights of the `experts_hit` experts that got a token (summed over
    the expert layers) once, K and V of the positions attended on each
    attention layer once, each row's conv state read and written."""
    weights = (unrouted_layer_weights(cfg) + conv_layers(cfg) * conv_taps(cfg)
               + cfg["hidden_size"] * cfg["vocab_size"]
               + experts_hit * expert_weights(cfg))
    return (weights * itemsize
            + kv_bytes_per_position(cfg, itemsize) * attention_layers(cfg)
            * positions
            + 2 * state_bytes_per_row(cfg, itemsize) * rows)
