"""Operations and bytes of a looped decoder: L layers of weights applied T =
`total_ut_steps` times, each pass with keys and values of its own.

What the mathematics asks for, whatever implements it (see `opsbytes.py`): a
decode step uses every layer weight once in EVERY pass, so it needs them T
times (an implementation that kept a layer's weights on the chip across the
passes would still be read against this count), and the head once; a cached
token holds K and V of T x L cache layers. Configurations arrive as the dicts
of `perfbench/configs/*.json`.
"""

from perfbench import opsbytes


def passes(cfg):
    return int(cfg.get("total_ut_steps", 1))


def layer_passes(cfg):
    """Layers a token goes through: the stack's, times its passes."""
    return passes(cfg) * cfg["num_hidden_layers"]


def layer_parameters(cfg):
    """One layer's parameters: its matrices and its four norm gains."""
    return opsbytes.llama_layer_weights(cfg) + 4 * cfg["hidden_size"]


def parameter_count(cfg):
    """Every parameter of the model, each counted once: the layers, the
    embedding and the head (one matrix if tied), the final norm and the exit
    gate (hidden + 1)."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    tables = 1 if cfg.get("tie_word_embeddings", False) else 2
    return (cfg["num_hidden_layers"] * layer_parameters(cfg)
            + tables * h * vocab + h + (h + 1))


def decode_weight_reads(cfg):
    """Weights one decode step multiplies by: every layer matrix once in
    each pass, and the head (the embedding is read one row a token; norms
    and the gate are noise)."""
    return (layer_passes(cfg) * opsbytes.llama_layer_weights(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of one cached position over all T x L cache layers."""
    _, nkv, d = opsbytes._attn_dims(cfg)
    return 2 * layer_passes(cfg) * nkv * d * itemsize


def decode_flops(cfg, context_len):
    """One new token that attends to `context_len` cached positions in each
    of the T x L layer passes."""
    nq, _, d = opsbytes._attn_dims(cfg)
    return (2 * decode_weight_reads(cfg)
            + layer_passes(cfg) * 4 * nq * d * context_len)


def decode_bytes(cfg, context_lens, itemsize=2):
    """Bytes one decode step needs: the layer weights once a pass, the head
    once, and the K and V of each active row's live context once."""
    return (decode_weight_reads(cfg) * itemsize
            + kv_bytes_per_token(cfg, itemsize) * sum(context_lens))
