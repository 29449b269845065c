"""Operations and bytes that a Granite-4.0-H-shaped decoder requires, from
a cell's shapes: Mamba-2 state-space layers, grouped-query attention
layers, a SwiGLU MLP after each.

As ``work.py`` has it: what the model needs, whatever implements it, a
multiply-add two operations, each function over the ``model`` group of a
configuration file.
"""


def layer_counts(model):
    """``(state-space layers, attention layers)``."""
    kinds = model["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def mamba_macs_per_token(model):
    """Multiply-adds of one token through one state-space mixer: the
    input projection to ``z``, ``xBC`` and ``dt``, the convolution's
    ``mamba_d_conv`` taps over ``xBC``, the recurrence (every element of
    the state ``[H, P, N]`` decayed and added to, two, and read once into
    ``y``, one) and the output projection."""
    d, n = model["d_model"], model["mamba_d_state"]
    inner = model["mamba_n_heads"] * model["mamba_d_head"]
    conved = inner + 2 * n
    return d * (2 * inner + 2 * n + model["mamba_n_heads"]) \
        + conved * model["mamba_d_conv"] + 3 * inner * n + inner * d


def attention_macs_per_token(model, context):
    """Multiply-adds of one token through one attention mixer at a cache
    of ``context`` tokens: q and o over all heads, k and v over the
    key-value heads, scores and values over the context for every query
    head."""
    d, heads = model["d_model"], model["num_heads"]
    hd = d // heads
    return 2 * d * heads * hd + 2 * d * model["num_key_value_heads"] * hd \
        + 2 * context * heads * hd


def decoder_flops_per_token(model, context):
    """Forward operations for one token at a cache of ``context`` tokens:
    every layer's mixer and its SwiGLU MLP, and the head (the embedding
    itself)."""
    d = model["d_model"]
    mamba, attention = layer_counts(model)
    return 2 * (mamba * mamba_macs_per_token(model)
                + attention * attention_macs_per_token(model, context)
                + (mamba + attention) * 3 * d * model["d_ff"]
                + d * model["vocab_size"])


def state_bytes_per_row(model, bytes_per_value=4):
    """Bytes of recurrent state ``[H, P, N]`` that one sequence holds in
    one state-space layer: what a step reads once and writes once for
    each of its rows, whatever the row's context."""
    return model["mamba_n_heads"] * model["mamba_d_head"] \
        * model["mamba_d_state"] * bytes_per_value


def state_step_bytes(model, rows):
    """Bytes of state that a step of ``rows`` sequences moves: every
    state-space layer's state of each row, read once and written once."""
    return 2 * rows * layer_counts(model)[0] * state_bytes_per_row(model)


def kv_cache_bytes(model, contexts, block_size, bytes_per_value=2):
    """Bytes of K and V pages that one step's sequences require: each
    sequence's context rounded up to whole pages of ``2 x
    num_key_value_heads x head size`` values a token, for every
    attention layer."""
    hd = model["d_model"] // model["num_heads"]
    pages = sum(-(-c // block_size) for c in contexts)
    return layer_counts(model)[1] * pages * block_size \
        * 2 * model["num_key_value_heads"] * hd * bytes_per_value
