"""Operations and bytes that a block-diffusion expert decoder requires
(``model_type`` ``sdar_moe``), from a cell's shapes: grouped-query
attention under a block-causal mask, a softmax top-k router over SwiGLU
experts, an untied head.

As ``work.py`` has it: what the model needs, whatever implements it, a
multiply-add two operations, each function over the ``model`` group of a
configuration file alone. A token *fed* counts, whatever pass of its block
it is fed in: a change that answers the same with fewer passes does less
of this work.
"""


def attention_projection_macs(model):
    """Multiply-adds of one token through one layer's four projections:
    q and o over all heads, k and v over the key-value heads."""
    d, hd = model["d_model"], model["head_dim"]
    return 2 * d * model["num_heads"] * hd \
        + 2 * d * model["num_key_value_heads"] * hd


def expert_macs(model):
    """Multiply-adds of one token through one expert's three matrices."""
    return 3 * model["d_model"] * model["moe_intermediate_size"]


def mean_context(model, n_tokens, context):
    """The mean number of keys a row's ``n_tokens`` see, the row's
    context ``context`` after them: a position sees up to the end of its
    block, so a block fed whole sees all of ``context`` and a chunk of
    whole blocks ``(n_tokens - block_length) / 2`` fewer on average."""
    return context - (n_tokens - model["block_length"]) / 2


def decoder_flops_per_token(model, context):
    """Forward operations for one token that sees ``context`` keys: every
    layer's projections, its scores and values over the keys (``2 x
    context x heads x 2 x head_dim``), the router, ``num_experts_per_tok``
    experts, and the head."""
    d, heads, hd = model["d_model"], model["num_heads"], model["head_dim"]
    layer = 2 * attention_projection_macs(model) \
        + 2 * context * heads * 2 * hd \
        + 2 * (d * model["num_experts"]
               + model["num_experts_per_tok"] * expert_macs(model))
    return model["num_layers"] * layer + 2 * d * model["vocab_size"]


def attention_flops(model, n_tokens, context):
    """Operations of the scores and values alone for a row's ``n_tokens``,
    every layer: what the attention kernel has to compute."""
    return model["num_layers"] * n_tokens * 2 * mean_context(
        model, n_tokens, context) * model["num_heads"] \
        * 2 * model["head_dim"]


def kv_cache_bytes(model, contexts, block_size, bytes_per_value=2):
    """Bytes of K and V pages that one step's sequences require: each
    sequence's context rounded up to whole pages of ``2 x
    num_key_value_heads x head_dim`` values a token, for every layer."""
    pages = sum(-(-c // block_size) for c in contexts)
    return model["num_layers"] * pages * block_size \
        * 2 * model["num_key_value_heads"] * model["head_dim"] \
        * bytes_per_value


def expert_bytes(model, bytes_per_weight=2):
    """Bytes of one expert's three matrices."""
    return expert_macs(model) * bytes_per_weight


def expert_pair_flops(model):
    """Operations of one (token, expert) pair."""
    return 2 * expert_macs(model)


def experts_held(model):
    """Experts held over all the layers: what a step's experts hit are a
    share of."""
    return model["num_experts"] * model["num_layers"]
