"""Operations and bytes that a DeepSeek-V3-shaped decoder requires, from a
cell's shapes: latent attention (MLA) and routed-plus-shared experts.

As ``work.py`` has it: what the model needs, whatever implements it, a
multiply-add two operations, each function over the ``model`` group of a
configuration file. Attention is counted in its *expanded* form (what the
published layer computes for one more token); the absorbed form the paged
path runs does more arithmetic on the same bytes.
"""


def expert_layers(model):
    return max(0, model["num_layers"] - model["first_k_dense_replace"])


def attention_projection_macs(model):
    """Multiply-adds of one token through one layer's four projections:
    q, the down-projection to the latent, the latent's up-projection to
    its own keys and values, and the output."""
    d, heads, rank = model["d_model"], model["num_heads"], \
        model["kv_lora_rank"]
    nope, rope, vd = model["qk_nope_head_dim"], \
        model["qk_rope_head_dim"], model["v_head_dim"]
    return d * heads * (nope + rope) + d * (rank + rope) \
        + rank * heads * (nope + vd) + heads * vd * d


def swiglu_macs(d, width):
    return 3 * d * width


def decoder_flops_per_token(model, context):
    """Forward operations for one token at a cache of ``context`` tokens:
    every layer's projections and its scores and values over the context
    (``2 * context * heads * (nope + rope + v)``), the dense MLP of the
    leading layers, the router, ``num_experts_per_tok`` routed and the
    shared experts of the others, and the head."""
    d, heads = model["d_model"], model["num_heads"]
    per_head = model["qk_nope_head_dim"] + model["qk_rope_head_dim"] \
        + model["v_head_dim"]
    attention = 2 * attention_projection_macs(model) \
        + 2 * context * heads * per_head
    n_expert = expert_layers(model)
    n_dense = model["num_layers"] - n_expert
    m = model["moe_intermediate_size"]
    expert_layer = 2 * (d * model["n_routed_experts"] + swiglu_macs(
        d, m) * (model["num_experts_per_tok"] + model["n_shared_experts"]))
    return model["num_layers"] * attention \
        + n_dense * 2 * swiglu_macs(d, model["d_ff"]) \
        + n_expert * expert_layer + 2 * d * model["vocab_size"]


def latent_cache_bytes(model, contexts, block_size, bytes_per_value=2):
    """Bytes of latent pages that one step's sequences require: each
    sequence's context rounded up to whole pages of ``kv_lora_rank +
    qk_rope_head_dim`` values a token, for every layer."""
    pages = sum(-(-c // block_size) for c in contexts)
    return model["num_layers"] * pages * block_size * (
        model["kv_lora_rank"] + model["qk_rope_head_dim"]) * bytes_per_value


def expert_bytes(model, bytes_per_weight=2):
    """Bytes of one routed expert's three matrices."""
    return swiglu_macs(model["d_model"], model["moe_intermediate_size"]) \
        * bytes_per_weight


def expert_pair_flops(model):
    """Operations of one (token, expert) pair."""
    return 2 * swiglu_macs(model["d_model"],
                           model["moe_intermediate_size"])
