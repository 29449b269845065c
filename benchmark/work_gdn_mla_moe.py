"""Operations and bytes that a GigaChat-3.5-shaped decoder requires
(``model_type`` ``gigachat3_5``), from a cell's shapes: GatedDeltaNet
layers over a slot state, latent attention (MLA) with a query latent and
an output gate, a dense MLP or routed-plus-shared experts of which the
chip holds a share, an untied head.

As ``work.py`` has it: what the model needs, whatever implements it, a
multiply-add two operations, each function over the ``model`` group of a
configuration file alone. Latent attention is counted in its *expanded*
form, as ``work_mla_moe.py`` counts it. The routed experts are not in
``decoder_flops_per_token``: the pairs that this chip computes are counted
from the program's step spans, a pair at ``expert_pair_flops``; a pair
routed to an expert held elsewhere is no work of this chip's.
"""
from benchmark.work_mla_moe import swiglu_macs


def layer_counts(model):
    """``(GatedDeltaNet layers, latent layers, dense layers, expert
    layers)``."""
    full = len(model["full_attention_layers"])
    dense = model["first_k_dense_replace"]
    return (model["num_layers"] - full, full, dense,
            model["num_layers"] - dense)


def gdn_projection_macs(model):
    """Multiply-adds of one token through one GatedDeltaNet layer's
    projections: q, k, v, z, a, b and the output."""
    d = model["d_model"]
    qk = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    v = model["linear_num_value_heads"] * model["linear_value_head_dim"]
    return d * (2 * qk + 2 * v + 2 * model["linear_num_value_heads"]) \
        + v * d


def gdn_conv_macs(model):
    """Multiply-adds of one token through the causal convolution over
    ``[q | k | v]``."""
    qk = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    v = model["linear_num_value_heads"] * model["linear_value_head_dim"]
    return (2 * qk + v) * model["linear_conv_kernel_dim"]


def delta_rule_flops(model):
    """Operations of one token through the delta rule of one layer: per
    value head the decay (``Dk x Dv`` products), ``S^T k``, the rank-1
    write and ``S^T q`` (``Dk x Dv`` multiply-adds each)."""
    return model["linear_num_value_heads"] * 7 \
        * model["linear_key_head_dim"] * model["linear_value_head_dim"]


def latent_projection_macs(model):
    """Multiply-adds of one token through one latent layer's
    projections: the query latent and its heads, the key-value latent
    and its keys and values, the output gate and the output."""
    d, heads = model["d_model"], model["num_heads"]
    qr, rank = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
    return d * qr + qr * heads * (nope + rope) + d * (rank + rope) \
        + rank * heads * (nope + vd) + 2 * d * heads * vd


def decoder_flops_per_token(model, context):
    """Forward operations for one token at a cache of ``context`` tokens,
    the routed experts left out: every GatedDeltaNet layer's projections,
    convolution and delta rule; every latent layer's projections and its
    scores and values over the context (``2 x context x heads x (nope +
    rope + v)``); the dense MLP of the leading layers; the router over all
    ``router_experts`` and the shared experts of the others; the head."""
    d, heads = model["d_model"], model["num_heads"]
    linear, full, dense, expert = layer_counts(model)
    per_head = model["qk_nope_head_dim"] + model["qk_rope_head_dim"] \
        + model["v_head_dim"]
    shared = model["n_shared_experts"] * model["moe_intermediate_size"]
    return linear * (2 * gdn_projection_macs(model)
                     + 2 * gdn_conv_macs(model) + delta_rule_flops(model)) \
        + full * (2 * latent_projection_macs(model)
                  + 2 * context * heads * per_head) \
        + dense * 2 * swiglu_macs(d, model["d_ff"]) \
        + expert * 2 * (d * model["router_experts"]
                        + swiglu_macs(d, shared)) \
        + 2 * d * model["vocab_size"]


def expert_bytes(model, bytes_per_weight=2):
    """Bytes of one routed expert's three matrices."""
    return swiglu_macs(model["d_model"], model["moe_intermediate_size"]) \
        * bytes_per_weight


def expert_pair_flops(model):
    """Operations of one (token, expert) pair."""
    return 2 * swiglu_macs(model["d_model"], model["moe_intermediate_size"])


def state_bytes_per_row(model, bytes_per_value=4):
    """Bytes of one slot's delta-rule state over the GatedDeltaNet layers,
    ``[value heads, Dk, Dv]`` float32 a layer (the convolution's window
    is kept apart, and the state kernel does not move it)."""
    return layer_counts(model)[0] * model["linear_num_value_heads"] \
        * model["linear_key_head_dim"] * model["linear_value_head_dim"] \
        * bytes_per_value


def state_step_bytes(model, rows):
    """Bytes the state kernel has to move in one step of ``rows`` rows:
    each row's state read once and written once, in every layer."""
    return 2 * rows * state_bytes_per_row(model)


def delta_rule_step_flops(model, tokens):
    """Operations of the delta rule for ``tokens`` tokens fed, every
    GatedDeltaNet layer."""
    return tokens * layer_counts(model)[0] * delta_rule_flops(model)
