"""Operations and bytes that a Command-A+-shaped decoder requires
(``model_type`` ``cohere2_moe``), from a cell's shapes: parallel blocks of
grouped-query attention (sliding-window layers and full layers) and a
routed-plus-shared expert layer of which the chip holds a share, the head
the embedding.

As ``work.py`` has it: what the model needs, whatever implements it, a
multiply-add two operations, each function over the ``model`` group of a
configuration file alone. Attention is counted by the keys a token SEES:
``min(p + 1, sliding_window)`` on a sliding layer, ``p + 1`` on a full
one. The routed experts are not in ``decoder_flops_per_token``: the pairs
this chip computes are counted from the program's step spans, a pair at
``expert_pair_flops``; a pair routed to an expert held elsewhere is no work
of this chip's.
"""
import importlib.util
import os

from benchmark.work_mla_moe import swiglu_macs

SLIDING = "sliding_attention"


def layer_counts(model):
    """``(sliding-window layers, full layers)``."""
    sliding = sum(1 for k in model["layer_types"] if k == SLIDING)
    return sliding, model["num_layers"] - sliding


def projection_macs(model):
    """Multiply-adds of one token through one layer's attention
    projections: queries, keys, values and the output."""
    d, hd = model["d_model"], model["head_dim"]
    return 2 * d * hd * (model["num_heads"] + model["num_key_value_heads"])


def attention_flops(model, keys):
    """Operations of one token's scores and values over ``keys`` keys, all
    its query heads."""
    return 4 * keys * model["num_heads"] * model["head_dim"]


def visible_keys(model, context):
    """``(keys a token at a cache of ``context`` tokens sees on a sliding
    layer, on a full layer)``: its own position included."""
    return min(context, model["sliding_window"]), context


def dense_flops_per_token(model):
    """Forward operations for one token that do not depend on its
    context, the routed experts left out: every layer's projections, the
    router over all ``router_experts`` and the shared experts; the head
    over the vocabulary."""
    d = model["d_model"]
    shared = model["num_shared_experts"] * model["moe_intermediate_size"]
    return model["num_layers"] * 2 * (
        projection_macs(model) + d * model["router_experts"]
        + swiglu_macs(d, shared)) + 2 * d * model["vocab_size"]


def decoder_flops_per_token(model, context):
    """Forward operations for one token at a cache of ``context`` tokens
    (itself included), the routed experts left out:
    ``dense_flops_per_token`` and every layer's scores and values over the
    keys it sees."""
    sliding, full = layer_counts(model)
    win, whole = visible_keys(model, context)
    return dense_flops_per_token(model) \
        + sliding * attention_flops(model, win) \
        + full * attention_flops(model, whole)


def expert_bytes(model, bytes_per_weight=2):
    """Bytes of one routed expert's three matrices."""
    return swiglu_macs(model["d_model"], model["moe_intermediate_size"]) \
        * bytes_per_weight


def expert_pair_flops(model):
    """Operations of one (token, expert) pair."""
    return 2 * swiglu_macs(model["d_model"], model["moe_intermediate_size"])


def page_bytes(model, block_size, bytes_per_value=2):
    """Bytes of one layer's page: ``block_size`` rows of a token's value
    and key heads."""
    return block_size * 2 * model["num_key_value_heads"] \
        * model["head_dim"] * bytes_per_value


def attention_row(model, block_size, n_tokens, context):
    """``(bytes, operations)`` the attention kernel requires for one row of
    a step (``n_tokens`` fed, ``context`` after them), every layer: the
    pages the row's keys lie in, read once a layer (a sliding layer's from
    the page of its first token's first visible key, a full layer's all of
    them), and its tokens' scores and values over the keys they see."""
    sliding, full = layer_counts(model)
    W = model["sliding_window"]
    first = context - n_tokens
    last_page = (context - 1) // block_size
    win_pages = last_page - max(0, first - W + 1) // block_size + 1
    pages = sliding * win_pages + full * (last_page + 1)
    ops = 0
    for p in range(first, context):
        win, whole = visible_keys(model, p + 1)
        ops += sliding * attention_flops(model, win) \
            + full * attention_flops(model, whole)
    return pages * page_bytes(model, block_size), ops


def traced_step_spans(seen):
    """The program's ``mxtpu.llm.step`` spans of a run's traced steps,
    matched by their ``rows`` as ``kernel.moe_experts_roofline.serve``
    matches them: its own function, read from its file."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "layer_metrics",
                        "kernel.moe_experts_roofline.serve.py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_kernel_moe_experts_roofline_serve", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.traced_step_spans(seen)
