"""Operations and bytes that the algorithm requires, from a cell's shapes.

What the model needs, whatever implements it: never XLA's
``cost_analysis``, which counts what the compiled program does (padding,
recomputation and layout copies included). A multiply-add is two
operations. Each function takes the ``model`` group of a configuration
file.
"""


def resnet_conv_macs(model):
    """Multiply-adds of one image's forward pass through the convolutions
    of a ResNet v1 of bottleneck blocks: stem 7x7/2, max pool /2, then
    ``layers[s]`` blocks a stage of 1x1 (carrying the stage's stride),
    3x3, 1x1, with a 1x1 projection on each stage's first shortcut."""
    ch, size = model["channels"], model["image"] // 2
    macs = size * size * 7 * 7 * 3 * ch[0]
    size //= 2
    for s, n in enumerate(model["layers"]):
        cin, cout = ch[s], ch[s + 1]
        mid = cout // 4
        if s > 0:
            size //= 2
        for b in range(n):
            inc = cin if b == 0 else cout
            macs += size * size * (inc * mid + 9 * mid * mid + mid * cout)
            if b == 0 and cin != cout:
                macs += size * size * cin * cout
    return macs


def resnet_dense_macs(model):
    return model["channels"][-1] * model["classes"]


def resnet_train_flops_per_image(model):
    """Forward and backward: the backward pass multiplies each layer's
    output gradient once with the weights and once with the input, so it
    costs two forward passes. The stem's input gradient is not needed,
    but the count keeps the usual 3x and says so."""
    return 3 * 2 * (resnet_conv_macs(model) + resnet_dense_macs(model))


def resnet_conv_train_flops_per_image(model):
    """The convolutions' share of :func:`resnet_train_flops_per_image`."""
    return 3 * 2 * resnet_conv_macs(model)


def decoder_flops_per_token(model, context):
    """Forward operations for one token of a GPT-2-shaped decoder at a
    cache of ``context`` tokens: four d x d attention projections, the
    two d x d_ff MLP matrices, attention scores and values over the
    context, and the d x vocab head."""
    d, ff = model["d_model"], model["d_ff"]
    per_layer = 2 * (4 * d * d + 2 * d * ff) + 2 * 2 * context * d
    return model["num_layers"] * per_layer + 2 * d * model["vocab_size"]


def decoder_weight_bytes(model, bytes_per_weight=4):
    """Bytes of the weights a decode step has to read once."""
    d, ff = model["d_model"], model["d_ff"]
    n = model["num_layers"] * (4 * d * d + 2 * d * ff) \
        + d * model["vocab_size"]
    return n * bytes_per_weight


def ragged_attention_bytes(model, contexts, block_size, bytes_per_value=4):
    """Bytes of K and V pages that one step's sequences require: each
    sequence's context rounded up to whole pages, for every layer."""
    d = model["d_model"]
    pages = sum(-(-c // block_size) for c in contexts)
    return 2 * model["num_layers"] * pages * block_size * d * bytes_per_value
