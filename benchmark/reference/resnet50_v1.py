"""Plain float32 reference for the ResNet v1 bottleneck training step.

He et al. 2015 (arXiv:1512.03385), Table 1, as MXNet's Gluon model zoo
writes it: the stride of a stage sits in the first 1x1 convolution of its
first block, the 1x1 convolutions of a block's body carry a bias (the 3x3
and the projection shortcut do not), BatchNorm uses the batch's biased
variance with eps 1e-5. The 7x7 stride-2 stem is written as the plain
convolution; the program's space-to-depth stem is the same sum.

Nothing here imports the program. Weights are made from the seed by
:func:`init_params`; the driver loads the same values into the program.
The optimizer is SGD with momentum as MXNet states it,
``mom = m*mom - lr*(g + wd*w); w += mom``, on float32 weights.

``quant`` names a lower precision for the *control* of the benchmark's
correctness check (a key of ``PRECISIONS``): every tensor that a
program computing in that precision would hold in it (the weights and
inputs of each convolution and of the dense layer, and the output of each
convolution, BatchNorm, activation and residual sum) is rounded to it.
Forward only: gradients pass straight through the rounding and the
arithmetic stays float32.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def param_specs(model):
    """``[(name, shape, kind)]`` in the order the program's
    ``collect_params()`` lists them. ``kind`` is one of ``conv``, ``bias``,
    ``gamma``, ``beta``, ``mean``, ``var``, ``dense``, ``dense_bias``."""
    layers, ch = model["layers"], model["channels"]
    specs = []

    def bn(prefix, c):
        specs.extend([(prefix + "_gamma", (c,), "gamma"),
                      (prefix + "_beta", (c,), "beta"),
                      (prefix + "_running_mean", (c,), "mean"),
                      (prefix + "_running_var", (c,), "var")])

    specs.append(("stem_weight", (ch[0], 7, 7, 3), "conv"))
    bn("stem_bn", ch[0])
    for s, n in enumerate(layers):
        cin, cout = ch[s], ch[s + 1]
        mid = cout // 4
        for b in range(n):
            p = f"stage{s + 1}_block{b}"
            inc = cin if b == 0 else cout
            specs.append((p + "_conv1_weight", (mid, 1, 1, inc), "conv"))
            specs.append((p + "_conv1_bias", (mid,), "bias"))
            bn(p + "_bn1", mid)
            specs.append((p + "_conv2_weight", (mid, 3, 3, mid), "conv"))
            bn(p + "_bn2", mid)
            specs.append((p + "_conv3_weight", (cout, 1, 1, mid), "conv"))
            specs.append((p + "_conv3_bias", (cout,), "bias"))
            bn(p + "_bn3", cout)
            if b == 0 and cin != cout:
                specs.append((p + "_down_weight", (cout, 1, 1, cin), "conv"))
                bn(p + "_down_bn", cout)
    specs.append(("dense_weight", (model["classes"], ch[-1]), "dense"))
    specs.append(("dense_bias", (model["classes"],), "dense_bias"))
    return specs


def trainable(specs):
    """Names of the leaves the optimizer moves."""
    return [n for n, _, k in specs if k not in ("mean", "var")]


def matrices(specs):
    """Names of the trainable leaves of more than one axis: the
    convolution kernels and the dense matrix."""
    return [n for n, shape, k in specs
            if len(shape) > 1 and k not in ("mean", "var")]


def init_params(seed, model):
    """All leaves in one jitted call from ``seed``: He-normal convolutions
    and dense weights, gamma 1, everything else 0 (running variance 1).
    Values are rounded to bfloat16 and kept in float32, so that a program
    that holds bfloat16 weights starts from exactly these."""
    specs = param_specs(model)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(specs):
            if kind in ("conv", "dense"):
                fan_in = 1
                for d in shape[1:]:
                    fan_in *= d
                w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * (2.0 / fan_in) ** 0.5
                # not astype(bfloat16).astype(float32): XLA may drop
                # that pair, and on the TPU it does
                out[name] = lax.reduce_precision(w, 8, 7)
            elif kind in ("gamma", "var"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    return make(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


#: (exponent bits, mantissa bits) of the precisions a control may name
PRECISIONS = {"bfloat16": (8, 7), "float8_e5m2": (5, 2)}


def _rounder(quant):
    """Round a tensor to ``quant`` and pass gradients straight through.
    ``lax.reduce_precision`` does the rounding: XLA is free to drop a cast
    to a narrower type and back (``xla_allow_excess_precision``), and on
    the TPU it does."""
    if quant is None:
        return lambda a: a
    exponent, mantissa = PRECISIONS[quant]

    def q(a):
        r = lax.reduce_precision(a, exponent, mantissa)
        return a + lax.stop_gradient(r - a)
    return q


def _conv(x, w, stride, pad, q, bias=0.0):
    return q(lax.conv_general_dilated(
        q(x), q(w), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "OHWI", "NHWC"),
        precision=lax.Precision.HIGHEST) + bias)


def _bn(x, gamma, beta, q, relu=False):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + EPS) * gamma + beta
    return q(jax.nn.relu(y) if relu else y)


def _block(p, x, prefix, stride, q):
    def g(k):
        return p[prefix + k]
    y = _conv(x, g("_conv1_weight"), stride, 0, q, g("_conv1_bias"))
    y = _bn(y, g("_bn1_gamma"), g("_bn1_beta"), q, relu=True)
    y = _conv(y, g("_conv2_weight"), 1, 1, q)
    y = _bn(y, g("_bn2_gamma"), g("_bn2_beta"), q, relu=True)
    y = _conv(y, g("_conv3_weight"), 1, 0, q, g("_conv3_bias"))
    y = _bn(y, g("_bn3_gamma"), g("_bn3_beta"), q)
    if prefix + "_down_weight" in p:
        x = _conv(x, g("_down_weight"), stride, 0, q)
        x = _bn(x, g("_down_bn_gamma"), g("_down_bn_beta"), q)
    return q(jax.nn.relu(y + x))


def forward(p, x, model, quant=None):
    """Training-mode logits ``[N, classes]`` for NHWC images ``x``."""
    q = _rounder(quant)
    x = x.astype(jnp.float32)
    y = _conv(x, p["stem_weight"], 2, 3, q)
    y = _bn(y, p["stem_bn_gamma"], p["stem_bn_beta"], q, relu=True)
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    for s, n in enumerate(model["layers"]):
        for b in range(n):
            prefix = f"stage{s + 1}_block{b}"
            stride = 2 if (b == 0 and s > 0) else 1
            # one block's activations live at a time, so that float32 at
            # the cell's batch fits the chip beside nothing else
            blk = jax.checkpoint(functools.partial(
                _block, prefix=prefix, stride=stride, q=q))
            sub = {k: v for k, v in p.items() if k.startswith(prefix + "_")}
            y = blk(sub, y)
    y = jnp.mean(y, axis=(1, 2))
    return q(jnp.dot(q(y), q(p["dense_weight"]).T,
                     precision=lax.Precision.HIGHEST) + p["dense_bias"])


def loss_rows(p, x, y, model, quant=None):
    """Cross entropy of each row, float32."""
    logp = jax.nn.log_softmax(forward(p, x, model, quant), axis=-1)
    return -jnp.take_along_axis(logp, y[:, None].astype(jnp.int32),
                                axis=1)[:, 0]


def train_steps(params, xs, ys, model, opt, steps=3, quant=None,
                rows=None):
    """Follow ``steps`` SGD-momentum steps over batches ``xs[i], ys[i]``.

    Returns ``(losses, grad_norms, update_norms, row_losses)``: the mean
    loss of each step, by trainable leaf (a dict by name) the norm of step
    1's gradient of the mean loss and the norm of the weights' change
    after the last step, and step 1's loss row by row. ``rows`` keeps only
    the first ``rows`` rows of each batch, the mean taken over them: the
    "half of the batch left out" fault, for reading what the comparison
    makes of it."""
    specs = param_specs(model)
    names = trainable(specs)
    lr, m, wd = opt["learning_rate"], opt["momentum"], opt["wd"]

    @jax.jit
    def one(w, aux, mom, x, y):
        if rows is not None:
            x, y = x[:rows], y[:rows]

        def objective(w):
            rows_ = loss_rows({**aux, **w}, x, y, model, quant)
            return jnp.mean(rows_), rows_
        (loss, by_row), g = jax.value_and_grad(objective, has_aux=True)(w)
        mom = {k: m * mom[k] - lr * (g[k] + wd * w[k]) for k in w}
        new = {k: w[k] + mom[k] for k in w}
        return loss, by_row, new, mom, {k: jnp.linalg.norm(g[k]) for k in w}

    w0 = {k: params[k] for k in names}
    aux = {k: v for k, v in params.items() if k not in w0}
    w, mom = w0, {k: jnp.zeros_like(v) for k, v in w0.items()}
    losses, grad_norms, row_losses = [], None, None
    for i in range(steps):
        loss, by_row, w, mom, gn = one(w, aux, mom, xs[i], ys[i])
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(v) for k, v in gn.items()}
            row_losses = [float(v) for v in by_row]
    change = jax.jit(lambda a, b: {k: jnp.linalg.norm(a[k] - b[k])
                                   for k in a})(w, w0)
    return (losses, grad_norms, {k: float(v) for k, v in change.items()},
            row_losses)
