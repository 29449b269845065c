"""Plain float32 reference for a GPT-2-shaped decoder: learned positions,
pre-LayerNorm blocks, multi-head causal attention, a 4x GELU (tanh) MLP, a
final LayerNorm and an output head of its own.

A copy of the dense mathematics of ``TinyDecoder.forward``
(``mxnet_tpu/serving/llm/model.py``), so that a later PR to that file
cannot move the yardstick. Departures from the published GPT-2 layer, the
program's and kept here: no biases on the four attention projections, the
output head not tied to the embedding. Nothing here imports the program.
Matrix products run at ``highest`` precision; ``quant`` names the lower
precision of the correctness check's *control* (a key of ``PRECISIONS``):
both operands of every matrix product are then rounded to it
(``lax.reduce_precision``: XLA may drop a cast to a narrower type and back,
and on the TPU it does), the sums and the residual stream stay float32.
"""
import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
#: (exponent bits, mantissa bits) of the precisions a control may name
PRECISIONS = {"bfloat16": (8, 7), "float8_e5m2": (5, 2)}


def init_params(seed, model, dtype=jnp.float32):
    """The parameter tree in one jitted call from ``seed``: normal weights
    scaled by the square root of their fan-in (embedding 0.5, positions
    0.1), LayerNorm gains 1, biases 0."""
    d, ff, vocab = model["d_model"], model["d_ff"], model["vocab_size"]
    n_layers, ctx = model["num_layers"], model["max_context"]

    @jax.jit
    def make(key):
        count = [0]

        def w(*shape, scale=None):
            count[0] += 1
            scale = shape[0] ** -0.5 if scale is None else scale
            return (jax.random.normal(jax.random.fold_in(key, count[0]),
                                      shape, jnp.float32)
                    * scale).astype(dtype)

        def ones(n):
            return jnp.ones((n,), dtype)

        def zeros(n):
            return jnp.zeros((n,), dtype)

        layers = [{"ln1_g": ones(d), "ln1_b": zeros(d),
                   "wq": w(d, d), "wk": w(d, d), "wv": w(d, d),
                   "wo": w(d, d), "ln2_g": ones(d), "ln2_b": zeros(d),
                   "w1": w(d, ff), "b1": zeros(ff), "w2": w(ff, d),
                   "b2": zeros(d)} for _ in range(n_layers)]
        return {"embed": w(vocab, d, scale=0.5), "pos": w(ctx, d, scale=0.1),
                "lnf_g": ones(d), "lnf_b": zeros(d), "head": w(d, vocab),
                "layers": layers}

    return make(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


def _layer_norm(x, g, b):
    m = x.mean(axis=-1, keepdims=True)
    v = ((x - m) ** 2).mean(axis=-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + EPS) * g + b


def _rounder(quant):
    if quant is None:
        return lambda a: a
    exponent, mantissa = PRECISIONS[quant]
    return lambda a: lax.reduce_precision(a, exponent, mantissa)


def forward(params, tokens, model, quant=None):
    """Logits ``[T, vocab]`` (float32) of one sequence ``tokens [T]``,
    every position attending to itself and what came before it."""
    heads = model["num_heads"]
    q_ = _rounder(quant)

    def _mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=lax.Precision.HIGHEST)
    p = params
    T = tokens.shape[0]
    h = p["embed"][tokens] + p["pos"][:T]
    mask = jnp.tril(jnp.ones((T, T), bool))
    for lp in p["layers"]:
        x = _layer_norm(h, lp["ln1_g"], lp["ln1_b"])
        q = _mm(x, lp["wq"]).reshape(T, heads, -1)
        k = _mm(x, lp["wk"]).reshape(T, heads, -1)
        v = _mm(x, lp["wv"]).reshape(T, heads, -1)
        s = jnp.einsum("qhd,khd->hqk", q_(q), q_(k),
                       precision=lax.Precision.HIGHEST)
        s = jnp.where(mask, s * q.shape[-1] ** -0.5, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum("hqk,khd->qhd", q_(a), q_(v),
                         precision=lax.Precision.HIGHEST).reshape(T, -1)
        h = h + _mm(att, lp["wo"])
        x2 = _layer_norm(h, lp["ln2_g"], lp["ln2_b"])
        h = h + _mm(jax.nn.gelu(_mm(x2, lp["w1"]) + lp["b1"]), lp["w2"]) \
            + lp["b2"]
    logits = _mm(_layer_norm(h, p["lnf_g"], p["lnf_b"]), p["head"])
    return logits


def served_token_gaps(params, prompt, served, model, pad_to, quant=None):
    """For each served token, how far its logit lies below the best logit
    at its position: ``best - logit[served]``, an array of
    ``len(served)``. With ``quant`` the served tokens are ignored and the
    gap is read for the token that the lower precision puts first (the
    control). Sequences are padded to ``pad_to`` so that one program
    serves every length."""
    n_p, n_s = len(prompt), len(served)
    toks = jnp.zeros((pad_to,), jnp.int32).at[:n_p + n_s].set(
        jnp.asarray(list(prompt) + list(served), jnp.int32))
    shape = tuple(sorted(model.items()))
    logits = _logits(params, toks, shape, None)
    # the logits at position i choose token i + 1
    chosen = jnp.roll(toks, -1) if quant is None else jnp.argmax(
        _logits(params, toks, shape, quant), axis=-1)
    gaps = logits.max(axis=-1) - jnp.take_along_axis(
        logits, chosen[:, None], axis=1)[:, 0]
    return gaps[n_p - 1:n_p - 1 + n_s]


_logits = jax.jit(
    lambda params, toks, shape, quant: forward(params, toks, dict(shape),
                                               quant),
    static_argnums=(2, 3))
