"""Plain float32 reference for a Granite-4.0-H-shaped decoder
(``model_type`` ``granitemoehybrid``, its dense members): Mamba-2
state-space layers and grouped-query attention layers without positions,
each followed by a SwiGLU MLP, RMSNorm, four scalar multipliers, the
output head tied to the embedding.

For ``h [T, d]`` at positions ``0..T-1``, ``h0 = embed[token] *
embedding_multiplier``, and with ``r = residual_multiplier`` one layer is

    h = h + r * mixer(rms(h; norm1))          mixer by layer_types[i]
    x = rms(h; norm2);  h = h + r * (silu(x w_gate) * (x w_up)) w_down

and ``logits = rms(h; norm_f) embed^T / logits_scaling``.

*Attention mixer* (no bias, no rotary or learned positions): ``q = x wq``
as ``num_heads`` heads, ``k, v = x wk, x wv`` as ``num_key_value_heads``
heads; query head ``j`` attends kv head ``j // (num_heads //
num_key_value_heads)`` causally; scores times ``attention_multiplier`` (the
published 1/64 at a head of 64: NOT ``1/sqrt``); softmax; ``wo``.

*Mamba-2 mixer* (``H = mamba_n_heads`` heads of ``P = mamba_d_head``,
state ``N = mamba_d_state``, one group, a convolution ``K = mamba_d_conv``
wide with a bias, no projection bias):

    [z | xBC | dt] = x w_in                    widths H P | H P + 2 N | H
    xBC_t = silu(conv_b + sum_k conv_w[:, k] * xBC_{t-K+1+k})   zeros before 0
    [xs | B | C] = xBC                         H P (as [H, P]) | N | N
    dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A)[:, None, None] S_{t-1}
          + (dt_t[:, None] xs_t)[:, :, None] B_t[None, None, :]   S_{-1} = 0
    y_t = S_t C_t + D[:, None] xs_t
    out = (rms(y_t * silu(z_t); norm_y)) w_out      gate BEFORE the norm

computed literally: a ``lax.scan`` over the tokens that carries ``S [H, P,
N]``. The published code computes the same function in chunks of
``mamba_chunk_size`` tokens (the SSD form); a step that carries ``S``
exactly needs no chunk, so the key is not read here.

Nothing here imports the program. Weights are the published dtype
(bfloat16) and are upcast a layer at a time (each layer is one jitted
call, so that the reference fits beside 6.4 GB of weights); every product
runs in float32 at ``highest``; queries are taken in blocks. ``quant``
names the lower precision of the correctness check's *control* (a key of
``PRECISIONS``): both operands of every matrix product are then rounded
to it (``lax.reduce_precision``); sums, the recurrence and the residual
stream stay float32.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

#: (exponent bits, mantissa bits) of the precisions a control may name
PRECISIONS = {"bfloat16": (8, 7), "float8_e5m2": (5, 2)}
#: queries attended at once (a [H, QUERY_BLOCK, T] float32 score array)
QUERY_BLOCK = 256
#: rows of logits made at once by ``served_token_gaps``
HEAD_ROWS = 128


def _sizes(model):
    """``(inner channels, convolved channels)`` of a state-space layer."""
    inner = model["mamba_n_heads"] * model["mamba_d_head"]
    return inner, inner + 2 * model["mamba_d_state"]


def init_params(seed, model, dtype=None):
    """The parameter tree from ``seed``, a layer a jitted call. Every
    matrix normal and scaled by the square root of its fan-in, with two
    exceptions, each forced by a published number. The embedding is
    scaled by ``d_model ** -0.5 / embedding_multiplier`` (the stream
    starts at norm 1): the head is the embedding itself, and at scale 1 a
    token's own row would make it every position's first choice by a
    margin that no fault in the layers could move. ``wq`` and ``wk`` are
    scaled four times their fan-in: under the published
    ``attention_multiplier`` of 1/64 (an eighth of ``1/sqrt(64)``) scores
    then spread by about 2 over the keys, and attention picks keys as a
    trained model's does, so that a fault in it shows. Of the
    state-space vectors ``dt_bias`` is the inverse softplus of steps from
    0.001 to 0.1 (geometric over the heads) and ``D`` 1, as the published
    code starts them; ``A_log`` is the logarithms of 1/16 to 1 over the
    heads: the published start, 1 to 16, forgets within a few hundred
    tokens under random weights, and here a head's state fades over 10
    to 16,000 tokens, as a trained model's remembers over its context,
    so that a state that was not reset still shows behind a long prompt.
    The convolution's bias is normal at 0.1 so that its path is
    exercised; norm gains 1. The program's ``TinyDecoder.init_params``
    states the same rule. Every leaf is ``dtype``: the model's
    ``torch_dtype``, bfloat16 as published unless the configuration says
    otherwise."""
    dtype = jnp.dtype(dtype or model.get("torch_dtype", "bfloat16"))
    d, ff, vocab = model["d_model"], model["d_ff"], model["vocab_size"]
    heads, groups = model["num_heads"], model["num_key_value_heads"]
    hd = d // heads
    hm, n, k = model["mamba_n_heads"], model["mamba_d_state"], \
        model["mamba_d_conv"]
    inner, conved = _sizes(model)
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))

    def normal(k_, shape, scale):
        return (jax.random.normal(k_, shape, jnp.float32)
                * scale).astype(dtype)

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(k_, kind):
        ks = jax.random.split(k_, 8)
        ones = jnp.ones((d,), dtype)
        lp = {"norm1": ones, "norm2": ones}
        if kind == "attention":
            lp.update(wq=normal(ks[0], (d, heads * hd), 4 * d ** -0.5),
                      wk=normal(ks[1], (d, groups * hd), 4 * d ** -0.5),
                      wv=normal(ks[2], (d, groups * hd), d ** -0.5),
                      wo=normal(ks[3], (heads * hd, d),
                                (heads * hd) ** -0.5))
        else:
            steps = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), hm))
            lp.update(
                w_in=normal(ks[0], (d, 2 * inner + 2 * n + hm), d ** -0.5),
                conv_w=normal(ks[1], (conved, k), k ** -0.5),
                conv_b=normal(ks[2], (conved,), 0.1),
                dt_bias=jnp.log(jnp.expm1(steps)).astype(dtype),
                A_log=jnp.log(jnp.linspace(1.0 / 16, 1.0, hm)).astype(
                    dtype),
                D=jnp.ones((hm,), dtype),
                norm_y=jnp.ones((inner,), dtype),
                w_out=normal(ks[3], (inner, d), inner ** -0.5))
        lp.update(w_gate=normal(ks[4], (d, ff), d ** -0.5),
                  w_up=normal(ks[5], (d, ff), d ** -0.5),
                  w_down=normal(ks[6], (ff, d), ff ** -0.5))
        return lp

    @jax.jit
    def ends(k_):
        return {"embed": normal(k_, (vocab, d), d ** -0.5
                                / model["embedding_multiplier"]),
                "norm_f": jnp.ones((d,), dtype)}

    params = ends(jax.random.fold_in(key, 0))
    params["layers"] = [layer(jax.random.fold_in(key, 1 + li), kind)
                        for li, kind in enumerate(model["layer_types"])]
    return params


def _rounder(quant):
    if quant is None:
        return lambda a: a
    exponent, mantissa = PRECISIONS[quant]
    return lambda a: lax.reduce_precision(a, exponent, mantissa)


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x * lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _attention(lp, x, model, mm, q_):
    T = x.shape[0]
    heads, groups = model["num_heads"], model["num_key_value_heads"]
    hd = model["d_model"] // heads
    q = mm(x, _f32(lp["wq"])).reshape(T, groups, heads // groups, hd)
    k = mm(x, _f32(lp["wk"])).reshape(T, groups, hd)
    v = mm(x, _f32(lp["wv"])).reshape(T, groups, hd)
    pos = jnp.arange(T, dtype=jnp.int32)
    block = min(QUERY_BLOCK, T)

    def attend(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=0)
        s = jnp.einsum("qgmd,kgd->gmqk", q_(qb), q_(k),
                       precision=lax.Precision.HIGHEST) \
            * model["attention_multiplier"]
        seen = pos[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        return jnp.einsum("gmqk,kgd->qgmd", q_(p), q_(v),
                          precision=lax.Precision.HIGHEST)
    # the last block starts early enough to end at T: rows it shares
    # with the block before it are computed twice, alike
    starts = jnp.minimum(jnp.arange(-(-T // block)) * block, T - block)
    att = jnp.zeros((T, groups, heads // groups, hd), jnp.float32)
    for i, o in enumerate(lax.map(attend, starts)):
        att = lax.dynamic_update_slice_in_dim(att, o, starts[i], axis=0)
    return mm(att.reshape(T, heads * hd), _f32(lp["wo"]))


def _mamba(lp, x, model, mm):
    T = x.shape[0]
    hm, p, n = model["mamba_n_heads"], model["mamba_d_head"], \
        model["mamba_d_state"]
    k = model["mamba_d_conv"]
    inner, conved = _sizes(model)
    zxbcdt = mm(x, _f32(lp["w_in"]))
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conved],
                  zxbcdt[:, inner + conved:])
    w = _f32(lp["conv_w"])
    before = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(_f32(lp["conv_b"]) + sum(
        w[:, j] * before[j:j + T] for j in range(k)))
    xs = xbc[:, :inner].reshape(T, hm, p)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + _f32(lp["dt_bias"]))
    a = -jnp.exp(_f32(lp["A_log"]))

    def token(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, (state * c_t[None, None, :]).sum(axis=-1)

    _, y = lax.scan(token, jnp.zeros((hm, p, n), jnp.float32),
                    (xs, dt, b, c))
    y = y + _f32(lp["D"])[:, None] * xs
    gated = y.reshape(T, inner) * jax.nn.silu(z)
    return mm(_rms(gated, lp["norm_y"], model["rms_norm_eps"]),
              _f32(lp["w_out"]))


def _layer(lp, h, model, quant):
    """One layer over a whole sequence ``h [T, d]`` (float32); the
    mixer is told by the layer's own leaves."""
    q_ = _rounder(quant)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=lax.Precision.HIGHEST)
    eps, r = model["rms_norm_eps"], model["residual_multiplier"]
    x = _rms(h, lp["norm1"], eps)
    mix = _attention(lp, x, model, mm, q_) if "wq" in lp \
        else _mamba(lp, x, model, mm)
    h = h + r * mix
    x2 = _rms(h, lp["norm2"], eps)
    return h + r * mm(jax.nn.silu(mm(x2, _f32(lp["w_gate"])))
                      * mm(x2, _f32(lp["w_up"])), _f32(lp["w_down"]))


_layer_jit = jax.jit(
    lambda lp, h, shape, quant: _layer(lp, h, dict(shape), quant),
    static_argnums=(2, 3))


def _static(model):
    """The sizes ``_layer`` reads, hashable (``layer_types`` is a list,
    and the layer's kind is told by its leaves)."""
    return tuple(sorted((k, v) for k, v in model.items()
                        if not isinstance(v, (list, tuple, dict))))


def hidden(params, tokens, model, quant=None):
    """The residual stream after the last layer, ``[T, d]`` float32."""
    shape = _static(model)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][tokens]) * model["embedding_multiplier"]
        for lp in params["layers"]:
            h = _layer_jit(lp, h, shape, quant)
    return h


def _head(params, h, eps, scaling, quant):
    q_ = _rounder(quant)
    x = _rms(h, params["norm_f"], eps)
    return jnp.matmul(q_(x), q_(_f32(params["embed"]).T),
                      precision=lax.Precision.HIGHEST) / scaling


_head_jit = jax.jit(_head, static_argnums=(2, 3, 4))


def forward(params, tokens, model, quant=None):
    """Logits ``[T, vocab]`` (float32) of one sequence ``tokens [T]``,
    every position seeing itself and what came before it."""
    return _head_jit(params, hidden(params, tokens, model, quant),
                     model["rms_norm_eps"], model["logits_scaling"], quant)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _window_gaps(params, h, hq, toks, start, eps, scaling, quant):
    """The gaps at positions ``start`` to ``start + HEAD_ROWS``."""
    def rows(a):
        return lax.dynamic_slice_in_dim(a, start, HEAD_ROWS, axis=0)
    logits = _head(params, rows(h), eps, scaling, None)
    # the logits at position i choose token i + 1
    chosen = rows(jnp.roll(toks, -1)) if quant is None else jnp.argmax(
        _head(params, rows(hq), eps, scaling, quant), axis=-1)
    return logits.max(axis=-1) - jnp.take_along_axis(
        logits, chosen[:, None], axis=1)[:, 0]


def served_token_gaps(params, prompt, served, model, pad_to, quant=None):
    """For each served token, how far its logit lies below the best logit
    at its position: ``best - logit[served]``, an array of
    ``len(served)``. With ``quant`` the served tokens are ignored and the
    gap is read for the token that the lower precision puts first (the
    control). Sequences are padded to ``pad_to`` so that one program
    serves every length (what lies behind a sequence changes nothing
    before it: attention is causal and the recurrence runs forward);
    logits are made ``HEAD_ROWS`` positions at a time and only where a
    served token is chosen."""
    n_p, n_s = len(prompt), len(served)
    toks = jnp.zeros((pad_to,), jnp.int32).at[:n_p + n_s].set(
        jnp.asarray(list(prompt) + list(served), jnp.int32))
    eps, scaling = model["rms_norm_eps"], model["logits_scaling"]
    h = hidden(params, toks, model)
    hq = h if quant is None else hidden(params, toks, model, quant)
    out = jnp.zeros((pad_to,), jnp.float32)
    for start in range(n_p - 1, n_p - 1 + n_s, HEAD_ROWS):
        start = min(start, pad_to - HEAD_ROWS)
        out = lax.dynamic_update_slice_in_dim(
            out, _window_gaps(params, h, hq, toks, start, eps, scaling,
                              quant), start, axis=0)
    return out[n_p - 1:n_p - 1 + n_s]
