"""Plain float32 reference for a Command-A+-shaped decoder (``model_type``
``cohere2_moe``): a PARALLEL block in every layer, attention and an expert
layer both reading one LayerNorm of the residual, attention through a
sliding window with rotary positions on most layers and over the whole
context without positions on the rest; a final LayerNorm and the embedding
as the head. Of the routed experts this chip holds a contiguous share.

For ``h [T, d]`` at positions ``0..T-1`` one layer is

    u = LN(h; norm)          (mean-centred, a gain, no bias, layer_norm_eps)
    h = h + attn(u) + routed(u) + shared(u)

*Attention*: ``q = u wq`` (``num_heads`` heads of ``head_dim``), ``k = u wk``
and ``v = u wv`` (``num_key_value_heads`` heads; query head ``j`` reads kv
head ``j // (num_heads / num_key_value_heads)``). On a layer that
``layer_types`` names ``sliding_attention`` q and k are turned by their
positions on INTERLEAVED pairs (GPT-J: element ``2i`` with ``2i + 1`` at
``rope_theta ** (-2i / head_dim)``) and position ``p`` sees the keys ``j``
with ``p - sliding_window < j <= p``; on a ``full_attention`` layer there
are no positions and ``j <= p``. ``attn = softmax(q . k / sqrt(head_dim))
v wo``.

*Experts*: ``s = sigmoid(u router)`` over all ``router_experts`` (no
selection bias), the ``num_experts_per_tok`` largest, gates ``s[sel] /
sum s[sel]`` (``norm_topk_prob``); ``routed`` the gated sum over the chosen
experts that lie in ``[first_routed_expert, first_routed_expert +
n_routed_experts)`` alone (the held range: a pair routed elsewhere adds
nothing); ``shared`` the MEAN of ``num_shared_experts`` shared experts.
Every expert is ``(silu(u Wg) * (u Wu)) Wd``. The logits are
``logit_scale * LN(h; norm_f) embed^T``.

Nothing here imports the program. Weights are the published dtype
(bfloat16) and are upcast inside each layer's jitted call; every product
runs in float32 at ``highest``. So that a context of 26k positions fits
beside the weights on one chip, attention is computed in blocks of
``QUERY_BLOCK`` queries (each against the keys its mask can reach: the
window's on a sliding layer), the expert layer in blocks of ``MLP_BLOCK``
tokens with the held experts one at a time (a masked sum over them).
``quant`` names the lower precision of the correctness check's *control*
(a key of ``PRECISIONS``): both operands of every matrix product are then
rounded to it (``lax.reduce_precision``); sums, the softmax and the
residual stream stay float32, and the control routes by its own rounded
scores.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

#: (exponent bits, mantissa bits) of the precisions a control may name
PRECISIONS = {"bfloat16": (8, 7), "float8_e5m2": (5, 2)}
#: queries attended at once (a [heads, QUERY_BLOCK, keys] float32 array)
QUERY_BLOCK = 32
#: tokens through the expert layer at once
MLP_BLOCK = 1024
#: rows of logits made at once by ``served_token_gaps``
HEAD_ROWS = 128
SLIDING = "sliding_attention"

#: the rule of scale, stated alike in ``TinyDecoder._init_params_window``:
#: every matrix normal by its fan-in, except
WEIGHT_RULE = {
    "embed": "normal at d ** -0.5: the head is the embedding, so logits "
             "spread by about 1 and the layers decide them (at 1 a "
             "token's own row is every position's first choice whatever "
             "the layers do)",
    "wq, wk": "twice fan-in: scores spread by about 4 over the keys, so "
              "that a query's few largest keys carry its attention and "
              "keys left out of a window, or turned when they should not "
              "be, move what it reads",
    "norm gains": "1 plus a normal at 0.1, so that the gains carry "
                  "numbers; no biases (the published LayerNorm has none)",
    "router, experts": "fan-in, as every matrix; the router float32, as "
                       "the published code computes its scores",
}
NORM_GAIN = 0.1


def _normal(k, shape, scale, to):
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(to)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _layer_params(k, shape, dtype):
    """One layer's leaves from key ``k``; one program for every seed
    (``shape`` is the model, hashable)."""
    model = dict(shape)
    d, heads, kv = (model["d_model"], model["num_heads"],
                    model["num_key_value_heads"])
    hd, held, m = (model["head_dim"], model["n_routed_experts"],
                   model["moe_intermediate_size"])
    shared = model["num_shared_experts"] * m
    ks = jax.random.split(k, 16)

    def normal(i, shape, scale, to=dtype):
        return _normal(ks[i], shape, scale, to)
    lp = {"norm": (1.0 + normal(0, (d,), NORM_GAIN, jnp.float32)).astype(
              dtype),
          "wq": normal(1, (d, heads * hd), 2 * d ** -0.5),
          "wk": normal(2, (d, kv * hd), 2 * d ** -0.5),
          "wv": normal(3, (d, kv * hd), d ** -0.5),
          "wo": normal(4, (heads * hd, d), (heads * hd) ** -0.5),
          "router": normal(5, (d, model["router_experts"]), d ** -0.5,
                           jnp.float32),
          "experts_gate": normal(6, (held, d, m), d ** -0.5),
          "experts_up": normal(7, (held, d, m), d ** -0.5),
          "experts_down": normal(8, (held, m, d), m ** -0.5)}
    if shared:
        lp.update(shared_gate=normal(9, (d, shared), d ** -0.5),
                  shared_up=normal(10, (d, shared), d ** -0.5),
                  shared_down=normal(11, (shared, d), m ** -0.5))
    return lp


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _ends(k, vocab, d, dtype):
    ks = jax.random.split(k, 2)
    return {"embed": _normal(ks[0], (vocab, d), d ** -0.5, dtype),
            "norm_f": (1.0 + _normal(ks[1], (d,), NORM_GAIN,
                                     jnp.float32)).astype(dtype)}


def _frozen(model):
    """A configuration, hashable."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def init_params(seed, model, dtype=None):
    """The parameter tree from ``seed``, a layer a jitted call, by
    ``WEIGHT_RULE``. The router is float32, every other leaf ``dtype``:
    the model's ``torch_dtype``, bfloat16 as published unless the
    configuration says otherwise."""
    dtype = jnp.dtype(dtype or model.get("torch_dtype", "bfloat16"))
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    params = _ends(jax.random.fold_in(key, 0), model["vocab_size"],
                   model["d_model"], dtype)
    shape = _frozen(model)
    params["layers"] = [
        _layer_params(jax.random.fold_in(key, 1 + li), shape, dtype)
        for li in range(model["num_layers"])]
    return params


def _rounder(quant):
    if quant is None:
        return lambda a: a
    exponent, mantissa = PRECISIONS[quant]
    return lambda a: lax.reduce_precision(a, exponent, mantissa)


def _f32(a):
    return a.astype(jnp.float32)


def _norm(x, g, eps):
    x = x - x.mean(axis=-1, keepdims=True)
    return x * lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _rope(x, pos, theta):
    """``x [T, heads, D]`` turned by ``pos [T]`` on interleaved pairs:
    element ``2i`` with ``2i + 1`` at ``theta ** (-2i / D)``."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _blocks(T, block):
    """Starts of blocks of ``block`` rows over ``T``: the last starts early
    enough to end at ``T`` (rows it shares with the one before it are
    computed twice, alike)."""
    return jnp.minimum(jnp.arange(-(-T // block)) * block, T - block)


def _attention(lp, u, model, sliding, mm, q_):
    """``attn(u) [T, d]`` by blocks of queries, each against the keys its
    mask can reach."""
    T = u.shape[0]
    heads, G, D = (model["num_heads"], model["num_key_value_heads"],
                   model["head_dim"])
    W, theta = model["sliding_window"], model["rope_theta"]
    pos = jnp.arange(T, dtype=jnp.int32)
    k = mm(u, _f32(lp["wk"])).reshape(T, G, D)
    v = mm(u, _f32(lp["wv"])).reshape(T, G, D)
    if sliding:
        k = _rope(k, pos, theta)
    block = min(QUERY_BLOCK, T)
    # the keys a block of queries may see: a window's and the block's on
    # a sliding layer, all of them on a full one
    reach = min(T, W - 1 + block) if sliding else T
    wq, wo = _f32(lp["wq"]), _f32(lp["wo"])

    def attend(start):
        qpos = start + jnp.arange(block, dtype=jnp.int32)
        q = mm(lax.dynamic_slice_in_dim(u, start, block, axis=0),
               wq).reshape(block, G, heads // G, D)
        first = jnp.clip(start - W + 1, 0, T - reach) if sliding else 0
        kpos = first + jnp.arange(reach, dtype=jnp.int32)
        kb = lax.dynamic_slice_in_dim(k, first, reach, axis=0)
        vb = lax.dynamic_slice_in_dim(v, first, reach, axis=0)
        if sliding:
            q = _rope(q.reshape(block, heads, D), qpos,
                      theta).reshape(q.shape)
        s = jnp.einsum("qgmd,kgd->gmqk", q_(q), q_(kb),
                       precision=lax.Precision.HIGHEST) * D ** -0.5
        seen = kpos[None, :] <= qpos[:, None]
        if sliding:
            seen = seen & (kpos[None, :] > qpos[:, None] - W)
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        o = jnp.einsum("gmqk,kgd->qgmd", q_(p), q_(vb),
                       precision=lax.Precision.HIGHEST)
        return mm(o.reshape(block, heads * D), wo)
    starts = _blocks(T, block)
    out = jnp.zeros((T, u.shape[1]), jnp.float32)
    for i, o in enumerate(lax.map(attend, starts)):
        out = lax.dynamic_update_slice_in_dim(out, o, starts[i], axis=0)
    return out


def _swiglu(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, _f32(wg))) * mm(x, _f32(wu)), _f32(wd))


def route(x, router, model, mm):
    """``(selected experts [T, k], gates [T, k])``: sigmoid scores over
    all ``router_experts``, the ``k`` largest, renormalised."""
    s = jax.nn.sigmoid(mm(x, _f32(router)))
    picked, sel = lax.top_k(s, model["num_experts_per_tok"])
    if model.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    return sel, picked


def _experts(lp, u, model, mm):
    """``routed(u) + shared(u) [T, d]`` in blocks of tokens."""
    T, d = u.shape
    held = model["n_routed_experts"]
    n_shared = model["num_shared_experts"]
    block = min(MLP_BLOCK, T)

    def one_block(start):
        x = lax.dynamic_slice_in_dim(u, start, block, axis=0)
        sel, gates = route(x, lp["router"], model, mm)
        # each token's weight on each held expert: the pairs outside the
        # held range fall in a column of their own, which is dropped
        local = sel - model["first_routed_expert"]
        local = jnp.where((local >= 0) & (local < held), local, held)
        weight = jnp.zeros((block, held + 1), jnp.float32).at[
            jnp.arange(block)[:, None], local].add(gates)[:, :held]

        def one(acc, e):
            wg, wu, wd, w = e
            return acc + w[:, None] * _swiglu(x, wg, wu, wd, mm), None
        out, _ = lax.scan(one, jnp.zeros((block, d), jnp.float32),
                          (lp["experts_gate"], lp["experts_up"],
                           lp["experts_down"], weight.T))
        if n_shared:
            out = out + _swiglu(x, lp["shared_gate"], lp["shared_up"],
                                lp["shared_down"], mm) / n_shared
        return out
    starts = _blocks(T, block)
    out = jnp.zeros((T, d), jnp.float32)
    for i, o in enumerate(lax.map(one_block, starts)):
        out = lax.dynamic_update_slice_in_dim(out, o, starts[i], axis=0)
    return out


def _layer(lp, h, model, sliding, quant):
    """One layer over a whole sequence ``h [T, d]`` (float32)."""
    q_ = _rounder(quant)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=lax.Precision.HIGHEST)
    u = _norm(h, lp["norm"], model["layer_norm_eps"])
    return h + _attention(lp, u, model, sliding, mm, q_) \
        + _experts(lp, u, model, mm)


_layer_jit = jax.jit(
    lambda lp, h, shape, sliding, quant: _layer(lp, h, dict(shape),
                                                sliding, quant),
    static_argnums=(2, 3, 4))


def hidden(params, tokens, model, quant=None):
    """The residual stream after the last layer, ``[T, d]`` float32."""
    shape = _frozen(model)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][tokens])
        for kind, lp in zip(model["layer_types"], params["layers"]):
            h = _layer_jit(lp, h, shape, kind == SLIDING, quant)
    return h


def _head(params, h, eps, scale, quant):
    q_ = _rounder(quant)
    x = _norm(h, params["norm_f"], eps)
    return jnp.einsum("td,vd->tv", q_(x), q_(_f32(params["embed"])),
                      precision=lax.Precision.HIGHEST) * scale


def forward(params, tokens, model, quant=None):
    """Logits ``[T, vocab]`` (float32) of one sequence ``tokens [T]``."""
    with jax.default_matmul_precision("highest"):
        return _head(params, hidden(params, tokens, model, quant),
                     model["layer_norm_eps"], model["logit_scale"], quant)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _window_gaps(params, h, hq, toks, start, eps, scale, quant):
    """The gaps at positions ``start`` to ``start + HEAD_ROWS``."""
    def rows(a):
        return lax.dynamic_slice_in_dim(a, start, HEAD_ROWS, axis=0)
    logits = _head(params, rows(h), eps, scale, None)
    # the logits at position i choose token i + 1
    chosen = rows(jnp.roll(toks, -1)) if quant is None else jnp.argmax(
        _head(params, rows(hq), eps, scale, quant), axis=-1)
    return logits.max(axis=-1) - jnp.take_along_axis(
        logits, chosen[:, None], axis=1)[:, 0]


def served_token_gaps(params, prompt, served, model, pad_to, quant=None):
    """For each served token, how far its logit lies below the best logit
    at its position: ``best - logit[served]``, an array of
    ``len(served)``. With ``quant`` the served tokens are ignored and the
    gap is read for the token that the lower precision puts first (the
    control). Sequences are padded to ``pad_to`` so that one program
    serves every length (what lies behind a sequence changes nothing
    before it: attention is causal); logits are made ``HEAD_ROWS``
    positions at a time and only where a served token is chosen."""
    n_p, n_s = len(prompt), len(served)
    toks = jnp.zeros((pad_to,), jnp.int32).at[:n_p + n_s].set(
        jnp.asarray(list(prompt) + list(served), jnp.int32))
    eps, scale = model["layer_norm_eps"], model["logit_scale"]
    h = hidden(params, toks, model)
    hq = h if quant is None else hidden(params, toks, model, quant)
    out = jnp.zeros((pad_to,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for start in range(n_p - 1, n_p - 1 + n_s, HEAD_ROWS):
            start = min(start, pad_to - HEAD_ROWS)
            out = lax.dynamic_update_slice_in_dim(
                out, _window_gaps(params, h, hq, toks, start, eps, scale,
                                  quant), start, axis=0)
    return out[n_p - 1:n_p - 1 + n_s]
