"""Plain float32 reference for a GigaChat-3.5-shaped decoder (``model_type``
``gigachat3_5``): GatedDeltaNet linear-attention layers beside multi-head
latent attention (MLA) layers, each sublayer normed before and after, a
dense SwiGLU in the leading layers and a routed-plus-shared expert layer in
the rest, of whose routed experts this chip holds a contiguous share; a
final norm and an untied head. No position table.

Every norm is zero-centred, ``rms(x; g) = x / sqrt(mean(x^2) + eps) * (1 +
g)``; the four sublayer norms and the final one are also gated per channel,
``* gating_weight * sigmoid(gate)`` (``ZeroCenteredGatedNorm``). For ``h [T,
d]`` at positions ``0..T-1`` one layer is

    h = h + rms(mixer(rms(h; norm1)); norm2)
    h = h + rms(mlp(rms(h; norm3)); norm4)

*Latent mixer* (layers in ``full_attention_layers``), in its EXPANDED form:

    c_q = rms(x wq_a; norm_q);  q = c_q wq_b -> [T, H, nope + rope]
    a = x wkv_a -> [T, rank + rope];  c = rms(a[:, :rank]; norm_kv)
    k_rope = rope(a[:, rank:]), one for all heads;  q_rope = rope(q[.., nope:])
    c wkv_b -> [T, H, nope + v]: k_nope, v
    scores (q_nope . k_nope + q_rope . k_rope) * scale, causal softmax,
    o = sum p v;  mixer = (o * sigmoid(x w_gate_attn)) wo

Rotary pairs are interleaved (element ``2i`` turns with ``2i + 1``) at
YaRN's inverse frequencies (``rope_scaling``: the plain ones of
``rope_theta`` for pairs that turn more than ``beta_fast`` times over
``original_max_position_embeddings``, those over ``factor`` for pairs that
turn fewer than ``beta_slow`` times, a ramp between), and ``scale`` is
``(nope + rope) ** -0.5`` times ``(0.1 ln(factor) + 1) ** 2``.

*GatedDeltaNet mixer* (every other layer; ``Hk`` key heads, ``Hv`` value
heads, a convolution ``K`` wide):

    [q | k | v] = silu(causal depthwise conv of x [wq | wk | wv])  zeros before 0
    q, k L2-normed per head, q * Dk ** -0.5; key head j // (Hv / Hk) serves
    value head j;  beta = sigmoid(x wb);  g = -exp(A_log) softplus(x wa + dt_bias)
    per value head, S [Dk, Dv] from zeros:
        S = exp(g_t) S;  S = S + k_t (beta_t (v_t - S^T k_t))^T;  o_t = S^T q_t
    mixer = (rms(o; norm_o) per head * gate_scale * sigmoid(x wz)) wo

computed literally: a ``lax.scan`` over the tokens that carries ``S``.

*MLP*: the first ``first_k_dense_replace`` layers one SwiGLU; the rest
``s = sigmoid(x router)`` over all ``router_experts``, the
``num_experts_per_tok`` with the largest ``s + router_bias``, gates
``s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor``; the routed part
summed over the chosen experts that lie in ``[first_routed_expert,
first_routed_expert + n_routed_experts)`` alone (the held range: a pair
routed elsewhere adds nothing), plus the shared expert. Every SwiGLU is
``(silu(min(x Wg, L)) * clip(x Wu, -L, L)) Wd`` with ``L = swiglu_limit``.

Nothing here imports the program. Weights are the published dtype
(bfloat16) and are upcast a layer at a time (each layer is one jitted
call); every product runs in float32 at ``highest``; queries are taken in
blocks and the held experts one at a time (a masked sum over them), so
that the reference fits beside the weights on one chip. ``quant`` names
the lower precision of the correctness check's *control* (a key of
``PRECISIONS``): both operands of every matrix product are then rounded
to it (``lax.reduce_precision``); sums, the recurrence and the residual
stream stay float32, and the control routes by its own rounded scores.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: (exponent bits, mantissa bits) of the precisions a control may name
PRECISIONS = {"bfloat16": (8, 7), "float8_e5m2": (5, 2)}
#: queries attended at once (a [H, QUERY_BLOCK, T] float32 score array)
QUERY_BLOCK = 256
#: rows of logits made at once by ``served_token_gaps``
HEAD_ROWS = 128

#: the rule of scale, stated alike in ``TinyDecoder._init_params_gdn``:
#: every matrix normal by its fan-in and the embedding 1, except
WEIGHT_RULE = {
    "norm gains and gates": "gains (their distance from 1) normal at 0.1, "
                            "the sublayer and final norms' gates normal at "
                            "0.5 (factors 2 sigmoid(gate) of about 0.6 to "
                            "1.4): both paths carry numbers",
    "router, experts": "fan-in, as every matrix, the router's bias normal "
                       "at 0.01: every sublayer's output is normed to the "
                       "stream's scale (no residual damping), and at three "
                       "times fan-in for the router and twice for "
                       "experts_down two experts swapped at a near tie under "
                       "bfloat16 moved a sound run's widest gap (1.28 on the "
                       "chip) most of the way to the control's (1.79)",
    "A_log, dt_bias": "A_log the logarithms of 1/16 to 1 over the value "
                      "heads and dt_bias the inverse softplus of steps "
                      "from 0.001 to 0.1 (geometric), as the Mamba-2 "
                      "family starts dt: a head's state fades over 10 to "
                      "16,000 tokens, as a trained model's remembers over "
                      "its context, so that a state not reset or a decay "
                      "dropped shows behind a long prompt (the published "
                      "start of A, 1 to 16, forgets within a few tokens "
                      "under random weights)",
    "conv_w": "normal by the square root of the width",
}
NORM_GAIN, NORM_GATE = 0.1, 0.5


def _sizes(model):
    """``(Hk x Dk, Hv x Dv, convolved channels)`` of a linear layer."""
    qk = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    v = model["linear_num_value_heads"] * model["linear_value_head_dim"]
    return qk, v, 2 * qk + v


def _normal(k, shape, scale, to):
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(to)


def _gains(k, names, n, dtype):
    ks = jax.random.split(k, 2 * len(names))
    out = {}
    for i, name in enumerate(names):
        out[name] = _normal(ks[2 * i], (n,), NORM_GAIN, dtype)
        out[name + "_gate"] = _normal(ks[2 * i + 1], (n,), NORM_GATE, dtype)
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _layer_params(k, shape, dtype, full, dense):
    """One layer's leaves from key ``k``: latent attention if ``full``,
    else GatedDeltaNet; a dense MLP if ``dense``, else the experts. One
    program for every seed (``shape`` is the model, hashable)."""
    model = _thawed(shape)
    d, ff = model["d_model"], model["d_ff"]
    heads, qr, rank = (model["num_heads"], model["q_lora_rank"],
                       model["kv_lora_rank"])
    nope, rope, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
    hv, dvl = model["linear_num_value_heads"], model["linear_value_head_dim"]
    conv_k = model["linear_conv_kernel_dim"]
    qk, v_width, conved = _sizes(model)
    held, m = model["n_routed_experts"], model["moe_intermediate_size"]
    shared = model["n_shared_experts"] * m
    routed = model["router_experts"]
    ks = jax.random.split(k, 24)

    def normal(i, shape, scale, to=dtype):
        return _normal(ks[i], shape, scale, to)
    lp = _gains(ks[0], ("norm1", "norm2", "norm3", "norm4"), d, dtype)
    if full:
        lp.update(wq_a=normal(1, (d, qr), d ** -0.5),
                  norm_q=normal(2, (qr,), NORM_GAIN),
                  wq_b=normal(3, (qr, heads * (nope + rope)), qr ** -0.5),
                  wkv_a=normal(4, (d, rank + rope), d ** -0.5),
                  norm_kv=normal(5, (rank,), NORM_GAIN),
                  wkv_b=normal(6, (rank, heads * (nope + vd)),
                               rank ** -0.5),
                  w_gate_attn=normal(7, (d, heads * vd), d ** -0.5),
                  wo=normal(8, (heads * vd, d), (heads * vd) ** -0.5))
    else:
        steps = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), hv))
        lp.update(
            wq=normal(1, (d, qk), d ** -0.5),
            wk=normal(2, (d, qk), d ** -0.5),
            wv=normal(3, (d, v_width), d ** -0.5),
            wz=normal(4, (d, v_width), d ** -0.5),
            wa=normal(5, (d, hv), d ** -0.5),
            wb=normal(6, (d, hv), d ** -0.5),
            conv_w=normal(7, (conved, conv_k), conv_k ** -0.5),
            A_log=jnp.log(jnp.linspace(1.0 / 16, 1.0, hv)).astype(dtype),
            dt_bias=jnp.log(jnp.expm1(steps)).astype(dtype),
            norm_o=normal(8, (dvl,), NORM_GAIN),
            wo=normal(9, (v_width, d), v_width ** -0.5))
    if dense:
        lp.update(w_gate=normal(10, (d, ff), d ** -0.5),
                  w_up=normal(11, (d, ff), d ** -0.5),
                  w_down=normal(12, (ff, d), ff ** -0.5))
    else:
        lp.update(
            router=normal(10, (d, routed), d ** -0.5, jnp.float32),
            router_bias=normal(11, (routed,), 0.01, jnp.float32),
            experts_gate=normal(12, (held, d, m), d ** -0.5),
            experts_up=normal(13, (held, d, m), d ** -0.5),
            experts_down=normal(14, (held, m, d), m ** -0.5),
            shared_gate=normal(15, (d, shared), d ** -0.5),
            shared_up=normal(16, (d, shared), d ** -0.5),
            shared_down=normal(17, (shared, d), shared ** -0.5))
    return lp


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _ends(k, vocab, d, dtype):
    ks = jax.random.split(k, 3)
    return {"embed": _normal(ks[0], (vocab, d), 1.0, dtype),
            **_gains(ks[1], ("norm_f",), d, dtype),
            "head": _normal(ks[2], (d, vocab), d ** -0.5, dtype)}


def init_params(seed, model, dtype=None):
    """The parameter tree from ``seed``, a layer a jitted call, by
    ``WEIGHT_RULE``. The router's matrix and bias are float32 (the
    published code computes the scores in float32), every other leaf is
    ``dtype``: the model's ``torch_dtype``, bfloat16 as published unless
    the configuration says otherwise."""
    dtype = jnp.dtype(dtype or model.get("torch_dtype", "bfloat16"))
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    params = _ends(jax.random.fold_in(key, 0), model["vocab_size"],
                   model["d_model"], dtype)
    full, shape = set(model["full_attention_layers"]), _frozen(model)
    params["layers"] = [
        _layer_params(jax.random.fold_in(key, 1 + li), shape, dtype,
                      li in full, li < model["first_k_dense_replace"])
        for li in range(model["num_layers"])]
    return params


def _rounder(quant):
    if quant is None:
        return lambda a: a
    exponent, mantissa = PRECISIONS[quant]
    return lambda a: lax.reduce_precision(a, exponent, mantissa)


def _f32(a):
    return a.astype(jnp.float32)


def _norm(x, g, eps, gate=None, weight=2.0):
    out = x * lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) \
        * (1.0 + _f32(g))
    return out if gate is None else out * weight * jax.nn.sigmoid(_f32(gate))


def inv_freq(model):
    """The rotary part's inverse frequencies, YaRN's where
    ``rope_scaling`` names it (DeepSeek-V3's reading), numpy float64."""
    dim, base = model["qk_rope_head_dim"], model["rope_theta"]
    plain = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    sc = model.get("rope_scaling")
    if not sc:
        return plain

    def turns_at(turns):
        return dim * math.log(sc["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(turns_at(sc["beta_fast"])), 0)
    high = min(math.ceil(turns_at(sc["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0, 1)
    return plain / sc["factor"] * ramp + plain * (1 - ramp)


def softmax_scale(model):
    """``(nope + rope) ** -0.5``, times YaRN's ``0.1 ln(factor) + 1``
    at ``mscale_all_dim``, squared, where ``rope_scaling`` is set."""
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    sc = model.get("rope_scaling")
    if sc and sc["factor"] > 1:
        m = 0.1 * sc.get("mscale_all_dim", 1.0) * math.log(sc["factor"]) + 1
        scale *= m * m
    return scale


def _rope(x, pos, freqs):
    """``x [T, ..., r]`` turned by ``pos [T]``, interleaved pairs: element
    ``2i`` with ``2i + 1`` at ``freqs[i]``."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(x, wg, wu, wd, mm, limit):
    g, u = mm(x, _f32(wg)), mm(x, _f32(wu))
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return mm(jax.nn.silu(g) * u, _f32(wd))


def _latent(lp, x, model, mm, q_):
    T = x.shape[0]
    heads, rank = model["num_heads"], model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    eps = model["rms_norm_eps"]
    pos = jnp.arange(T, dtype=jnp.int32)
    freqs = inv_freq(model)
    q = mm(_norm(mm(x, _f32(lp["wq_a"])), lp["norm_q"], eps),
           _f32(lp["wq_b"])).reshape(T, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, freqs)],
                        axis=-1)
    a = mm(x, _f32(lp["wkv_a"]))
    c = _norm(a[:, :rank], lp["norm_kv"], eps)
    k_rope = _rope(a[:, rank:], pos, freqs)
    kv = mm(c, _f32(lp["wkv_b"])).reshape(T, heads, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, None, :], (T, heads, rope))], axis=-1)
    v = kv[..., nope:]
    scale = softmax_scale(model)
    block = min(QUERY_BLOCK, T)

    def attend(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", q_(qb), q_(k),
                       precision=lax.Precision.HIGHEST) * scale
        seen = pos[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", q_(p), q_(v),
                          precision=lax.Precision.HIGHEST)
    # the last block starts early enough to end at T: rows it shares
    # with the block before it are computed twice, alike
    starts = jnp.minimum(jnp.arange(-(-T // block)) * block, T - block)
    att = jnp.zeros((T, heads, v.shape[-1]), jnp.float32)
    for i, o in enumerate(lax.map(attend, starts)):
        att = lax.dynamic_update_slice_in_dim(att, o, starts[i], axis=0)
    att = att.reshape(T, -1) * jax.nn.sigmoid(mm(x, _f32(lp["w_gate_attn"])))
    return mm(att, _f32(lp["wo"]))


def _gated_delta(lp, x, model, mm):
    T = x.shape[0]
    hk, dk = model["linear_num_key_heads"], model["linear_key_head_dim"]
    hv, dv = model["linear_num_value_heads"], model["linear_value_head_dim"]
    conv_k = model["linear_conv_kernel_dim"]
    qk, _, _ = _sizes(model)
    eps = model["rms_norm_eps"]
    xc = jnp.concatenate([mm(x, _f32(lp[n])) for n in ("wq", "wk", "wv")],
                         axis=-1)
    w = _f32(lp["conv_w"])
    before = jnp.pad(xc, ((conv_k - 1, 0), (0, 0)))
    xc = jax.nn.silu(sum(w[:, j] * before[j:j + T] for j in range(conv_k)))

    def l2(a):
        return a * lax.rsqrt((a * a).sum(axis=-1, keepdims=True) + 1e-6)
    q = l2(xc[:, :qk].reshape(T, hk, dk)) * dk ** -0.5
    k = l2(xc[:, qk:2 * qk].reshape(T, hk, dk))
    v = xc[:, 2 * qk:].reshape(T, hv, dv)
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    beta = jax.nn.sigmoid(mm(x, _f32(lp["wb"])))
    g = -jnp.exp(_f32(lp["A_log"])) * jax.nn.softplus(
        mm(x, _f32(lp["wa"])) + _f32(lp["dt_bias"]))

    def token(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[:, None, None] * state
        kv = jnp.einsum("hk,hkv->hv", k_t, state,
                        precision=lax.Precision.HIGHEST)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - kv))[
            :, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state,
                                 precision=lax.Precision.HIGHEST)

    _, o = lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                    (q, k, v, g, beta))
    y = _norm(o, lp["norm_o"], eps) \
        * model["linear_sigmoid_gate_scale"] \
        * jax.nn.sigmoid(mm(x, _f32(lp["wz"])).reshape(T, hv, dv))
    return mm(y.reshape(T, hv * dv), _f32(lp["wo"]))


def route(x, router, bias, model, mm):
    """``(selected experts [T, k], gates [T, k])`` of the published
    ``noaux_tc`` router with one group, over all ``router_experts``."""
    s = jax.nn.sigmoid(mm(x, _f32(router)))
    _, sel = lax.top_k(s + _f32(bias), model["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, sel, axis=1)
    if model.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    return sel, picked * model["routed_scaling_factor"]


def _mlp(lp, x, model, mm):
    limit = model.get("swiglu_limit")
    if "w_gate" in lp:
        return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"], mm, limit)
    T = x.shape[0]
    sel, gates = route(x, lp["router"], lp["router_bias"], model, mm)
    # each token's weight on each held expert: the pairs outside the held
    # range fall in a column of their own, which is dropped
    held = model["n_routed_experts"]
    local = sel - model["first_routed_expert"]
    local = jnp.where((local >= 0) & (local < held), local, held)
    weight = jnp.zeros((T, held + 1), jnp.float32).at[
        jnp.arange(T)[:, None], local].add(gates)[:, :held]

    def one(acc, e):
        wg, wu, wd, w = e
        return acc + w[:, None] * _swiglu(x, wg, wu, wd, mm, limit), None
    routed, _ = lax.scan(one, jnp.zeros((T, x.shape[1]), jnp.float32),
                         (lp["experts_gate"], lp["experts_up"],
                          lp["experts_down"], weight.T))
    return routed + _swiglu(x, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"], mm, limit)


def _layer(lp, h, model, quant):
    """One layer over a whole sequence ``h [T, d]`` (float32); the mixer
    and the MLP are told by the layer's own leaves."""
    q_ = _rounder(quant)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=lax.Precision.HIGHEST)
    eps, gw = model["rms_norm_eps"], model["layernorm_gating_weight"]

    def norm(x, name):
        return _norm(x, lp[name], eps, lp[name + "_gate"], gw)
    x = norm(h, "norm1")
    mix = _latent(lp, x, model, mm, q_) if "wq_a" in lp \
        else _gated_delta(lp, x, model, mm)
    h = h + norm(mix, "norm2")
    return h + norm(_mlp(lp, norm(h, "norm3"), model, mm), "norm4")


def _frozen(value):
    """A configuration value, hashable."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


def _thawed(shape):
    return {k: dict(v) if k == "rope_scaling" and v is not None else v
            for k, v in shape}


_layer_jit = jax.jit(
    lambda lp, h, shape, quant: _layer(lp, h, _thawed(shape), quant),
    static_argnums=(2, 3))


def hidden(params, tokens, model, quant=None):
    """The residual stream after the last layer, ``[T, d]`` float32."""
    shape = _frozen(model)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][tokens])
        for lp in params["layers"]:
            h = _layer_jit(lp, h, shape, quant)
    return h


def _head(params, h, eps, weight, quant):
    q_ = _rounder(quant)
    x = _norm(h, params["norm_f"], eps, params["norm_f_gate"], weight)
    return jnp.matmul(q_(x), q_(_f32(params["head"])),
                      precision=lax.Precision.HIGHEST)


_head_jit = jax.jit(_head, static_argnums=(2, 3, 4))


def forward(params, tokens, model, quant=None):
    """Logits ``[T, vocab]`` (float32) of one sequence ``tokens [T]``,
    every position seeing itself and what came before it."""
    return _head_jit(params, hidden(params, tokens, model, quant),
                     model["rms_norm_eps"], model["layernorm_gating_weight"],
                     quant)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _window_gaps(params, h, hq, toks, start, eps, weight, quant):
    """The gaps at positions ``start`` to ``start + HEAD_ROWS``."""
    def rows(a):
        return lax.dynamic_slice_in_dim(a, start, HEAD_ROWS, axis=0)
    logits = _head(params, rows(h), eps, weight, None)
    # the logits at position i choose token i + 1
    chosen = rows(jnp.roll(toks, -1)) if quant is None else jnp.argmax(
        _head(params, rows(hq), eps, weight, quant), axis=-1)
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
    eps, weight = model["rms_norm_eps"], model["layernorm_gating_weight"]
    h = hidden(params, toks, model)
    hq = h if quant is None else hidden(params, toks, model, quant)
    out = jnp.zeros((pad_to,), jnp.float32)
    for start in range(n_p - 1, n_p - 1 + n_s, HEAD_ROWS):
        start = min(start, pad_to - HEAD_ROWS)
        out = lax.dynamic_update_slice_in_dim(
            out, _window_gaps(params, h, hq, toks, start, eps, weight,
                              quant), start, axis=0)
    return out[n_p - 1:n_p - 1 + n_s]
