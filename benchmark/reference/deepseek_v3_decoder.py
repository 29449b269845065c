"""Plain float32 reference for a DeepSeek-V3-shaped decoder (Moonlight's
``model_type``): RMSNorm, multi-head latent attention (MLA) in its
*expanded* form, rotary positions on a split-off part of q and k, a SwiGLU
MLP in the leading dense layers and a routed-plus-shared expert layer in
the rest, a final RMSNorm and an untied output head. No position table.

For ``h [T, d]`` at positions ``0..T-1``, one layer is

    x = rms(h; norm1);  q = x wq -> [T, H, nope + rope]
    a = x wkv_a -> [T, rank + rope];  c = rms(a[:, :rank]; norm_kv)
    k_rope = rope(a[:, rank:]), one for all heads;  q_rope = rope(q[.., nope:])
    c wkv_b -> [T, H, nope + v]: k_nope, v
    scores (q_nope . k_nope + q_rope . k_rope) * (nope + rope) ** -0.5,
    causal softmax, o = sum p v;  h += o wo
    x2 = rms(h; norm2)
    dense layer:   h += (silu(x2 w_gate) * (x2 w_up)) w_down
    expert layer:  s = sigmoid(x2 router); the k experts with the largest
                   s + router_bias; gates s[sel] / (sum s[sel] + 1e-20) *
                   routed_scaling_factor (the bias selects only);
                   h += sum_i gate_i E_sel_i(x2) + S(x2)

with every ``E`` a SwiGLU of width ``moe_intermediate_size`` and ``S`` one
of ``n_shared_experts`` times that. ``n_group`` 1 and ``topk_group`` 1 make
the published group limiting the identity, so it is not written.

The rotary pairing is half-split: element ``i`` of the rotary part turns
with element ``i + rope/2``. The published checkpoints store that part
interleaved and the published code permutes it into this order before it
turns it; with weights from a seed the two are one fixed relabelling.

Nothing here imports the program. Weights are the published dtype
(bfloat16) and are upcast a layer at a time (each layer is one jitted
call); every product runs in float32 at ``highest``; queries are taken in
blocks and the experts one at a time (a masked sum over all of them), so
that the reference fits beside the weights on one chip. ``quant`` names
the lower precision of the correctness check's *control* (a key of
``PRECISIONS``): both operands of every matrix product are then rounded
to it (``lax.reduce_precision``), sums and the residual stream stay
float32, and the control routes by its own rounded scores.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

#: (exponent bits, mantissa bits) of the precisions a control may name
PRECISIONS = {"bfloat16": (8, 7), "float8_e5m2": (5, 2)}
#: queries attended at once (a [H, QUERY_BLOCK, T] float32 score array)
QUERY_BLOCK = 512
#: rows of logits made at once by ``served_token_gaps``
HEAD_ROWS = 128


def init_params(seed, model, dtype=None):
    """The parameter tree from ``seed``, a layer a jitted call: normal
    weights scaled by the square root of their fan-in, the embedding 1,
    gains 1, the router's selection bias normal at scale 0.01 so that the
    bias path is exercised. One rule besides, GPT-2's and Megatron's, at
    the depth the configuration itself has: every matrix that writes to
    the residual stream (``wo``, ``w_down``, ``experts_down``,
    ``shared_down``) is scaled by ``(2 * num_layers) ** -0.5`` on top
    of its fan-in, the routed experts like the shared ones (their gates
    sum to ``routed_scaling_factor``, about the square root of the
    experts a token takes, so the routed sum is as large as one
    expert's output). The program's ``TinyDecoder.init_params`` states
    the same rule. The router's matrix and bias are float32 (the
    published code computes the scores in float32), every other leaf is
    ``dtype``: the model's ``torch_dtype``, bfloat16 as published unless
    the configuration says otherwise."""
    dtype = jnp.dtype(dtype or model.get("torch_dtype", "bfloat16"))
    d, ff, vocab = model["d_model"], model["d_ff"], model["vocab_size"]
    heads, rank = model["num_heads"], model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd, experts = model["v_head_dim"], model["n_routed_experts"]
    m = model["moe_intermediate_size"]
    shared = model["n_shared_experts"] * m
    out = (2 * model["num_layers"]) ** -0.5
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))

    def normal(k, shape, scale, to=dtype):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(to)

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(k, dense):
        ks = jax.random.split(k, 16)
        ones = jnp.ones((d,), dtype)
        lp = {"norm1": ones, "norm2": ones,
              "norm_kv": jnp.ones((rank,), dtype),
              "wq": normal(ks[0], (d, heads * (nope + rope)), d ** -0.5),
              "wkv_a": normal(ks[1], (d, rank + rope), d ** -0.5),
              "wkv_b": normal(ks[2], (rank, heads * (nope + vd)),
                              rank ** -0.5),
              "wo": normal(ks[3], (heads * vd, d),
                           out * (heads * vd) ** -0.5)}
        if dense:
            lp.update(w_gate=normal(ks[4], (d, ff), d ** -0.5),
                      w_up=normal(ks[5], (d, ff), d ** -0.5),
                      w_down=normal(ks[6], (ff, d), out * ff ** -0.5))
        else:
            lp.update(
                router=normal(ks[4], (d, experts), d ** -0.5, jnp.float32),
                router_bias=normal(ks[5], (experts,), 0.01, jnp.float32),
                experts_gate=normal(ks[6], (experts, d, m), d ** -0.5),
                experts_up=normal(ks[7], (experts, d, m), d ** -0.5),
                experts_down=normal(ks[8], (experts, m, d),
                                    out * m ** -0.5),
                shared_gate=normal(ks[9], (d, shared), d ** -0.5),
                shared_up=normal(ks[10], (d, shared), d ** -0.5),
                shared_down=normal(ks[11], (shared, d),
                                   out * shared ** -0.5))
        return lp

    @jax.jit
    def ends(k):
        ks = jax.random.split(k, 2)
        return {"embed": normal(ks[0], (vocab, d), 1.0),
                "norm_f": jnp.ones((d,), dtype),
                "head": normal(ks[1], (d, vocab), d ** -0.5)}

    params = ends(jax.random.fold_in(key, 0))
    params["layers"] = [
        layer(jax.random.fold_in(key, 1 + li),
              li < model["first_k_dense_replace"])
        for li in range(model["num_layers"])]
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


def _rope(x, pos, theta):
    """``x [T, ..., r]`` turned by ``pos [T]``: element ``i`` pairs with
    ``i + r/2``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _swiglu(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, _f32(wg))) * mm(x, _f32(wu)), _f32(wd))


def route(x2, router, bias, model, mm):
    """``(selected experts [T, k], gates [T, k])`` of the published
    ``noaux_tc`` router with one group."""
    k = model["num_experts_per_tok"]
    s = jax.nn.sigmoid(mm(x2, _f32(router)))
    _, sel = lax.top_k(s + _f32(bias), k)
    picked = jnp.take_along_axis(s, sel, axis=1)
    if model.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    return sel, picked * model.get("routed_scaling_factor", 1.0)


def _layer(lp, h, model, quant):
    """One layer over a whole sequence ``h [T, d]`` (float32)."""
    q_ = _rounder(quant)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=lax.Precision.HIGHEST)
    T = h.shape[0]
    heads, rank = model["num_heads"], model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    pos = jnp.arange(T, dtype=jnp.int32)

    x = _rms(h, lp["norm1"], eps)
    q = mm(x, _f32(lp["wq"])).reshape(T, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)],
                        axis=-1)
    a = mm(x, _f32(lp["wkv_a"]))
    c = _rms(a[:, :rank], lp["norm_kv"], eps)
    k_rope = _rope(a[:, rank:], pos, theta)
    kv = mm(c, _f32(lp["wkv_b"])).reshape(T, heads, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, None, :], (T, heads, rope))], axis=-1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
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
    h = h + mm(att.reshape(T, -1), _f32(lp["wo"]))

    x2 = _rms(h, lp["norm2"], eps)
    if "w_gate" in lp:
        return h + _swiglu(x2, lp["w_gate"], lp["w_up"], lp["w_down"], mm)
    sel, gates = route(x2, lp["router"], lp["router_bias"], model, mm)
    # each token's weight on each expert (0 where it was not selected)
    weight = jnp.zeros((T, lp["router"].shape[1]), jnp.float32).at[
        jnp.arange(T)[:, None], sel].add(gates)

    def one(acc, e):
        wg, wu, wd, w = e
        return acc + w[:, None] * _swiglu(x2, wg, wu, wd, mm), None
    routed, _ = lax.scan(one, jnp.zeros_like(h),
                         (lp["experts_gate"], lp["experts_up"],
                          lp["experts_down"], weight.T))
    return h + routed + _swiglu(x2, lp["shared_gate"], lp["shared_up"],
                                lp["shared_down"], mm)


_layer_jit = jax.jit(
    lambda lp, h, shape, quant: _layer(lp, h, dict(shape), quant),
    static_argnums=(2, 3))


def hidden(params, tokens, model, quant=None):
    """The residual stream after the last layer, ``[T, d]`` float32."""
    shape = tuple(sorted(model.items()))
    h = _f32(params["embed"][tokens])
    for lp in params["layers"]:
        h = _layer_jit(lp, h, shape, quant)
    return h


def _head(params, h, eps, quant):
    q_ = _rounder(quant)
    x = _rms(h, params["norm_f"], eps)
    return jnp.matmul(q_(x), q_(_f32(params["head"])),
                      precision=lax.Precision.HIGHEST)


def forward(params, tokens, model, quant=None):
    """Logits ``[T, vocab]`` (float32) of one sequence ``tokens [T]``,
    every position attending to itself and what came before it."""
    return _head_jit(params, hidden(params, tokens, model, quant),
                     model["rms_norm_eps"], quant)


_head_jit = jax.jit(_head, static_argnums=(2, 3))


@functools.partial(jax.jit, static_argnums=(5, 6))
def _window_gaps(params, h, hq, toks, start, eps, quant):
    """The gaps at positions ``start`` to ``start + HEAD_ROWS``."""
    def rows(a):
        return lax.dynamic_slice_in_dim(a, start, HEAD_ROWS, axis=0)
    logits = _head(params, rows(h), eps, None)
    # the logits at position i choose token i + 1
    chosen = rows(jnp.roll(toks, -1)) if quant is None else jnp.argmax(
        _head(params, rows(hq), eps, quant), axis=-1)
    return logits.max(axis=-1) - jnp.take_along_axis(
        logits, chosen[:, None], axis=1)[:, 0]


def served_token_gaps(params, prompt, served, model, pad_to, quant=None):
    """For each served token, how far its logit lies below the best logit
    at its position: ``best - logit[served]``, an array of
    ``len(served)``. With ``quant`` the served tokens are ignored and the
    gap is read for the token that the lower precision puts first (the
    control). Sequences are padded to ``pad_to`` so that one program
    serves every length; logits are made ``HEAD_ROWS`` positions at a
    time and only where a served token is chosen."""
    n_p, n_s = len(prompt), len(served)
    toks = jnp.zeros((pad_to,), jnp.int32).at[:n_p + n_s].set(
        jnp.asarray(list(prompt) + list(served), jnp.int32))
    eps = model["rms_norm_eps"]
    h = hidden(params, toks, model)
    hq = h if quant is None else hidden(params, toks, model, quant)
    out = jnp.zeros((pad_to,), jnp.float32)
    for start in range(n_p - 1, n_p - 1 + n_s, HEAD_ROWS):
        start = min(start, pad_to - HEAD_ROWS)
        out = lax.dynamic_update_slice_in_dim(
            out, _window_gaps(params, h, hq, toks, start, eps, quant),
            start, axis=0)
    return out[n_p - 1:n_p - 1 + n_s]
