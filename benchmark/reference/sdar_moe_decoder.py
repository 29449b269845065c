"""Plain float32 reference for an SDAR expert decoder (``model_type``
``sdar_moe``): a Qwen3-MoE-shaped layer that generates by diffusion over
blocks. RMSNorm, grouped-query attention with each query and key head
RMS-normed over its own values before rotary positions, a softmax top-k
router over SwiGLU experts, a final RMSNorm and an untied head.

For ``h [T, d]``, one layer is

    x = rms(h; norm1);  q = x wq -> [T, H, D];  k = x wk, v = x wv -> [T, G, D]
    q = rope(rms(q; q_norm)), k = rope(rms(k; k_norm)): per head, over its D
    values, then turned by the token's own position, whole head, half-split
    scores q_j . k_(j // (H / G)) * D ** -0.5, softmax under the mask,
    o = sum p v;  h += o wo
    x2 = rms(h; norm2);  s = softmax(x2 router) over all the experts;
    the k largest; gates s[sel] / sum s[sel] (``norm_topk_prob``);
    h += sum_i gate_i (silu(x2 Wg_i) * (x2 Wu_i)) Wd_i

The mask is BLOCK-CAUSAL: with block length ``B`` position ``p`` sees
every position below ``(p // B + 1) * B``: the blocks before its own and
the whole of its own. The logits at a position are for that position
itself (no shift).

Generation (the family's ``block_diffusion_generate``, from memory): the
prompt's leading whole blocks leave K/V; then block by block, up to
``denoising_steps`` passes over the block's ``B`` positions (revealed ones
as their tokens, the rest as ``mask_token_id``), each revealing
``B / denoising_steps`` masked positions as their first choice (the
leftmost under ``sequential``), and one more pass over the revealed block
whose K/V later blocks read. A prompt's last ``P % B`` tokens sit revealed
in the first generated block.

``token_gaps`` follows a served request in ONE forward pass over the
clean sequence (prompt and served tokens) followed by one COPY of the
generated region for each denoising pass, the copy holding what each
block held when that pass ran, under the mask of block-diffusion
training: a clean position sees as above; a copy's position sees the
clean blocks before its block and its own block of its own copy; copies
carry their true positions into the rotary term. No token of one copy is
seen from another, nor from the clean sequence, so each copy's block is
exactly the pass the program ran.

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
import numpy as np
from jax import lax

#: (exponent bits, mantissa bits) of the precisions a control may name
PRECISIONS = {"bfloat16": (8, 7), "float8_e5m2": (5, 2)}
#: queries attended at once (a [H, QUERY_BLOCK, T] float32 score array)
QUERY_BLOCK = 512
#: rows of logits made at once by ``token_gaps``
HEAD_ROWS = 128

#: the rule of scale, stated alike in ``TinyDecoder._init_params_blockdiff``:
#: every matrix by its fan-in and norm gains 1, except
WEIGHT_RULE = {
    "embed": "0.5: a masked position starts as the mask token's row "
             "whatever its context, so what the layers add has to decide "
             "its first choice; at scale 1 the mask row alone would",
    "wo, experts_down": "1/sqrt(2 x num_layers) on top of fan-in, GPT-2's "
                        "and Megatron's rule at the cut's own depth",
    "experts_down": "twice that: normalised gates average a token's "
                    "experts, and the routed sum should weigh as much as "
                    "attention's",
    "router": "three times fan-in: a token's gates then fall off as a "
              "trained router's do (the eighth a few per cent of the "
              "first), so that two experts swapped at a near tie move a "
              "logit less than a fault does",
    "q_norm, k_norm": "gains 1.5: scores spread by about 2 over the keys "
                      "and attention picks keys",
}
EMBED_SCALE, ROUTER_SCALE, QK_GAIN, EXPERTS_DOWN = 0.5, 3.0, 1.5, 2.0


def init_params(seed, model, dtype=None):
    """The parameter tree from ``seed``, a layer a jitted call, by
    ``WEIGHT_RULE``. The router's matrix is float32 (the published code
    computes the scores in float32), every other leaf is ``dtype``: the
    model's ``torch_dtype``, bfloat16 as published unless the
    configuration says otherwise."""
    dtype = jnp.dtype(dtype or model.get("torch_dtype", "bfloat16"))
    d, vocab = model["d_model"], model["vocab_size"]
    H, G, D = (model["num_heads"], model["num_key_value_heads"],
               model["head_dim"])
    E, m = model["num_experts"], model["moe_intermediate_size"]
    out = (2 * model["num_layers"]) ** -0.5
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))

    def normal(k, shape, scale, to=dtype):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(to)

    @jax.jit
    def layer(k):
        ks = jax.random.split(k, 8)
        return {"norm1": jnp.ones((d,), dtype),
                "norm2": jnp.ones((d,), dtype),
                "q_norm": jnp.full((D,), QK_GAIN, dtype),
                "k_norm": jnp.full((D,), QK_GAIN, dtype),
                "wq": normal(ks[0], (d, H * D), d ** -0.5),
                "wk": normal(ks[1], (d, G * D), d ** -0.5),
                "wv": normal(ks[2], (d, G * D), d ** -0.5),
                "wo": normal(ks[3], (H * D, d), out * (H * D) ** -0.5),
                "router": normal(ks[4], (d, E), ROUTER_SCALE * d ** -0.5,
                                 jnp.float32),
                "experts_gate": normal(ks[5], (E, d, m), d ** -0.5),
                "experts_up": normal(ks[6], (E, d, m), d ** -0.5),
                "experts_down": normal(ks[7], (E, m, d),
                                       EXPERTS_DOWN * out * m ** -0.5)}

    @jax.jit
    def ends(k):
        ks = jax.random.split(k, 2)
        return {"embed": normal(ks[0], (vocab, d), EMBED_SCALE),
                "norm_f": jnp.ones((d,), dtype),
                "head": normal(ks[1], (d, vocab), d ** -0.5)}

    params = ends(jax.random.fold_in(key, 0))
    params["layers"] = [layer(jax.random.fold_in(key, 1 + li))
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
    """``x [T, heads, D]`` turned by ``pos [T]``: element ``i`` pairs with
    ``i + D/2``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (pos.astype(jnp.float32)[:, None] * freqs)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def route(x2, router, model, mm):
    """``(selected experts [T, k], gates [T, k])``: softmax over all the
    experts, the ``k`` largest, renormalised if ``norm_topk_prob``."""
    s = jax.nn.softmax(mm(x2, _f32(router)), axis=-1)
    gates, sel = lax.top_k(s, model["num_experts_per_tok"])
    if model.get("norm_topk_prob", True):
        gates = gates / gates.sum(axis=1, keepdims=True)
    return sel, gates


def _layer(lp, h, pos, copy, model, quant):
    """One layer over ``h [T, d]`` (float32): token ``t`` stands at
    position ``pos[t]`` in copy ``copy[t]`` (0 the clean sequence, 1 and
    up a denoising pass's copy, -1 padding that nothing sees)."""
    q_ = _rounder(quant)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=lax.Precision.HIGHEST)
    T = h.shape[0]
    H, G, D = (model["num_heads"], model["num_key_value_heads"],
               model["head_dim"])
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    blk = pos // model["block_length"]

    x = _rms(h, lp["norm1"], eps)
    q = _rope(_rms(mm(x, _f32(lp["wq"])).reshape(T, H, D), lp["q_norm"],
                   eps), pos, theta).reshape(T, G, H // G, D)
    k = _rope(_rms(mm(x, _f32(lp["wk"])).reshape(T, G, D), lp["k_norm"],
                   eps), pos, theta)
    v = mm(x, _f32(lp["wv"])).reshape(T, G, D)
    block = min(QUERY_BLOCK, T)

    def attend(start):
        def rows(a):
            return lax.dynamic_slice_in_dim(a, start, block, axis=0)
        s = jnp.einsum("qgmd,kgd->gmqk", q_(rows(q)), q_(k),
                       precision=lax.Precision.HIGHEST) * D ** -0.5
        bq, cq = rows(blk)[:, None], rows(copy)[:, None]
        seen = ((copy[None] == 0) & (blk[None] < bq)) \
            | ((copy[None] == cq) & (blk[None] == bq) & (cq >= 0))
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        return jnp.einsum("gmqk,kgd->qgmd", q_(p), q_(v),
                          precision=lax.Precision.HIGHEST)
    # the last block starts early enough to end at T: rows it shares
    # with the block before it are computed twice, alike
    starts = jnp.minimum(jnp.arange(-(-T // block)) * block, T - block)
    att = jnp.zeros((T, G, H // G, D), jnp.float32)
    for i, o in enumerate(lax.map(attend, starts)):
        att = lax.dynamic_update_slice_in_dim(att, o, starts[i], axis=0)
    h = h + mm(att.reshape(T, H * D), _f32(lp["wo"]))

    x2 = _rms(h, lp["norm2"], eps)
    sel, gates = route(x2, lp["router"], model, mm)
    # each token's weight on each expert (0 where it was not selected)
    weight = jnp.zeros((T, lp["router"].shape[1]), jnp.float32).at[
        jnp.arange(T)[:, None], sel].add(gates)

    def one(acc, e):
        wg, wu, wd, w = e
        y = mm(jax.nn.silu(mm(x2, _f32(wg))) * mm(x2, _f32(wu)), _f32(wd))
        return acc + w[:, None] * y, None
    routed, _ = lax.scan(one, jnp.zeros_like(h),
                         (lp["experts_gate"], lp["experts_up"],
                          lp["experts_down"], weight.T))
    return h + routed


_layer_jit = jax.jit(
    lambda lp, h, pos, copy, shape, quant: _layer(lp, h, pos, copy,
                                                  dict(shape), quant),
    static_argnums=(4, 5))


def hidden(params, tokens, pos, copy, model, quant=None):
    """The residual stream after the last layer, ``[T, d]`` float32."""
    shape = tuple(sorted((k, v) for k, v in model.items()
                         if not isinstance(v, (list, dict))))
    h = _f32(params["embed"][tokens])
    for lp in params["layers"]:
        h = _layer_jit(lp, h, pos, copy, shape, quant)
    return h


def _head(params, h, eps, quant):
    q_ = _rounder(quant)
    x = _rms(h, params["norm_f"], eps)
    return jnp.matmul(q_(x), q_(_f32(params["head"])),
                      precision=lax.Precision.HIGHEST)


_head_jit = jax.jit(_head, static_argnums=(2, 3))


def forward(params, tokens, model, quant=None):
    """Logits ``[T, vocab]`` (float32) of one clean sequence ``tokens
    [T]`` under the block-causal mask, the logits at a position for that
    position itself."""
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    h = hidden(params, tokens, jnp.arange(T, dtype=jnp.int32),
               jnp.zeros((T,), jnp.int32), model, quant)
    return _head_jit(params, h, model["rms_norm_eps"], quant)


def reveal_plan(model):
    """How many positions each denoising pass of a block reveals."""
    base, more = divmod(model["block_length"], model["denoising_steps"])
    return [base + (j < more) for j in range(model["denoising_steps"])]


def sequential_passes(n_prompt, n_served, model):
    """Under ``sequential`` the pass of its block that revealed each
    position from the prompt's end to the end of the last block: the
    leftmost masked positions first, by ``reveal_plan``. The first
    ``n_served`` are the served tokens', the rest the positions the last
    block computed past the answer."""
    B, plan = model["block_length"], reveal_plan(model)
    passes = []
    for b0 in range(n_prompt // B * B, n_prompt + n_served, B):
        at, left = 0, B - max(0, n_prompt - b0)
        while left:
            take = min(plan[at], left)
            passes += [at] * take
            left, at = left - take, at + 1
    return passes


@functools.partial(jax.jit, static_argnums=(5, 6))
def _window_gaps(params, h, hq, chosen, start, eps, quant):
    """The gaps of ``HEAD_ROWS`` read-out rows from ``start``."""
    def rows(a):
        return lax.dynamic_slice_in_dim(a, start, HEAD_ROWS, axis=0)
    logits = _head(params, rows(h), eps, None)
    pick = rows(chosen) if quant is None else jnp.argmax(
        _head(params, rows(hq), eps, quant), axis=-1)
    return logits.max(axis=-1) - jnp.take_along_axis(
        logits, pick[:, None], axis=1)[:, 0]


def token_gaps(params, prompt, served, model, reveal_pass, block_tail=(),
               pad_to=None, quant=None):
    """For each served token, how far its logit lies below the best logit
    at its position in the pass that revealed it: ``best - logit[served]``,
    an array of ``len(served)``. ``reveal_pass`` gives that pass for each
    served token, ``block_tail`` ``(token, pass)`` for the positions the
    last block computed past the answer (a position no read-out pass saw
    revealed needs no token). With ``quant`` the served tokens are
    ignored where a token is chosen: the gap is read for the token that
    the lower precision puts first (the control). The clean sequence is
    padded to ``pad_to`` and each copy to whole ``HEAD_ROWS``, so that one
    program serves every length."""
    B, steps = model["block_length"], model["denoising_steps"]
    mask_id = model["mask_token_id"]
    n_p, n_s = len(prompt), len(served)
    clean = list(prompt) + list(served)
    tail = list(block_tail)
    g0, g1 = n_p // B * B, -(-len(clean) // B) * B
    # the generated region, position by position: its token and the pass
    # that revealed it (-1: a prompt token, revealed from the start)
    region_tok = clean[g0:] + [t for t, _ in tail]
    region_pass = [-1] * (n_p - g0) + list(reveal_pass) \
        + [p for _, p in tail]
    short = g1 - g0 - len(region_tok)      # a tail no one handed over
    region_tok += [mask_id] * short
    region_pass += [steps] * short
    pad_to = pad_to or -(-len(clean) // HEAD_ROWS) * HEAD_ROWS
    width = -(-(n_s + 2 * B) // HEAD_ROWS) * HEAD_ROWS
    total = pad_to + steps * width
    toks = np.zeros(total, np.int32)
    pos = np.zeros(total, np.int32)
    copy = np.full(total, -1, np.int32)
    toks[:len(clean)] = clean
    pos[:len(clean)] = np.arange(len(clean))
    copy[:len(clean)] = 0
    read = np.zeros(n_s, np.int32)         # each served token's row
    for j in range(steps):
        at = pad_to + j * width
        toks[at:at + g1 - g0] = [t if p < j else mask_id
                                 for t, p in zip(region_tok, region_pass)]
        pos[at:at + g1 - g0] = np.arange(g0, g1)
        copy[at:at + g1 - g0] = j + 1
        for i, p in enumerate(reveal_pass):
            if p == j:
                read[i] = at + n_p - g0 + i
    toks, pos, copy = jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(copy)
    eps = model["rms_norm_eps"]
    h = hidden(params, toks, pos, copy, model)
    hq = h if quant is None else hidden(params, toks, pos, copy, model,
                                        quant)
    rows = -(-n_s // HEAD_ROWS) * HEAD_ROWS
    read = jnp.zeros((rows,), jnp.int32).at[:n_s].set(jnp.asarray(read))
    chosen = jnp.zeros((rows,), jnp.int32).at[:n_s].set(
        jnp.asarray(list(served), jnp.int32))
    h, hq = h[read], hq[read]
    out = [_window_gaps(params, h, hq, chosen, start, eps, quant)
           for start in range(0, rows, HEAD_ROWS)]
    return jnp.concatenate(out)[:n_s]


def served_token_gaps(params, prompt, served, model, pad_to, quant=None):
    """``token_gaps`` for the serve driver, which keeps a request's tokens
    and nothing else: only under ``sequential`` do the tokens fix what
    each pass saw."""
    if model["remasking_strategy"] != "sequential":
        raise ValueError(
            "the served tokens alone fix what each pass saw only under "
            f"'sequential', not {model['remasking_strategy']!r}: hand "
            "token_gaps the request's reveal passes")
    passes = sequential_passes(len(prompt), len(served), model)
    return token_gaps(params, prompt, served, model,
                      passes[:len(served)], pad_to=pad_to, quant=quant)
