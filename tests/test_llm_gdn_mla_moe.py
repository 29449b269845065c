"""The GigaChat-3.5 block of ``TinyDecoder`` (``model_type``
``gigachat3_5``: GatedDeltaNet layers over a slot state pool beside latent
attention over a paged latent pool, sandwich norms, a share of the routed
experts) at a toy size on the CPU: the paged path against the benchmark's
plain reference, the gated delta-rule kernel in interpret mode against its
``jnp`` twin, the expert shares against the uncut layer, YaRN against
hand-worked values, what the engine refuses, and one step in flight
against the synchronous step.
"""
import functools
import hashlib
import importlib
import math
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from mxnet_tpu.ops import gdn, moe, registry            # noqa: E402
from mxnet_tpu.serving.llm import (LLMEngine, LLMServer,  # noqa: E402
                                   PagedKVCache, Sequence)
from mxnet_tpu.serving.llm import model as model_mod    # noqa: E402
from mxnet_tpu.serving.llm.model import (               # noqa: E402
    DecoderConfig, TinyDecoder, UnsupportedModelFeatureError,
    yarn_inv_freq, yarn_mscale)

ref = importlib.import_module("benchmark.reference.gigachat35_decoder")

YARN = {"factor": 8, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
        "type": "yarn"}
#: layer 0 GatedDeltaNet and dense, then a period of three GatedDeltaNet
#: layers and one latent layer, each with 4 of 16 routed experts held
#: (experts 4 to 7), top-4, one shared; latent 16 + 8, GatedDeltaNet 2 key
#: heads over 4 value heads of 16
TOY = dict(vocab_size=97, d_model=64, num_layers=5, num_heads=4, d_ff=96,
           max_context=128, model_type="gigachat3_5",
           full_attention_layers=[4], q_lora_rank=24, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
           rope_theta=10000.0, rope_scaling=YARN, rms_norm_eps=1e-6,
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, linear_sigmoid_gate_scale=2.0,
           layernorm_gating_weight=2.0, first_k_dense_replace=1,
           router_experts=16, first_routed_expert=4, n_routed_experts=4,
           num_experts_per_tok=4, n_shared_experts=1,
           moe_intermediate_size=32, routed_scaling_factor=2.5,
           norm_topk_prob=True, swiglu_limit=10.0, torch_dtype="float32")
TOKENS = np.random.RandomState(0).randint(0, 97, 40)


@pytest.fixture(scope="module")
def model():
    return TinyDecoder(DecoderConfig(**TOY))


@pytest.fixture(scope="module")
def params():
    return ref.init_params(5, TOY, dtype=jnp.float32)


def _prompt(rs, n):
    return [int(t) for t in rs.randint(0, TOY["vocab_size"], n)]


def _drain(engine, seqs, limit=2000):
    for s in seqs:
        engine.add(s)
    for _ in range(limit):
        if not engine.has_work():
            return
        engine.step()
    raise AssertionError("the engine did not drain")


def _worst_gap(params, prompt, served):
    return float(ref.served_token_gaps(params, prompt, served, TOY,
                                       128).max())


def paged_logits(model, params, tokens, chunk, dtype="float32"):
    """``tokens`` through ``decode_flat`` in chunks of ``chunk`` (1: one
    decode row a step), one sequence in slot 1 whose pages lie scattered
    in the pool; the slot's state starts as garbage (the run at position
    0 must not read it)."""
    pool = PagedKVCache.for_model(model, 4, 40, 128, max_seqs=2,
                                  dtype=dtype)
    pages, state, conv = pool.pools()
    assert pages.shape == (1, 40, 4, 128) and state.shape == (4, 2, 4, 16,
                                                              16)
    state = state + 7.0
    conv = conv - 3.0
    table = jnp.asarray([[0] * 32, [3, 5, 7, 9, 11, 13, 15, 17, 2, 4, 6, 8]
                         + [0] * 20], jnp.int32)
    step = jax.jit(lambda *a, state: model.decode_flat(*a, state=state))
    out = []
    for start in range(0, len(tokens), chunk):
        n = min(chunk, len(tokens) - start)
        logits, pages, state, conv, load = step(
            params, jnp.asarray(tokens[start:start + n], jnp.int32),
            jnp.arange(start, start + n, dtype=jnp.int32),
            jnp.ones(n, jnp.int32), jnp.ones(n, jnp.int32), pages, None,
            table, state=(state, conv))
        out.append(logits)
        # four expert layers, four experts a token: every pair is held
        # here or elsewhere
        assert int(load[0]) + int(load[4]) == 4 * 4 * n
        assert int(load[1]) <= 4 * 4 and int(load[2]) <= n
    return jnp.concatenate(out)


@pytest.mark.parametrize("chunk", [40, 7, 1], ids=["one_chunk", "chunks",
                                                   "decode_rows"])
def test_paged_path_against_the_reference(model, params, chunk):
    """Prefill in one chunk, in chunks of 7, and token by token through
    the latent pool and the slot state pool, against the reference's whole
    forward. Float32 on both sides: the gap is round-off of a different
    order of summation (absorbed latent attention against the expanded
    form, the delta rule per token against a scan), a thousandth of the
    logits' spread."""
    want = ref.forward(params, jnp.asarray(TOKENS, jnp.int32), TOY)
    got = paged_logits(model, params, TOKENS, chunk)
    spread = float(jnp.abs(want).max())
    assert spread > 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3 * spread, rtol=0)
    assert (np.argmax(np.asarray(got), -1)
            == np.argmax(np.asarray(want), -1)).mean() > 0.95


def test_bfloat16_pages_and_weights_stay_near_the_reference(model):
    """The published dtype: bf16 weights and latent pages, float32
    state. The same weights upcast give the reference's logits to
    float32 round-off, so what bf16 leaves is the rounding of the
    activations before each product: at this toy size it swaps one of a
    token's 4 experts of 16 at a near tie often, and every sublayer's
    output is normed to the stream's own scale (no residual damping), so
    a swap moves a position's logits by up to a quarter of their spread;
    the median position moves by some per cent, and four first choices
    in five stand."""
    params = ref.init_params(5, TOY, dtype=jnp.bfloat16)
    want = np.asarray(ref.forward(params, jnp.asarray(TOKENS, jnp.int32),
                                  TOY))
    spread = np.abs(want).max()
    upcast = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    exact = np.asarray(paged_logits(model, upcast, TOKENS, 8))
    assert np.abs(exact - want).max() < 1e-4 * spread
    got = np.asarray(paged_logits(model, params, TOKENS, 8,
                                  dtype="bfloat16"))
    err = np.abs(got - want).max(axis=1)
    assert np.median(err) < 0.1 * spread and err.max() < 0.4 * spread
    assert (got.argmax(-1) == want.argmax(-1)).mean() >= 0.75


def _runs(rs, T, hk, hv, d, slots, layers):
    """A packed batch: slot 2 a chunk from position 0, slot 0 one decode
    row, slot 1 a chunk mid-sequence, then padding."""
    q = rs.randn(T, hk, d).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    k = rs.randn(T, hk, d).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rs.randn(T, hv, d).astype(np.float32)
    g = -np.abs(rs.randn(T, hv)).astype(np.float32) * 0.1
    beta = (1 / (1 + np.exp(-rs.randn(T, hv)))).astype(np.float32)
    state = rs.randn(layers, slots, hv, d, d).astype(np.float32)
    return q, k, v, g, beta, jnp.asarray(state)


@pytest.mark.parametrize("run", ["decode", "chunk"])
def test_state_kernel_in_interpret_mode_against_the_scan(run):
    """``_gdn_state_pallas`` (interpret mode) against the per-token
    ``lax.scan``: runs of 1 (every row a decode row) and of a chunk
    beside a decode row and padding, two head blocks of a pool of three
    layers. Outputs and the pool agree to float32 round-off; padding rows
    come back as zeros and touch no state."""
    rs = np.random.RandomState(1 if run == "decode" else 2)
    if run == "decode":
        seq = np.array([2, 0, 1, 0], np.int32)
        pos = np.array([9, 4, 0, 0], np.int32)
        valid = np.array([1, 1, 1, 0], np.int32)
    else:
        seq = np.array([2] * 5 + [0] + [1] * 4 + [0, 0], np.int32)
        pos = np.array([0, 1, 2, 3, 4, 7, 3, 4, 5, 6, 0, 0], np.int32)
        valid = np.array([1] * 10 + [0, 0], np.int32)
    q, k, v, g, beta, state = _runs(rs, len(seq), 4, 8, 128, 3, 3)
    assert gdn._head_block(len(seq), 8, 4, 128, 128) == 8
    want_o, want = gdn.gdn_state_step(q, k, v, g, beta, state, seq, pos,
                                      valid, 1, use_pallas=False)
    got_o, got = gdn.gdn_state_step(q, k, v, g, beta, state, seq, pos,
                                    valid, 1, use_pallas=True,
                                    interpret=True)
    live = valid.astype(bool)
    np.testing.assert_allclose(np.asarray(got_o)[live],
                               np.asarray(want_o)[live], atol=2e-6)
    assert not np.asarray(got_o)[~live].any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6)
    # layers 0 and 2 are as they came
    np.testing.assert_array_equal(np.asarray(got)[[0, 2]],
                                  np.asarray(state)[[0, 2]])


def test_state_kernel_head_block_fits_the_budget():
    """At the cell's widths (64 value heads over 32 key heads of 128) a
    decode step of 128 rows holds 16 value heads a grid step and the top
    rung of 2048 tokens 4: the blocks, each held twice, within
    ``VMEM_BUDGET``."""
    assert gdn._head_block(128, 64, 32, 128, 128) == 16
    assert gdn._head_block(2048, 64, 32, 128, 128) == 4
    assert gdn._head_block(16384, 64, 32, 128, 128) == 2


def test_delta_rule_takes_the_prediction_out():
    """One token into a state that already holds its key: the write
    replaces what the state predicted for that key by ``beta`` of the way
    to the new value, and leaves an orthogonal key's answer alone."""
    d = 128
    k1 = np.zeros((1, 1, d), np.float32)
    k1[0, 0, 0] = 1.0
    k2 = np.zeros((1, 1, d), np.float32)
    k2[0, 0, 1] = 1.0
    state = np.zeros((1, 1, 1, d, d), np.float32)
    state[0, 0, 0, 0, :] = 3.0          # key 1 -> 3
    state[0, 0, 0, 1, :] = -1.0         # key 2 -> -1
    v = np.full((1, 1, d), 5.0, np.float32)
    g = np.zeros((1, 1), np.float32)
    beta = np.full((1, 1), 0.25, np.float32)
    one = np.ones(1, np.int32)
    _, new = gdn.gdn_state_step(k1, k1, v, g, beta, jnp.asarray(state),
                                np.zeros(1, np.int32), one, one, 0,
                                use_pallas=False)
    s = np.asarray(new)[0, 0, 0]
    np.testing.assert_allclose(s[0], 3.0 + 0.25 * (5.0 - 3.0))
    np.testing.assert_allclose(s[1], -1.0)
    # key 1 written again, key 2 asked: its answer has not moved
    o, _ = gdn.gdn_state_step(k2, k1, v, g, beta, new,
                              np.zeros(1, np.int32), one, one, 0,
                              use_pallas=False)
    np.testing.assert_allclose(np.asarray(o)[0, 0], -1.0)


# ----------------------------------------------------- expert shares --

@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["plain_twin", "grouped_kernel"])
def test_expert_shares_add_up_to_the_uncut_layer(use_pallas):
    """A toy layer of 16 experts cut into four chips' shares, ``[0, 4)``,
    ``[4, 8)``, ``[8, 12)`` and ``[12, 16)``: each share computes only the
    pairs routed to its experts, and the four add up to the layer that
    holds all 16 (the shared expert, computed once, beside them). The
    loads of the shares split the pairs: held here plus held elsewhere is
    every pair, on every share."""
    rs = np.random.RandomState(3)
    T, d, m, E, k = 24, 64, 32, 16, 4
    x = jnp.asarray(rs.randn(T, d).astype(np.float32))
    router = jnp.asarray(rs.randn(d, E).astype(np.float32) / 8)
    wg, wu = (jnp.asarray(rs.randn(E, d, m).astype(np.float32) / 8)
              for _ in range(2))
    wd = jnp.asarray(rs.randn(E, m, d).astype(np.float32) / 6)
    valid = jnp.asarray(np.r_[np.ones(20), np.zeros(4)].astype(np.int32))
    sel, gates = moe.route_noaux_tc(x, router, jnp.zeros(E), k, True, 2.5)
    kw = dict(use_pallas=use_pallas, interpret=True, limit=10.0)
    whole = moe.moe_experts(x, sel, gates, valid, wg, wu, wd, **kw)
    parts, pairs = [], 0
    for first in range(0, E, 4):
        share = slice(first, first + 4)
        parts.append(moe.moe_experts(x, sel, gates, valid, wg[share],
                                     wu[share], wd[share], first=first,
                                     **kw))
        load = np.asarray(moe.moe_load(sel, valid, 4, first=first))
        assert load[0] + load[4] == 20 * k
        pairs += load[0]
    assert pairs == 20 * k
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=1e-5)
    assert not np.asarray(whole)[20:].any()
    # a share that holds every expert is the layer itself
    np.testing.assert_allclose(
        np.asarray(moe.moe_experts(x, sel, gates, valid, wg, wu, wd,
                                   first=0, **kw)), np.asarray(whole),
        atol=1e-6)


def _normalised(text):
    """An exported module's text without source locations and without
    the Mosaic kernels' serialized bodies (which carry file names)."""
    text = re.sub(r'loc\(.*?\)', '', text)
    text = "\n".join(line for line in text.splitlines()
                     if not line.startswith("#loc"))
    return re.sub(r'\\22body\\22: \\22[A-Za-z0-9+/=]*\\22', 'BODY', text)


#: the toy Moonlight- and SDAR-shaped steps of the exact-program check
MOONLIGHT = dict(vocab_size=97, d_model=64, num_layers=3, num_heads=4,
                 d_ff=96, max_context=64, model_type="deepseek_v3",
                 kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
                 v_head_dim=8, rope_theta=50000.0, rms_norm_eps=1e-5,
                 first_k_dense_replace=1, n_routed_experts=8,
                 num_experts_per_tok=2, n_shared_experts=2,
                 moe_intermediate_size=32, routed_scaling_factor=2.446,
                 norm_topk_prob=True)
SDAR = dict(vocab_size=509, d_model=64, num_layers=2, num_heads=4, d_ff=96,
            max_context=64, model_type="sdar_moe", num_key_value_heads=2,
            head_dim=16, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, mask_token_id=508)


@pytest.mark.parametrize("shape,width,digest", [
    (MOONLIGHT, 128, "eff36274f51edea0"), (SDAR, 64, "6c20926d955403b5")],
    ids=["moonlight", "sdar"])
def test_blocks_that_hold_every_expert_keep_their_step_program(
        monkeypatch, shape, width, digest):
    """A model that holds all of its experts passes no held range, and
    its step program is the one it was before the range existed: the
    ``deepseek_v3`` and ``sdar_moe`` toy steps, exported for the TPU with
    the grouped kernel, have the digest that the same export gave before
    ``moe_experts`` and ``moe_load`` took ``first`` (locations and the
    kernels' serialized bodies left out; the kernel's body takes no
    operation from a ``limit`` of ``None``)."""
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    m = TinyDecoder(DecoderConfig(**shape))
    p = m.init_params(0)
    T = 16
    pages = jnp.zeros((shape["num_layers"], 20, 4, width), jnp.float32)
    args = (p, jnp.zeros(T, jnp.int32), jnp.arange(T, dtype=jnp.int32),
            jnp.zeros(T, jnp.int32), jnp.ones(T, jnp.int32), pages, None,
            jnp.zeros((2, 16), jnp.int32))
    exported = jax.export.export(jax.jit(lambda *a: m.decode_flat(*a)),
                                 platforms=["tpu"])(*args)
    text = _normalised(exported.mlir_module())
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# --------------------------------------------------------------- YaRN --

def test_yarn_frequencies_and_scale_against_hand_worked_values():
    """The cell's YaRN (rotary part 64, base 100000, factor 8 over 32768
    positions, beta 32 and 1): the correction range is pairs 14 to 24
    (``64 ln(32768 / (32 x 2 pi)) / (2 ln 100000)`` = 14.16, floored;
    with 1 turn 23.79, ceiled), pairs below it keep the plain frequency,
    pairs from 24 on are divided by 8, pair 19 is half of each; the
    softmax scale is ``192 ** -0.5 x (0.1 ln 8 + 1) ** 2``."""
    sc = {"factor": 8, "original_max_position_embeddings": 32768,
          "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
    got = yarn_inv_freq(64, 100000.0, sc)
    plain = 100000.0 ** (-np.arange(0, 64, 2) / 64)
    assert got.shape == (32,) and got.dtype == np.float32
    np.testing.assert_allclose(got[:15], plain[:15], rtol=1e-6)
    np.testing.assert_allclose(got[24:], plain[24:] / 8, rtol=1e-6)
    np.testing.assert_allclose(got[19], plain[19] * (0.5 + 0.5 / 8),
                               rtol=1e-6)
    assert yarn_mscale(8) == pytest.approx(1.2079441541679836)
    assert yarn_mscale(8) ** 2 == pytest.approx(1.4591, abs=1e-4)
    assert yarn_mscale(1) == 1.0
    shape = dict(TOY, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 rope_theta=100000.0, rope_scaling=sc)
    freqs, scale = TinyDecoder(DecoderConfig(**shape))._rope_gdn()
    assert scale == pytest.approx(192 ** -0.5 * 1.4591, rel=1e-4)
    assert scale == pytest.approx(ref.softmax_scale(shape), rel=1e-12)
    np.testing.assert_allclose(freqs, ref.inv_freq(shape), rtol=1e-6)


def test_interleaved_rotary_pairs_turn_together():
    """``rope_interleave``: elements 2i and 2i + 1 turn by position x
    ``freqs[i]``, so a query and a key keep their product at equal
    positions and it depends on the positions' distance alone."""
    rs = np.random.RandomState(4)
    q = jnp.asarray(rs.randn(1, 8).astype(np.float32))
    k = jnp.asarray(rs.randn(1, 8).astype(np.float32))
    freqs = jnp.asarray(yarn_inv_freq(8, 10000.0, YARN))

    def dot(pq, pk):
        a = model_mod._rope_interleaved(q, jnp.asarray([pq]), freqs)
        b = model_mod._rope_interleaved(k, jnp.asarray([pk]), freqs)
        return float((a * b).sum())
    assert dot(0, 0) == pytest.approx(float((q * k).sum()), rel=1e-5)
    assert dot(7, 3) == pytest.approx(dot(14, 10), rel=1e-4)
    turned = model_mod._rope_interleaved(q, jnp.asarray([1]), freqs)
    want = ref._rope(q, jnp.asarray([1]), np.asarray(freqs))
    # the program keeps the pairs in half-split order, the reference in
    # place: the same numbers, permuted
    np.testing.assert_allclose(
        np.sort(np.asarray(turned)), np.sort(np.asarray(want)), atol=1e-6)


# ------------------------------------------- the configuration's shape --

def test_cache_layout_and_configuration_checks():
    """The cell's layout: one latent pool for the one latent layer and
    state pools for the four GatedDeltaNet layers; bad keys are refused
    by name."""
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "gigachat3_5_432b_a28b.json")) as fh:
        shape = json.load(fh)["model"]
    m = TinyDecoder(DecoderConfig(**shape))
    assert m.cache_layout == {
        "kind": "latent", "latent_dim": 576, "layers": 1,
        "state": {"gdn": (4, 64, 128, 128), "conv": (4, 3, 16384)}}
    assert m.expert_slots == 16 * 4
    assert m.refused_features == ("adapter_bank", "draft_model", "mesh",
                                  "weight_dtype", "kv_dtype",
                                  "prefix_cache")
    assert DecoderConfig(**shape).to_dict() == shape
    for bad, named in (({"full_attention_layers": []},
                        "full_attention_layers"),
                       ({"full_attention_layers": [5]},
                        "full_attention_layers"),
                       ({"first_routed_expert": 250}, "n_routed_experts"),
                       ({"linear_num_key_heads": 3}, "linear_num_value"),
                       ({"qk_rope_head_dim": 63}, "qk_rope_head_dim")):
        with pytest.raises(ValueError, match=named):
            DecoderConfig(**dict(TOY, **bad))


def test_host_parameter_tree_is_the_references(model, params):
    """``TinyDecoder.init_params`` makes the reference's leaves, shapes
    and rule of scale (the router and the experts by fan-in, ``A_log``
    from ln(1/16) to 0)."""
    mine = model.init_params(0)
    flat = jax.tree_util.tree_leaves_with_path
    assert [(p, a.shape) for p, a in flat(mine)] \
        == [(p, a.shape) for p, a in flat(params)]
    lp = mine["layers"][1]
    assert np.std(lp["router"]) == pytest.approx(64 ** -0.5, rel=0.1)
    assert np.std(lp["experts_down"]) == pytest.approx(32 ** -0.5, rel=0.1)
    np.testing.assert_allclose(lp["A_log"][[0, -1]], [math.log(1 / 16), 0],
                               atol=1e-6)


# ----------------------------------------------------------- refusals --

def _toy_draft():
    return TinyDecoder(DecoderConfig(vocab_size=97, max_context=128))


@pytest.mark.parametrize("kwargs,named", [
    (lambda: {"adapter_bank": object()}, "adapter_bank"),
    (lambda: {"weight_dtype": "int8"}, "weight_dtype"),
    (lambda: {"kv_dtype": "int8"}, "kv_dtype"),
    (lambda: {"mesh": "tp=2"}, "mesh"),
    (lambda: {"prefix_cache": True}, "prefix_cache"),
    (lambda: {"draft_model": _toy_draft(),
              "draft_params": _toy_draft().init_params(0)}, "draft_model"),
])
def test_engine_refuses_what_the_block_does_not_take(model, params, kwargs,
                                                     named):
    with pytest.raises(UnsupportedModelFeatureError, match=named):
        LLMEngine(model, params, block_size=4, **kwargs())


def test_entry_points_the_block_does_not_have_raise(model, params):
    z = jnp.zeros((1,), jnp.int32)
    with pytest.raises(UnsupportedModelFeatureError, match="oracle"):
        model.forward(params, jnp.zeros((1, 2), jnp.int32))
    pages = jnp.zeros((1, 4, 4, 128), jnp.float32)
    with pytest.raises(UnsupportedModelFeatureError, match="quantized"):
        model.decode_flat(params, z, z, z, z, pages, None,
                          jnp.zeros((1, 4), jnp.int32), k_scales=pages)


# --------------------------------------------------------- the engine --

def test_engine_serves_the_references_first_choices(model, params):
    """Five requests through two slots (each slot used again) in chunks
    of 5 beside decode rows: every served token is the reference's first
    choice, the pools' every slot state restarts at position 0, and the
    load's pairs held elsewhere reach the step span and the counters."""
    from mxnet_tpu.observability.tracing import get_tracer
    rs = np.random.RandomState(5)
    prompts = [_prompt(rs, n) for n in (9, 30, 4, 17, 12)]
    engine = LLMEngine(model, params, max_seqs=2, block_size=4,
                       prefill_chunk=5)
    seqs = [Sequence(p, 8) for p in prompts]
    tracer = get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enable()
    try:
        _drain(engine, seqs)
        spans = [s["attrs"] for s in tracer.snapshot()
                 if s["name"] == "mxtpu.llm.step" and "rows" in s["attrs"]]
    finally:
        if not was_on:
            tracer.disable()
        tracer.clear()
    for p, s in zip(prompts, seqs):
        assert len(s.generated) == 8
        assert _worst_gap(params, p, s.generated) <= 1e-4
    assert engine.state_pool_stats()["starts"] == 5
    assert spans and all(a["state_rows"] == len(a["rows"]) for a in spans)
    fed = sum(n for a in spans for n, _ in a["rows"])
    assert sum(a["moe_pairs"] + a["moe_pairs_elsewhere"] for a in spans) \
        == fed * 4 * 4


def test_pipelined_server_answers_as_the_synchronous_step(model, params):
    """One step in flight behind ``LLMServer``: the fed tokens are read
    from the step before's outputs on the device and the state from its
    donated pools; the tokens are the synchronous ``engine.step()``'s,
    and the counter of pairs held elsewhere moves."""
    rs = np.random.RandomState(6)
    prompts = [_prompt(rs, n) for n in (5, 14, 9)]
    kw = dict(max_seqs=2, block_size=16, prefill_chunk=4)
    engine = LLMEngine(model, params, **kw)
    seqs = [Sequence(p, 8) for p in prompts]
    _drain(engine, seqs)
    server = LLMServer(model, params, name="gdn_mla_ahead", **kw)
    server.start()
    try:
        got = [f.result(timeout=300).tokens
               for f in [server.submit(p, 8) for p in prompts]]
        stats = server.stats()
    finally:
        server.shutdown()
    assert got == [s.output_tokens() for s in seqs]
    assert stats["overlap_share"] > 0.5
    assert stats["moe_pairs_elsewhere"] > stats["moe_routed_pairs"] > 0
    assert server.engine.cache.allocator.num_used == 0


def test_registered_op_runs_the_scan_off_the_tpu():
    rs = np.random.RandomState(7)
    q, k, v, g, beta, state = _runs(rs, 3, 1, 2, 128, 1, 1)
    one = np.ones(3, np.int32)
    op = registry.get("gdn_state_step")
    assert op is not None and not op.differentiable
    got_o, _ = op.impl(q, k, v, g, beta, state, np.zeros(3, np.int32),
                       np.arange(3, dtype=np.int32), one)
    want_o, _ = gdn.gdn_state_step_reference(
        *(jnp.asarray(a) for a in (q, k, v, g, beta)), state,
        jnp.zeros(3, jnp.int32), jnp.arange(3, dtype=jnp.int32),
        jnp.ones(3, jnp.int32), 0)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=1e-6)


def test_kernel_path_through_the_engine(monkeypatch, params):
    """The step with the state kernel itself (interpret mode) and the
    grouped expert kernel: the same served tokens as the twins'."""
    for name in ("gdn_state_step", "moe_experts"):
        monkeypatch.setattr(model_mod, name, functools.partial(
            getattr(model_mod, name), use_pallas=True, interpret=True))
    # a model of its own: its programs trace the kernels, not the twins
    m = TinyDecoder(DecoderConfig(**TOY))
    rs = np.random.RandomState(8)
    prompts = [_prompt(rs, n) for n in (11, 3, 20)]
    engine = LLMEngine(m, params, max_seqs=2, block_size=8,
                       prefill_chunk=8)
    seqs = [Sequence(p, 6) for p in prompts]
    _drain(engine, seqs)
    for p, s in zip(prompts, seqs):
        assert _worst_gap(params, p, s.generated) <= 1e-4
