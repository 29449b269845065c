"""A small decoder-only transformer in paged-decode form.

What the serving engine calls and reads of a model is stated once, in
:class:`~.engine.LLMEngine`'s docstring (the model protocol);
:class:`TinyDecoder` is its reference implementation, for six blocks
(``DecoderConfig.model_type``; each entry point below goes to the
block's own method through ``TinyDecoder._of_block``). Its entry points:

- :meth:`TinyDecoder.decode_flat`: the step. A packed ``[T]`` batch of
  query tokens from many sequences against the paged cache; each
  attention layer writes its tokens' K/V (or latents) into their page
  slots, then attends over the block-table-indirected history through
  :func:`~mxnet_tpu.ops.ragged_attention.ragged_flat_attention`,
  :func:`~mxnet_tpu.ops.ragged_attention.mla_flat_attention`,
  :func:`~mxnet_tpu.ops.ragged_attention.gqa_flat_attention` or
  :func:`~mxnet_tpu.ops.ragged_attention.gqa_window_attention`; each
  state-space layer advances its sequences' slot states through
  :func:`~mxnet_tpu.ops.ssd.ssd_state_step`, each GatedDeltaNet layer
  through :func:`~mxnet_tpu.ops.gdn.gdn_state_step`. A pure function of
  ``(params, inputs)``; the engine jits it with donated pools, and
  prefill, decode and speculative verify are all this one call.
- :meth:`TinyDecoder.forward`: the eager oracle. A dense causal forward
  over a whole prefix ``[B, T]``, returning logits and the per-layer
  K/V (or latents) it computed. The engine never calls it; the serving
  tests compare the step with it, and :func:`greedy_decode_reference`
  is built on it and on :func:`_incremental_step`.

The architecture is deliberately small (the subsystem under test is
the serving engine, not a model zoo).
"""
from __future__ import annotations

import functools

import numpy as np

from ...ops.ragged_attention import (ragged_flat_attention,
                                     ragged_flat_attention_sharded,
                                     mla_flat_attention,
                                     gqa_flat_attention,
                                     gqa_window_attention)
from ...ops.ssd import ssd_state_step, slot_runs
from ...ops.gdn import gdn_state_step
from ...ops.moe import (route_noaux_tc, route_softmax_topk, moe_experts,
                        moe_load, moe_load_sum)
from ...ops.flash_attention import attention_reference
from ...ops.lora import (paged_lora_delta, gather_adapter,
                         PROJ_Q, PROJ_K, PROJ_V, PROJ_O)
from ...ops.quantization import quantized_matmul

__all__ = ["DecoderConfig", "TinyDecoder", "greedy_decode_reference",
           "UnsupportedModelFeatureError", "GPT2", "DEEPSEEK_V3",
           "GRANITE_HYBRID", "SDAR_MOE", "GIGACHAT3_5", "COHERE2_MOE",
           "yarn_inv_freq", "yarn_mscale"]

#: the blocks a :class:`DecoderConfig` can describe (``model_type``)
GPT2, DEEPSEEK_V3 = "gpt2", "deepseek_v3"
GRANITE_HYBRID = "granitemoehybrid"
SDAR_MOE = "sdar_moe"
GIGACHAT3_5 = "gigachat3_5"
COHERE2_MOE = "cohere2_moe"
#: how a block-diffusion model picks the masked positions a pass reveals
REMASKING = ("sequential", "low_confidence_static",
             "low_confidence_dynamic")
#: the kinds of layer a ``granitemoehybrid`` model's ``layer_types`` name
MAMBA, ATTENTION = "mamba", "attention"
#: the kinds of layer a ``cohere2_moe`` model's ``layer_types`` name
SLIDING, FULL = "sliding_attention", "full_attention"


class UnsupportedModelFeatureError(ValueError):
    """A model's block does not take a feature that was asked of it (an
    adapter bank, quantized weights or pages, a tensor-parallel mesh, a
    draft model, a decode entry point it does not implement)."""


def _lora_all_rows(x2d, a_sel, b_sel, li, proj, scale):
    """Single-adapter LoRA delta for every row of ``x2d [N, d]`` —
    the oracle-side twin of the flat step's per-token gather:
    ``a_sel/b_sel [P, L, 4, d|r, r|d]`` are one adapter's padded
    factor pages (:meth:`AdapterBank.adapter_arrays`), broadcast to
    every row so the einsum structure matches
    :func:`~...ops.lora.paged_lora_delta` exactly."""
    import jax.numpy as jnp
    n = x2d.shape[0]
    a = a_sel[:, li, proj]                       # [P, d, r]
    b = b_sel[:, li, proj]                       # [P, r, d]
    return paged_lora_delta(
        x2d,
        jnp.broadcast_to(a[None], (n,) + a.shape),
        jnp.broadcast_to(b[None], (n,) + b.shape),
        jnp.full((n,), scale, x2d.dtype))


class DecoderConfig:
    """Shape of a :class:`TinyDecoder` (serializable for deploy).

    The six ``FIELDS`` alone describe the GPT-2 block (``model_type``
    ``"gpt2"``: learned positions, LayerNorm, ``num_heads`` equal heads
    with a K and a V page each, a GELU MLP of width ``d_ff``). With
    ``model_type="deepseek_v3"`` the ``BLOCK_FIELDS``, named after the
    published ``config.json`` keys, describe the other block: RMSNorm,
    rotary positions, multi-head latent attention over a cache of
    ``kv_lora_rank + qk_rope_head_dim`` values a token, ``d_ff`` the
    width of the SwiGLU MLP of the first ``first_k_dense_replace``
    layers, every later layer ``n_routed_experts`` routed experts
    (``num_experts_per_tok`` a token, sigmoid scores with a selection
    bias) plus ``n_shared_experts`` shared ones, each of width
    ``moe_intermediate_size``; ``torch_dtype`` is the dtype of the
    weights (``float32`` or ``bfloat16``).

    ``model_type="granitemoehybrid"`` (Granite 4.0-H; its dense members,
    ``num_local_experts`` 0) is a third block, again under the published
    keys: ``layer_types`` names each layer's mixer, ``"mamba"`` (a
    Mamba-2 state-space layer of ``mamba_n_heads`` heads of
    ``mamba_d_head``, state size ``mamba_d_state``, ``mamba_n_groups``
    1, a causal convolution ``mamba_d_conv`` wide) or ``"attention"``
    (``num_heads`` query heads over ``num_key_value_heads`` K/V heads,
    no positions of any kind, scores times ``attention_multiplier``);
    every layer then a SwiGLU MLP of width ``d_ff`` (the published
    ``shared_intermediate_size``); RMSNorm; the embedding times
    ``embedding_multiplier``, each residual branch times
    ``residual_multiplier``, the logits over ``logits_scaling``, the
    head the embedding itself (``tie_word_embeddings``).

    ``model_type="sdar_moe"`` (SDAR's expert members) is a fourth block:
    RMSNorm, ``num_heads`` query heads over ``num_key_value_heads`` K/V
    heads of ``head_dim``, each query and key head RMS-normed over its
    own values (``q_norm``, ``k_norm``) before rotary positions at
    ``rope_theta`` on the whole head; then ``num_experts`` SwiGLU experts
    of width ``moe_intermediate_size``, ``num_experts_per_tok`` a token
    by a softmax router, renormalised if ``norm_topk_prob``; an untied
    head. It GENERATES BY DIFFUSION OVER BLOCKS: attention is
    block-causal (position ``p`` sees every position below ``(p //
    block_length + 1) * block_length``), a block of ``block_length``
    positions starts as ``mask_token_id`` and is revealed over
    ``denoising_steps`` passes, ``remasking_strategy`` (one of
    ``REMASKING``) choosing which positions a pass reveals
    (``confidence_threshold`` for the dynamic one); the logits at a
    position are for that position itself. These five are generation
    defaults the published ``config.json`` lacks.

    ``model_type="gigachat3_5"`` (GigaChat 3.5) is a fifth block. Layers
    whose index is in ``full_attention_layers`` are latent attention as
    the ``deepseek_v3`` block's, with a query down-projection to
    ``q_lora_rank`` values (normed) before the heads, a sigmoid gate
    from the layer's input on the heads' outputs (``gated_attention``),
    interleaved rotary pairs under YaRN (``rope_scaling``, the softmax
    scale times the square of its ``mscale_all_dim`` factor); every other
    layer is GatedDeltaNet linear attention (``linear_num_key_heads``
    key and query heads of ``linear_key_head_dim``,
    ``linear_num_value_heads`` value heads of ``linear_value_head_dim``,
    a causal convolution ``linear_conv_kernel_dim`` wide, the delta rule
    over a slot state of ``[value heads, key size, value size]``, the
    output normed per head and gated by ``linear_sigmoid_gate_scale x
    sigmoid(z)``). Each sublayer is normed before and after (``pre_post``),
    every norm zero-centred (gains stored as their distance from 1) and
    the four sublayer norms and the final one gated per channel by
    ``layernorm_gating_weight x sigmoid(gate)``. The first
    ``first_k_dense_replace`` layers take a SwiGLU of width ``d_ff``, the
    rest ``router_experts`` routed experts by the ``deepseek_v3``
    block's sigmoid router (``num_experts_per_tok`` a token,
    ``routed_scaling_factor``) and ``n_shared_experts`` shared ones, each
    of width ``moe_intermediate_size``, every SwiGLU's products held to
    ``swiglu_limit``. Of the routed experts the model HOLDS
    ``n_routed_experts``, those from ``first_routed_expert`` on (one
    chip's share under expert parallelism): a pair routed elsewhere adds
    nothing here. The head is untied.

    ``model_type="cohere2_moe"`` (Command A+) is a sixth block, a PARALLEL
    one: attention and the expert layer both read ``u = LN(x)`` (a
    LayerNorm with a gain and no bias, ``layer_norm_eps``) and both are
    added to the residual, ``x + attn(u) + experts(u)``. ``layer_types``
    names each layer ``"sliding_attention"`` (GPT-J rotary positions on
    interleaved pairs at ``rope_theta`` on queries and keys, and a window:
    position ``p`` sees the keys ``j`` with ``p - sliding_window < j <=
    p``) or ``"full_attention"`` (no positions of any kind, causal);
    ``num_heads`` query heads over ``num_key_value_heads`` K/V heads of
    ``head_dim``. The expert layer is the ``gigachat3_5`` block's held
    share (``router_experts``, ``first_routed_expert``,
    ``n_routed_experts``) under a sigmoid router WITHOUT a selection bias
    (``num_experts_per_tok`` a token, renormalised if ``norm_topk_prob``),
    plus the MEAN of ``num_shared_experts`` shared experts, every expert a
    SwiGLU of width ``moe_intermediate_size``. The head is the embedding
    (tied), the logits times ``logit_scale``."""

    FIELDS = ("vocab_size", "d_model", "num_layers", "num_heads",
              "d_ff", "max_context")
    #: what :meth:`to_dict` holds beside ``FIELDS``, by block
    FIELDS_OF = {
        GPT2: (),
        DEEPSEEK_V3: (
            "model_type", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta",
            "rms_norm_eps", "first_k_dense_replace", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts",
            "moe_intermediate_size", "routed_scaling_factor",
            "norm_topk_prob", "torch_dtype"),
        GRANITE_HYBRID: (
            "model_type", "layer_types", "num_key_value_heads",
            "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_d_conv", "mamba_n_groups", "mamba_expand",
            "embedding_multiplier", "attention_multiplier",
            "residual_multiplier", "logits_scaling",
            "tie_word_embeddings", "rms_norm_eps", "torch_dtype"),
        SDAR_MOE: (
            "model_type", "num_key_value_heads", "head_dim", "rope_theta",
            "rms_norm_eps", "num_experts", "num_experts_per_tok",
            "moe_intermediate_size", "norm_topk_prob", "torch_dtype",
            "block_length", "mask_token_id", "denoising_steps",
            "remasking_strategy", "confidence_threshold"),
        GIGACHAT3_5: (
            "model_type", "full_attention_layers", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "rope_scaling", "rms_norm_eps",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_sigmoid_gate_scale",
            "layernorm_gating_weight", "first_k_dense_replace",
            "router_experts", "first_routed_expert", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts",
            "moe_intermediate_size", "routed_scaling_factor",
            "norm_topk_prob", "swiglu_limit", "torch_dtype"),
        COHERE2_MOE: (
            "model_type", "layer_types", "sliding_window",
            "num_key_value_heads", "head_dim", "rope_theta",
            "layer_norm_eps", "router_experts", "first_routed_expert",
            "n_routed_experts", "num_experts_per_tok", "num_shared_experts",
            "moe_intermediate_size", "norm_topk_prob", "logit_scale",
            "torch_dtype"),
    }
    #: every block's own fields, each once
    BLOCK_FIELDS = tuple(dict.fromkeys(
        f for fields in FIELDS_OF.values() for f in fields))

    def __init__(self, vocab_size=32, d_model=32, num_layers=2,
                 num_heads=2, d_ff=64, max_context=128, model_type=GPT2,
                 kv_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=0,
                 v_head_dim=0, rope_theta=10000.0, rms_norm_eps=1e-5,
                 first_k_dense_replace=0, n_routed_experts=0,
                 num_experts_per_tok=0, n_shared_experts=0,
                 moe_intermediate_size=0, routed_scaling_factor=1.0,
                 norm_topk_prob=True, torch_dtype="float32",
                 layer_types=(), num_key_value_heads=0, mamba_n_heads=0,
                 mamba_d_head=0, mamba_d_state=0, mamba_d_conv=4,
                 mamba_n_groups=1, mamba_expand=2,
                 embedding_multiplier=1.0, attention_multiplier=1.0,
                 residual_multiplier=1.0, logits_scaling=1.0,
                 tie_word_embeddings=True, head_dim=0, num_experts=0,
                 block_length=4, mask_token_id=0, denoising_steps=4,
                 remasking_strategy="sequential",
                 confidence_threshold=0.9, full_attention_layers=(),
                 q_lora_rank=0, rope_scaling=None, linear_num_key_heads=0,
                 linear_num_value_heads=0, linear_key_head_dim=0,
                 linear_value_head_dim=0, linear_conv_kernel_dim=4,
                 linear_sigmoid_gate_scale=2.0, layernorm_gating_weight=2.0,
                 router_experts=0, first_routed_expert=0,
                 swiglu_limit=None, sliding_window=0, layer_norm_eps=1e-5,
                 num_shared_experts=0, logit_scale=1.0):
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.d_ff = int(d_ff)
        self.max_context = int(max_context)
        for f in self.FIELDS:
            if getattr(self, f) < 1:
                raise ValueError(f"{f} must be >= 1, got {getattr(self, f)}")
        if model_type not in self.FIELDS_OF:
            raise ValueError(f"model_type {model_type!r} is none of "
                             f"{sorted(self.FIELDS_OF)}")
        self.model_type = model_type
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.n_routed_experts = int(n_routed_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.n_shared_experts = int(n_shared_experts)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        # the dtype the weights are published in, and made in by
        # init_params; the step reads them as they come
        if torch_dtype not in ("float32", "bfloat16"):
            raise ValueError("torch_dtype is float32 or bfloat16, got "
                             f"{torch_dtype!r}")
        self.torch_dtype = torch_dtype
        self.layer_types = tuple(layer_types)
        self.num_key_value_heads = int(num_key_value_heads)
        self.mamba_n_heads = int(mamba_n_heads)
        self.mamba_d_head = int(mamba_d_head)
        self.mamba_d_state = int(mamba_d_state)
        self.mamba_d_conv = int(mamba_d_conv)
        self.mamba_n_groups = int(mamba_n_groups)
        self.mamba_expand = int(mamba_expand)
        self.embedding_multiplier = float(embedding_multiplier)
        self.attention_multiplier = float(attention_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.tie_word_embeddings = bool(tie_word_embeddings)
        self.num_experts = int(num_experts)
        self.block_length = int(block_length)
        self.mask_token_id = int(mask_token_id)
        self.denoising_steps = int(denoising_steps)
        self.remasking_strategy = remasking_strategy
        self.confidence_threshold = float(confidence_threshold)
        self.full_attention_layers = tuple(int(i)
                                           for i in full_attention_layers)
        self.q_lora_rank = int(q_lora_rank)
        self.rope_scaling = None if rope_scaling is None \
            else dict(rope_scaling)
        self.linear_num_key_heads = int(linear_num_key_heads)
        self.linear_num_value_heads = int(linear_num_value_heads)
        self.linear_key_head_dim = int(linear_key_head_dim)
        self.linear_value_head_dim = int(linear_value_head_dim)
        self.linear_conv_kernel_dim = int(linear_conv_kernel_dim)
        self.linear_sigmoid_gate_scale = float(linear_sigmoid_gate_scale)
        self.layernorm_gating_weight = float(layernorm_gating_weight)
        # the router's experts, of which n_routed_experts are held
        self.router_experts = int(router_experts) or self.n_routed_experts
        self.first_routed_expert = int(first_routed_expert)
        self.swiglu_limit = None if swiglu_limit is None \
            else float(swiglu_limit)
        self.sliding_window = int(sliding_window)
        self.layer_norm_eps = float(layer_norm_eps)
        self.num_shared_experts = int(num_shared_experts)
        self.logit_scale = float(logit_scale)
        if model_type == COHERE2_MOE:
            self.head_dim = int(head_dim)
            self._check_cohere2()
            return
        if model_type == GIGACHAT3_5:
            self._check_gigachat()
            self.head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
            return
        if model_type == SDAR_MOE:
            self.head_dim = int(head_dim)
            self._check_sdar()
            return
        if model_type == GRANITE_HYBRID:
            self._check_hybrid()
            self.head_dim = self.d_model // self.num_heads
            return
        if model_type == GPT2:
            if self.d_model % self.num_heads:
                raise ValueError(
                    f"d_model {d_model} not divisible by heads "
                    f"{num_heads}")
            self.head_dim = self.d_model // self.num_heads
            return
        for f in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim"):
            if getattr(self, f) < 1:
                raise ValueError(f"{f} must be >= 1 for {DEEPSEEK_V3}, "
                                 f"got {getattr(self, f)}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even, got "
                             f"{self.qk_rope_head_dim}")
        if self.first_k_dense_replace < self.num_layers:
            if not (1 <= self.num_experts_per_tok
                    <= self.n_routed_experts):
                raise ValueError(
                    f"num_experts_per_tok {self.num_experts_per_tok} of "
                    f"n_routed_experts {self.n_routed_experts}")
            if self.moe_intermediate_size < 1 or self.n_shared_experts < 0:
                raise ValueError("expert layers need "
                                 "moe_intermediate_size >= 1 and "
                                 "n_shared_experts >= 0")
        # a head's query width (nope + rope)
        self.head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim

    def _check_hybrid(self):
        """The ``granitemoehybrid`` keys against one another."""
        if len(self.layer_types) != self.num_layers or any(
                k not in (MAMBA, ATTENTION) for k in self.layer_types):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of "
                f"{sorted(set(self.layer_types))}; it takes "
                f"{self.num_layers} of {MAMBA!r} or {ATTENTION!r}")
        if self.d_model % self.num_heads or self.num_key_value_heads < 1 \
                or self.num_heads % self.num_key_value_heads:
            raise ValueError(
                f"d_model {self.d_model} over num_heads {self.num_heads} "
                f"over num_key_value_heads {self.num_key_value_heads}: "
                "each must divide the one before")
        if MAMBA in self.layer_types:
            for f in ("mamba_n_heads", "mamba_d_head", "mamba_d_state"):
                if getattr(self, f) < 1:
                    raise ValueError(f"{f} must be >= 1 for a {MAMBA} "
                                     f"layer, got {getattr(self, f)}")
            if self.mamba_n_groups != 1:
                raise ValueError("mamba_n_groups is 1 (all heads share B "
                                 f"and C), got {self.mamba_n_groups}")
            if self.mamba_d_conv < 2:
                raise ValueError("mamba_d_conv must be >= 2, got "
                                 f"{self.mamba_d_conv}")
            if self.mamba_expand * self.d_model != self.mamba_inner:
                raise ValueError(
                    f"mamba_expand {self.mamba_expand} x d_model "
                    f"{self.d_model} is not mamba_n_heads x mamba_d_head "
                    f"= {self.mamba_inner}")
        if not self.tie_word_embeddings:
            raise ValueError("the granitemoehybrid block's head is its "
                             "embedding (tie_word_embeddings)")

    def _check_sdar(self):
        """The ``sdar_moe`` keys against one another."""
        if self.head_dim < 2 or self.head_dim % 2:
            raise ValueError("head_dim must be even and >= 2 for "
                             f"{SDAR_MOE}, got {self.head_dim}")
        if self.num_key_value_heads < 1 \
                or self.num_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_heads {self.num_heads} over num_key_value_heads "
                f"{self.num_key_value_heads}: the second must divide the "
                "first")
        if not (1 <= self.num_experts_per_tok <= self.num_experts) \
                or self.moe_intermediate_size < 1:
            raise ValueError(
                f"num_experts_per_tok {self.num_experts_per_tok} of "
                f"num_experts {self.num_experts}, each of width "
                f"moe_intermediate_size {self.moe_intermediate_size}")
        if not (1 <= self.denoising_steps <= self.block_length):
            raise ValueError(
                f"denoising_steps {self.denoising_steps} must lie in 1 to "
                f"block_length {self.block_length}: a pass reveals at "
                "least one position")
        if self.max_context % self.block_length:
            raise ValueError(
                f"max_context {self.max_context} must be a multiple of "
                f"block_length {self.block_length}")
        if not (0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(f"mask_token_id {self.mask_token_id} is "
                             f"outside the vocabulary of {self.vocab_size}")
        if self.remasking_strategy not in REMASKING:
            raise ValueError(
                f"remasking_strategy {self.remasking_strategy!r} is none "
                f"of {REMASKING}")

    def _check_gigachat(self):
        """The ``gigachat3_5`` keys against one another."""
        for f in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "linear_num_key_heads",
                  "linear_num_value_heads", "linear_key_head_dim",
                  "linear_value_head_dim", "moe_intermediate_size"):
            if getattr(self, f) < 1:
                raise ValueError(f"{f} must be >= 1 for {GIGACHAT3_5}, "
                                 f"got {getattr(self, f)}")
        full = self.full_attention_layers
        if not full or len(set(full)) != len(full) or not all(
                0 <= i < self.num_layers for i in full):
            raise ValueError(
                f"full_attention_layers {list(full)} must name one or more "
                f"distinct layers of {self.num_layers}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even, got "
                             f"{self.qk_rope_head_dim}")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"linear_num_value_heads {self.linear_num_value_heads} "
                f"over linear_num_key_heads {self.linear_num_key_heads}: "
                "the second must divide the first")
        if self.linear_conv_kernel_dim < 2:
            raise ValueError("linear_conv_kernel_dim must be >= 2, got "
                             f"{self.linear_conv_kernel_dim}")
        if not (1 <= self.num_experts_per_tok <= self.router_experts) \
                or self.n_routed_experts < 1 or not (
                    0 <= self.first_routed_expert <= self.router_experts
                    - self.n_routed_experts):
            raise ValueError(
                f"n_routed_experts {self.n_routed_experts} held from "
                f"first_routed_expert {self.first_routed_expert} of "
                f"router_experts {self.router_experts}, "
                f"num_experts_per_tok {self.num_experts_per_tok}")

    def _check_cohere2(self):
        """The ``cohere2_moe`` keys against one another."""
        if self.head_dim < 2 or self.head_dim % 2:
            raise ValueError("head_dim must be even and >= 2 for "
                             f"{COHERE2_MOE}, got {self.head_dim}")
        if self.num_key_value_heads < 1 \
                or self.num_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_heads {self.num_heads} over num_key_value_heads "
                f"{self.num_key_value_heads}: the second must divide the "
                "first")
        if len(self.layer_types) != self.num_layers or any(
                k not in (SLIDING, FULL) for k in self.layer_types):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of "
                f"{sorted(set(self.layer_types))}; it takes "
                f"{self.num_layers} of {SLIDING!r} or {FULL!r}")
        if SLIDING in self.layer_types and self.sliding_window < 1:
            raise ValueError("a sliding_attention layer needs "
                             f"sliding_window >= 1, got {self.sliding_window}")
        if not (1 <= self.num_experts_per_tok <= self.router_experts) \
                or self.n_routed_experts < 1 \
                or self.moe_intermediate_size < 1 \
                or self.num_shared_experts < 0 or not (
                    0 <= self.first_routed_expert <= self.router_experts
                    - self.n_routed_experts):
            raise ValueError(
                f"n_routed_experts {self.n_routed_experts} held from "
                f"first_routed_expert {self.first_routed_expert} of "
                f"router_experts {self.router_experts}, "
                f"num_experts_per_tok {self.num_experts_per_tok}, "
                f"moe_intermediate_size {self.moe_intermediate_size}, "
                f"num_shared_experts {self.num_shared_experts}")

    @property
    def window_layers(self):
        """The sliding-window layers of a ``cohere2_moe`` model, by
        index."""
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == SLIDING)

    @property
    def linear_layers(self):
        """The GatedDeltaNet layers of a ``gigachat3_5`` model, by
        index."""
        return tuple(i for i in range(self.num_layers)
                     if i not in self.full_attention_layers)

    @property
    def linear_conv_dim(self):
        """Channels a GatedDeltaNet layer's convolution runs over: its
        queries, keys and values."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def mamba_inner(self):
        """Channels of a state-space layer's inner stream (heads x head
        size)."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self):
        """Channels the causal convolution runs over: the inner stream,
        then ``B`` and ``C`` of the one group."""
        return self.mamba_inner + 2 * self.mamba_d_state

    @property
    def latent_dim(self):
        """Values a token leaves in the cache of a layer of the
        ``deepseek_v3`` block: the compressed part and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def to_dict(self):
        out = {f: getattr(self, f)
               for f in self.FIELDS + self.FIELDS_OF[self.model_type]}
        for f in ("layer_types", "full_attention_layers"):
            if f in out:
                out[f] = list(out[f])                       # as JSON has it
        return out

    @classmethod
    def from_dict(cls, d):
        return cls(**{f: d[f] for f in cls.FIELDS + cls.BLOCK_FIELDS
                      if f in d})

    def __repr__(self):
        return ("DecoderConfig(" + ", ".join(
            f"{f}={v!r}" if isinstance(v, str) else f"{f}={v}"
            for f, v in self.to_dict().items()) + ")")


def _layer_norm(x, g, b, eps=1e-5):
    m = x.mean(axis=-1, keepdims=True)
    v = ((x - m) ** 2).mean(axis=-1, keepdims=True)
    import jax.numpy as jnp
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _rms_norm(x, g, eps):
    """RMSNorm in float32, whatever ``x`` and ``g`` are stored as."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, positions, theta, freqs=None):
    """Rotary positions on the last axis of ``x [..., T, (heads,) r]``
    (float32), ``positions`` shaped like ``x`` up to and including its
    token axis. Half-split pairing: element ``i`` turns with element
    ``i + r/2`` (the published checkpoints store the rotary part
    interleaved and the published code permutes it into this order;
    with seeded weights the two are one fixed relabelling). ``freqs``:
    the ``r/2`` inverse frequencies, where they are not ``theta``'s
    plain ones (YaRN's)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    if freqs is None:
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    ang = ang.reshape(positions.shape
                      + (1,) * (x.ndim - positions.ndim - 1) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _rope_interleaved(x, positions, freqs):
    """Rotary positions on interleaved pairs (``rope_interleave``):
    element ``2i`` turns with element ``2i + 1`` at ``freqs[i]``. The
    pairs are gathered to the half-split order (evens, then odds) and
    turned there, as the published code does; queries and keys are
    gathered alike, so their products are those of the pairs."""
    r = x.shape[-1]
    split = x.reshape(x.shape[:-1] + (r // 2, 2)).swapaxes(-1, -2)
    return _rope(split.reshape(x.shape), positions, None, freqs)


def yarn_mscale(scale, mscale=1.0):
    """YaRN's attention factor for a context stretched ``scale`` times:
    ``0.1 mscale ln(scale) + 1`` (1 where nothing is stretched)."""
    import math
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dim, base, scaling):
    """YaRN's inverse frequencies for a rotary part of ``dim`` values
    (DeepSeek-V3's reading, as numpy float32 ``[dim / 2]``): the plain
    ones of ``base`` for the pairs that turn more than ``beta_fast``
    times over ``original_max_position_embeddings``, those over
    ``factor`` for the pairs that turn fewer than ``beta_slow`` times,
    and a linear ramp between."""
    import math
    half = dim // 2
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    orig = scaling["original_max_position_embeddings"]

    def at(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(at(scaling["beta_fast"])), 0)
    high = min(math.ceil(at(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 0.001), 0, 1)
    keep = 1.0 - ramp
    return (plain / scaling["factor"] * (1 - keep) + plain * keep).astype(
        np.float32)


def _zc_norm(x, g, eps, gate=None, gating_weight=2.0):
    """Zero-centred RMSNorm in float32: the gain is ``1 + g``; with
    ``gate``, each channel also times ``gating_weight x sigmoid(gate)``
    (``ZeroCenteredGatedNorm``)."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    out = x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) \
        * (1.0 + g.astype(jnp.float32))
    if gate is None:
        return out
    return out * (gating_weight * jax.nn.sigmoid(gate.astype(jnp.float32)))


def _l2_norm(x, eps=1e-6):
    """Each row over its last axis to length 1 (float32)."""
    import jax
    return x * jax.lax.rsqrt((x * x).sum(axis=-1, keepdims=True) + eps)


def _wmm(x, w):
    """``x @ w`` with the activations rounded to the weights' dtype and
    float32 accumulation: a bf16 weight is read as bf16, and no float32
    copy of it is made."""
    import jax.numpy as jnp
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _swiglu(x, w_gate, w_up, w_down, limit=None):
    """``(silu(x Wg) * (x Wu)) Wd``; with ``limit``, ``x Wg`` capped at
    it and ``x Wu`` clipped to ``[-limit, limit]`` first."""
    import jax
    import jax.numpy as jnp
    if limit is None:
        return _wmm(jax.nn.silu(_wmm(x, w_gate)) * _wmm(x, w_up), w_down)
    g = jnp.minimum(_wmm(x, w_gate), limit)
    return _wmm(jax.nn.silu(g) * jnp.clip(_wmm(x, w_up), -limit, limit),
                w_down)


def _conv_windows(seq_ids, valid, positions, slots, K):
    """Where a causal convolution ``K`` wide over the packed step finds
    each token's ``K - 1`` predecessors, for every layer alike: ``(taps,
    keeps)``, row indices into ``[the step's inputs [T] | every slot's
    window [S x (K - 1)] | one row of zeros]``. ``taps`` (oldest first)
    gives each token's predecessors from its run or, for the run's first
    tokens, from its slot's window (zeros for a run that starts at
    position 0); ``keeps [S, K - 1]`` is what each slot's window holds
    after the step: the last ``K - 1`` inputs of window and run together
    (a run shorter than the window shifts it through)."""
    import jax.numpy as jnp
    T, S = seq_ids.shape[0], slots
    start, length = slot_runs(seq_ids, valid, S)
    fresh = jnp.logical_and(length > 0, positions[start] == 0)
    t = jnp.arange(T, dtype=jnp.int32)
    place = t - start[seq_ids]                  # in its slot's run
    zero_row = T + S * (K - 1)
    taps = []                                   # oldest first
    for back in range(K - 1, 0, -1):
        held = T + seq_ids * (K - 1) + (K - 1 - back) + place
        taps.append(jnp.clip(jnp.where(
            place >= back, t - back,
            jnp.where(fresh[seq_ids], zero_row, held)), 0, zero_row))
    j = length[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
    s_row = jnp.arange(S, dtype=jnp.int32)[:, None]
    keeps = jnp.where(
        j >= K - 1, start[:, None] + j - (K - 1),
        jnp.where(fresh[:, None], zero_row,
                  T + s_row * (K - 1) + j))
    return taps, keeps


def _causal_conv(x, window, conv_w, taps, keeps):
    """A depthwise causal convolution of one layer over the packed step:
    ``x [T, C]`` the step's inputs, ``window [S, K - 1, C]`` every slot's
    last inputs, ``conv_w [C, K]``; ``(the sums [T, C] float32, every
    slot's window after the step)`` by :func:`_conv_windows`'s rows."""
    import jax.numpy as jnp
    K = conv_w.shape[1]
    rows = jnp.concatenate(
        [x, window.reshape(-1, x.shape[1]),
         jnp.zeros((1, x.shape[1]), jnp.float32)])
    w = conv_w.astype(jnp.float32)
    conved = w[:, K - 1] * x + sum(
        w[:, i] * rows[tap] for i, tap in enumerate(taps))
    return conved, rows[keeps]


class TinyDecoder:
    """Decoder-only transformer with paged-decode support."""

    #: each block's own methods end in its name here
    #: (``_forward_<name>``, ``_decode_flat_<name>``, ...), and
    #: ``_TAKES`` is what its step takes beside the packed batch
    _BLOCK = {GPT2: "gpt2", DEEPSEEK_V3: "latent", GRANITE_HYBRID: "hybrid",
              SDAR_MOE: "blockdiff", GIGACHAT3_5: "gdn",
              COHERE2_MOE: "window"}
    _TAKES = {GPT2: ("k_scales", "v_scales", "adapter", "axis_name",
                     "w_scales"),
              DEEPSEEK_V3: (), GRANITE_HYBRID: ("state",), SDAR_MOE: (),
              GIGACHAT3_5: ("state",), COHERE2_MOE: ()}
    #: what each of those keywords stands for, when a block refuses it
    _STANDS_FOR = {"k_scales": "quantized pages",
                   "v_scales": "quantized pages",
                   "adapter": "an adapter bank",
                   "axis_name": "a tensor-parallel mesh",
                   "w_scales": "quantized weights",
                   "state": "slot state pools"}
    #: the engine arguments a block does not take, refused by name at
    #: the engine's construction
    _REFUSED = {GPT2: (),
                DEEPSEEK_V3: ("adapter_bank", "draft_model", "mesh",
                              "weight_dtype"),
                GRANITE_HYBRID: ("adapter_bank", "draft_model", "mesh",
                                 "weight_dtype", "kv_dtype",
                                 "prefix_cache"),
                SDAR_MOE: ("adapter_bank", "draft_model", "mesh",
                           "weight_dtype", "kv_dtype", "prefix_cache",
                           "sampling"),
                GIGACHAT3_5: ("adapter_bank", "draft_model", "mesh",
                              "weight_dtype", "kv_dtype", "prefix_cache"),
                COHERE2_MOE: ("adapter_bank", "draft_model", "mesh",
                              "weight_dtype", "kv_dtype", "prefix_cache")}

    def __init__(self, config=None, **kw):
        self.config = config if config is not None else DecoderConfig(**kw)

    def _of_block(self, what):
        """The configured block's own ``what``: the one place a model
        type is looked up."""
        return getattr(self, f"_{what}_{self._BLOCK[self.config.model_type]}")

    # engine-facing shape attributes
    @property
    def num_layers(self):
        return self.config.num_layers

    @property
    def num_heads(self):
        return self.config.num_heads

    @property
    def head_dim(self):
        return self.config.head_dim

    @property
    def vocab_size(self):
        return self.config.vocab_size

    @property
    def max_context(self):
        return self.config.max_context

    @property
    def _deepseek(self):
        return self.config.model_type == DEEPSEEK_V3

    @property
    def expert_slots(self):
        """Routed experts held over all expert layers (0 for a model
        without them): what a step's experts hit are a share of. A
        model with any returns their load from :meth:`decode_flat`,
        after the pools."""
        c = self.config
        if c.model_type == SDAR_MOE:
            return c.num_experts * c.num_layers
        # (held experts: a gigachat3_5 model holds a share of its router's)
        return c.n_routed_experts * max(
            0, c.num_layers - c.first_k_dense_replace)

    @property
    def block_diffusion(self):
        """How the model generates, where it generates by diffusion
        over blocks (``None`` for a model that decodes a token a step):
        ``{"block_length", "mask_token_id", "denoising_steps",
        "remasking_strategy", "confidence_threshold"}``, read by the
        engine (its model protocol says what it does with them)."""
        c = self.config
        if c.model_type != SDAR_MOE:
            return None
        return {"block_length": c.block_length,
                "mask_token_id": c.mask_token_id,
                "denoising_steps": c.denoising_steps,
                "remasking_strategy": c.remasking_strategy,
                "confidence_threshold": c.confidence_threshold}

    @property
    def cache_layout(self):
        """What the model keeps between steps, for the engine to shape
        the pools by. What a token leaves in the paged cache of one
        layer: ``{"kind": "kv", "num_heads": H, "head_dim": D}`` (a K
        and a V pool of ``[H, D]`` a slot), ``{"kind": "latent",
        "latent_dim": W}`` (one pool of ``W`` values a slot, no head
        axis, no V pool) or ``{"kind": "kv_packed", "num_heads": G,
        "head_dim": D}`` (one pool of rows, a token's ``G`` value heads
        and then its ``G`` key heads: whole lane tiles at any head
        size). ``"layers"``, where fewer layers than the model has leave
        pages. ``"window_layers"`` (with ``"window"``, ``W``), where some
        layers attend through a sliding window: a position of those
        layers sees the ``W`` keys up to itself, so a sequence need hold
        their pages from its window's first position on, while every
        other layer keeps all of its pages. ``"state"``, where a sequence
        also holds a fixed state that its tokens overwrite: ``{name:
        (layers, *shape of one slot's)}`` float32, in the order the step
        takes and returns the pools, after the pages."""
        c = self.config
        if c.model_type == SDAR_MOE:
            return {"kind": "kv_packed",
                    "num_heads": c.num_key_value_heads,
                    "head_dim": c.head_dim}
        if c.model_type == COHERE2_MOE:
            layout = {"kind": "kv_packed",
                      "num_heads": c.num_key_value_heads,
                      "head_dim": c.head_dim}
            if c.window_layers:
                layout.update(window_layers=list(c.window_layers),
                              window=c.sliding_window)
            return layout
        if c.model_type == GIGACHAT3_5:
            linear = len(c.linear_layers)
            layout = {"kind": "latent", "latent_dim": c.latent_dim,
                      "layers": len(c.full_attention_layers)}
            if linear:
                layout["state"] = {
                    "gdn": (linear, c.linear_num_value_heads,
                            c.linear_key_head_dim, c.linear_value_head_dim),
                    "conv": (linear, c.linear_conv_kernel_dim - 1,
                             c.linear_conv_dim)}
            return layout
        if c.model_type == GRANITE_HYBRID:
            mamba = c.layer_types.count(MAMBA)
            layout = {"kind": "kv_packed",
                      "num_heads": c.num_key_value_heads,
                      "head_dim": c.head_dim,
                      "layers": c.layer_types.count(ATTENTION)}
            if mamba:
                layout["state"] = {
                    "ssm": (mamba, c.mamba_d_state, c.mamba_inner),
                    "conv": (mamba, c.mamba_d_conv - 1, c.mamba_conv_dim)}
            return layout
        if self._deepseek:
            return {"kind": "latent", "latent_dim": c.latent_dim}
        return {"kind": "kv", "num_heads": c.num_heads,
                "head_dim": c.head_dim}

    @property
    def refused_features(self):
        """The engine arguments this model's block does not take, for
        the engine to refuse by name at construction."""
        return self._REFUSED[self.config.model_type]

    def _refuse(self, what):
        raise UnsupportedModelFeatureError(
            f"the {self.config.model_type} block does not take {what}")

    # ------------------------------------------------------- params --
    def init_params(self, seed=0):
        """Deterministic random params from ``seed``: host numpy
        float32 draws, in the configuration's ``torch_dtype`` where the
        block has one."""
        rs = np.random.RandomState(seed)

        def w(*shape, scale=None):
            scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
            return (rs.randn(*shape) * scale).astype(np.float32)

        return self._of_block("init_params")(w)

    def _init_params_gdn(self, w):
        """The ``gigachat3_5`` tree: the leaves of
        ``benchmark/reference/gigachat35_decoder.py``, and its rule of
        scale (``WEIGHT_RULE`` there says why each): every matrix by its
        fan-in (the router and the experts too: at larger scales the
        routing swaps experts at near ties under bfloat16 so often that
        what a sound run serves moves as far as a fault does), the
        embedding 1; the norms' gains (their distance from 1) normal at
        0.1 and their gates at 0.5; the router's bias normal at 0.01; of
        a GatedDeltaNet layer, ``A_log`` the logarithms of 1/16 to 1 and
        ``dt_bias`` the inverse softplus of 0.001 to 0.1 (geometric)."""
        c = self.config
        d, H, qr, rank = c.d_model, c.num_heads, c.q_lora_rank, \
            c.kv_lora_rank
        nope, rope, vd = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                          c.v_head_dim)
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dvl = c.linear_key_head_dim, c.linear_value_head_dim
        K, C = c.linear_conv_kernel_dim, c.linear_conv_dim
        E, m = c.n_routed_experts, c.moe_intermediate_size
        sh = c.n_shared_experts * m
        steps = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), hv))

        def gains(*names, n=d):
            out = {}
            for name in names:
                out[name] = w(n, scale=0.1)
                out[name + "_gate"] = w(n, scale=0.5)
            return out
        layers = []
        for li in range(c.num_layers):
            lp = gains("norm1", "norm2", "norm3", "norm4")
            if li in c.full_attention_layers:
                lp.update(wq_a=w(d, qr), norm_q=w(qr, scale=0.1),
                          wq_b=w(qr, H * (nope + rope)),
                          wkv_a=w(d, rank + rope),
                          norm_kv=w(rank, scale=0.1),
                          wkv_b=w(rank, H * (nope + vd)),
                          w_gate_attn=w(d, H * vd), wo=w(H * vd, d))
            else:
                lp.update(
                    wq=w(d, hk * dk), wk=w(d, hk * dk), wv=w(d, hv * dvl),
                    wz=w(d, hv * dvl), wa=w(d, hv), wb=w(d, hv),
                    conv_w=w(C, K, scale=K ** -0.5),
                    A_log=np.log(np.linspace(1.0 / 16, 1.0, hv)).astype(
                        np.float32),
                    dt_bias=np.log(np.expm1(steps)).astype(np.float32),
                    norm_o=w(dvl, scale=0.1), wo=w(hv * dvl, d))
            if li < c.first_k_dense_replace:
                lp.update(w_gate=w(d, c.d_ff), w_up=w(d, c.d_ff),
                          w_down=w(c.d_ff, d))
            else:
                lp.update(
                    router=w(d, c.router_experts, scale=d ** -0.5),
                    router_bias=w(c.router_experts, scale=0.01),
                    experts_gate=w(E, d, m, scale=d ** -0.5),
                    experts_up=w(E, d, m, scale=d ** -0.5),
                    experts_down=w(E, m, d, scale=m ** -0.5),
                    shared_gate=w(d, sh), shared_up=w(d, sh),
                    shared_down=w(sh, d))
            layers.append(lp)
        return self._published_dtype(
            {"embed": w(c.vocab_size, d, scale=1.0),
             **gains("norm_f"), "head": w(d, c.vocab_size),
             "layers": layers})

    def _published_dtype(self, params):
        """``params`` (host float32) in the configuration's
        ``torch_dtype``; a router's leaves stay float32, as the
        published code computes its scores."""
        if self.config.torch_dtype == "float32":
            return params
        import jax
        import jax.numpy as jnp
        return jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.asarray(a) if "router" in str(path[-1])
            else jnp.asarray(a, self.config.torch_dtype), params)

    def _init_params_gpt2(self, w):
        c = self.config
        layers = []
        for _ in range(c.num_layers):
            layers.append({
                "ln1_g": np.ones(c.d_model, np.float32),
                "ln1_b": np.zeros(c.d_model, np.float32),
                "wq": w(c.d_model, c.d_model),
                "wk": w(c.d_model, c.d_model),
                "wv": w(c.d_model, c.d_model),
                "wo": w(c.d_model, c.d_model),
                "ln2_g": np.ones(c.d_model, np.float32),
                "ln2_b": np.zeros(c.d_model, np.float32),
                "w1": w(c.d_model, c.d_ff),
                "b1": np.zeros(c.d_ff, np.float32),
                "w2": w(c.d_ff, c.d_model),
                "b2": np.zeros(c.d_model, np.float32),
            })
        return {
            "embed": w(c.vocab_size, c.d_model, scale=0.5),
            "pos": w(c.max_context, c.d_model, scale=0.1),
            "lnf_g": np.ones(c.d_model, np.float32),
            "lnf_b": np.zeros(c.d_model, np.float32),
            "head": w(c.d_model, c.vocab_size),
            "layers": layers,
        }

    def _init_params_latent(self, w):
        """The ``deepseek_v3`` tree: the leaves of
        ``benchmark/reference/deepseek_v3_decoder.py``, and its one
        rule of scale: fan-in, and the matrices that write to the
        residual stream (``wo``, ``w_down``, ``experts_down``,
        ``shared_down``) by 1/sqrt(2 x num_layers) besides (GPT-2's
        residual scaling, at the model's own depth); the embedding 1."""
        c = self.config
        d, H, rank = c.d_model, c.num_heads, c.kv_lora_rank
        nope, rope, vd = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                          c.v_head_dim)
        E, m = c.n_routed_experts, c.moe_intermediate_size
        sh = c.n_shared_experts * m
        out = (2 * c.num_layers) ** -0.5
        layers = []
        for li in range(c.num_layers):
            lp = {"norm1": np.ones(d, np.float32),
                  "norm2": np.ones(d, np.float32),
                  "norm_kv": np.ones(rank, np.float32),
                  "wq": w(d, H * (nope + rope)),
                  "wkv_a": w(d, rank + rope),
                  "wkv_b": w(rank, H * (nope + vd)),
                  "wo": w(H * vd, d, scale=out * (H * vd) ** -0.5)}
            if li < c.first_k_dense_replace:
                lp.update(w_gate=w(d, c.d_ff), w_up=w(d, c.d_ff),
                          w_down=w(c.d_ff, d, scale=out * c.d_ff ** -0.5))
            else:
                lp.update(
                    router=w(d, E), router_bias=w(E, scale=0.01),
                    experts_gate=w(E, d, m, scale=d ** -0.5),
                    experts_up=w(E, d, m, scale=d ** -0.5),
                    experts_down=w(E, m, d, scale=out * m ** -0.5))
                if sh:
                    lp.update(shared_gate=w(d, sh), shared_up=w(d, sh),
                              shared_down=w(sh, d,
                                            scale=out * sh ** -0.5))
            layers.append(lp)
        return self._published_dtype(
            {"embed": w(c.vocab_size, d, scale=1.0),
             "norm_f": np.ones(d, np.float32),
             "head": w(d, c.vocab_size), "layers": layers})

    def param_specs(self, axis="tp"):
        """PartitionSpec pytree matching :meth:`init_params` for
        tensor-parallel placement over mesh axis ``axis`` — the
        Megatron split: ``wq/wk/wv`` column-parallel (output heads),
        ``wo`` row-parallel (psum after), ``w1``/``b1``
        column-parallel, ``w2`` row-parallel (psum after, ``b2``
        replicated so it is added once). Everything position-,
        vocab- or norm-shaped rides replicated. Structure is a tree
        PREFIX of the params pytree (one spec per weight leaf)."""
        from jax.sharding import PartitionSpec as P
        if self.config.model_type != GPT2:
            self._refuse("a tensor-parallel mesh")
        layer = {
            "ln1_g": P(), "ln1_b": P(),
            "wq": P(None, axis), "wk": P(None, axis),
            "wv": P(None, axis), "wo": P(axis, None),
            "ln2_g": P(), "ln2_b": P(),
            "w1": P(None, axis), "b1": P(axis),
            "w2": P(axis, None), "b2": P(),
        }
        return {
            "embed": P(), "pos": P(), "lnf_g": P(), "lnf_b": P(),
            "head": P(),
            "layers": [dict(layer) for _ in range(self.config.num_layers)],
        }

    def weight_scale_specs(self, axis="tp"):
        """PartitionSpecs for the flat per-channel weight-scale dict
        (``serving.llm.quant.QuantizedWeights.scales``): each scale
        vector shards with its weight's OUTPUT axis — column-parallel
        matrices (``wq/wk/wv/w1``) carry axis-sharded scales, row-
        parallel ones (``wo/w2``) replicate their full-width output
        scale (the per-column factor commutes with the psum), and the
        replicated embedding/position/head scales ride replicated."""
        from jax.sharding import PartitionSpec as P
        specs = {"embed": P(), "pos": P(), "head": P()}
        for li in range(self.config.num_layers):
            for n, s in (("wq", P(axis)), ("wk", P(axis)),
                         ("wv", P(axis)), ("wo", P()),
                         ("w1", P(axis)), ("w2", P())):
                specs[f"layers.{li}.{n}"] = s
        return specs

    # ------------------------------------------------------ prefill --
    def forward(self, params, tokens, lora=None):
        """Dense causal forward. tokens: int32 [B, T] (T <=
        max_context). Returns (logits [B, T, V], k, v) with k/v
        [L, B, T, H, Dh] — the KV the prefill path writes into pages.

        ``lora``: optional single-adapter factors ``(a_sel, b_sel,
        scale)`` as returned by ``AdapterBank.adapter_arrays`` —
        applied to every row (the per-adapter oracle of the flat
        step's per-token dispatch).
        """
        if lora is None:
            return self._of_block("forward")(params, tokens)
        if self.config.model_type != GPT2:
            self._refuse("LoRA factors")
        return self._forward_gpt2(params, tokens, lora)

    def _forward_gpt2(self, params, tokens, lora=None):
        import jax
        import jax.numpy as jnp
        c = self.config
        B, T = tokens.shape
        h = params["embed"][tokens] + params["pos"][:T][None, :, :]
        if lora is not None:
            la, lb, lscale = (jnp.asarray(lora[0]), jnp.asarray(lora[1]),
                              lora[2])
        ks, vs = [], []
        for li, lp in enumerate(params["layers"]):
            x = _layer_norm(h, lp["ln1_g"], lp["ln1_b"])
            q = x @ lp["wq"]
            k = x @ lp["wk"]
            v = x @ lp["wv"]
            if lora is not None:
                x2d = x.reshape(B * T, c.d_model)
                q = q + _lora_all_rows(x2d, la, lb, li, PROJ_Q,
                                       lscale).reshape(B, T, c.d_model)
                k = k + _lora_all_rows(x2d, la, lb, li, PROJ_K,
                                       lscale).reshape(B, T, c.d_model)
                v = v + _lora_all_rows(x2d, la, lb, li, PROJ_V,
                                       lscale).reshape(B, T, c.d_model)
            q = q.reshape(B, T, c.num_heads, c.head_dim)
            k = k.reshape(B, T, c.num_heads, c.head_dim)
            v = v.reshape(B, T, c.num_heads, c.head_dim)
            ks.append(k)
            vs.append(v)
            att = attention_reference(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True)
            att = att.transpose(0, 2, 1, 3).reshape(B, T, c.d_model)
            o = att @ lp["wo"]
            if lora is not None:
                o = o + _lora_all_rows(att.reshape(B * T, c.d_model),
                                       la, lb, li, PROJ_O,
                                       lscale).reshape(B, T, c.d_model)
            h = h + o
            x2 = _layer_norm(h, lp["ln2_g"], lp["ln2_b"])
            h = h + jax.nn.gelu(x2 @ lp["w1"] + lp["b1"]) @ lp["w2"] \
                + lp["b2"]
        logits = _layer_norm(h, params["lnf_g"],
                             params["lnf_b"]) @ params["head"]
        return logits, jnp.stack(ks), jnp.stack(vs)

    # ------------------------------------------------------- decode --
    def decode_flat(self, params, tokens, positions, seq_ids, valid,
                    k_pages, v_pages, block_tables, k_scales=None,
                    v_scales=None, adapter=None, axis_name=None,
                    w_scales=None, state=None):
        """The FLAT ragged step: a packed ``[T]`` batch of query
        tokens from many sequences — no per-sequence padding, so a
        mixed prefill/decode/verify step computes exactly the tokens
        that exist (the "[total_q_tokens]" layout of the Ragged Paged
        Attention paper; the engine's hot path).

        tokens/positions/seq_ids: int32 [T] (packed; entries with
        ``valid[t] == 0`` are bucket padding — their K/V writes
        route to the null block and their outputs are garbage the
        caller discards); valid: int32/bool [T]; block_tables: int32
        [S, MB]. Causality is per token: token ``t`` attends over
        positions ``<= positions[t]`` of sequence ``seq_ids[t]`` —
        callers must have packed each sequence's tokens in position
        order so later chunk tokens see earlier ones' writes.
        Returns (logits [T, V], k_pages, v_pages).

        Pools: ``k_pages``/``v_pages`` are the stacked
        ``[L, N, bs, H, D]`` pools. Each layer writes its tokens in
        place (``.at[li, bidx, slot].set``) and hands the ragged
        kernel the WHOLE pool with ``layer=li`` — never
        ``k_pages[li]``: a slice handed to the kernel's custom call
        is a copy of one layer's pages (134 MB each for K and V at
        Cerebras-GPT-1.3B's pool, 48 times a step). The kernel's page
        index map takes the layer instead.

        Quantized KV (ISSUE 13): with ``k_scales``/``v_scales``
        ``[L, N, bs, H]`` f32 the pages are int8 — each token's K/V is
        quantized symmetrically per (slot, head) on write (scale =
        max|x|/127, stored alongside) and dequantized inside the
        ragged kernel on read. Quantization is a pure function of the
        written value, so a cached (prefix-shared) block holds exactly
        the bytes a recomputing sequence would produce. Returns
        (logits, k_pages, v_pages, k_scales, v_scales).

        Multi-LoRA (ISSUE 17): ``adapter = (a_pages, b_pages,
        a_tables, a_scales)`` — the bank's factor pools plus a
        per-SEQUENCE page-table row ``a_tables [S, P]`` int32 and
        scale ``a_scales [S]`` f32, all traced. Each token gathers
        its row's factor pages (``a_tables[seq_ids]``) and adds the
        low-rank delta to the four attention projections; rows whose
        table is all null page 0 (scale 0) get an exact-zero delta —
        one program serves any adapter mix.

        Quantized weights (ISSUE 20): ``w_scales`` is the flat
        ``{dot.path: [cols] f32}`` per-output-channel scale dict of a
        ``serving.llm.quant.QuantizedWeights`` checkpoint — the
        matching ``params`` leaves are int8/fp8 and every base matmul
        routes through the registry's weight-only
        ``quantized_matmul`` (dequant fused into the contraction);
        the embedding/position gathers dequantize after the lookup.
        Leaves without a scale entry (norms, biases) run f32
        unchanged, and LoRA deltas stay f32, applied AFTER the
        dequantized base matmul. Scales are traced arguments, so
        hot-swapping a quantized checkpoint reuses the warmed
        program. Under ``axis_name`` the scales arrive pre-sharded
        per :meth:`weight_scale_specs` — column-split weights carry
        their scale shard, row-split weights a replicated full-width
        scale (per-column factors commute with the psum).

        SPMD (ISSUE 19): with ``axis_name`` set this is the PER-SHARD
        body of a ``shard_map`` over a tensor-parallel mesh axis —
        ``wq/wk/wv/w1(+b1)`` arrive column-sharded and ``wo/w2``
        row-sharded (:meth:`param_specs`), and the KV pools (and
        their int8 scale pools) carry only this shard's heads. The
        attention inner loop needs NO collective (per-head
        independent; the softmax scale is 1/sqrt(head_dim), never
        head-count-dependent), so the only collectives in the step
        are one ``psum`` after the o-projection and one after the
        MLP down-projection — fused into the caller's single donated
        program. Batch inputs, layer norms, embeddings and the LM
        head ride replicated, as do the LoRA factor pools: q/k/v
        deltas are computed full-width and column-sliced to this
        shard, the o-delta sees the full-width attention output
        (reassembled by a psum of zero-padded shard slices, so it is
        typed replicated) and lands after the psum, so adapter maths
        is bitwise the single-device result. At axis extent 1 every
        collective is the identity — bit-exact vs the unsharded
        program by construction.

        All of the above is the GPT-2 block's. The other blocks take
        none of those keywords (each is refused by what it stands for)
        and ONE pool of pages, ``k_pages``, with ``v_pages`` ``None``:
        :meth:`_decode_flat_latent`, :meth:`_decode_flat_blockdiff`, and
        :meth:`_decode_flat_hybrid` and :meth:`_decode_flat_gdn`, which
        take ``state``, the pools of their sequences' slot states, and
        return them after the pages.
        """
        given = {k: v for k, v in (
            ("k_scales", k_scales), ("v_scales", v_scales),
            ("adapter", adapter), ("axis_name", axis_name),
            ("w_scales", w_scales), ("state", state)) if v is not None}
        for name in given:
            if name not in self._TAKES[self.config.model_type]:
                self._refuse(self._STANDS_FOR[name])
        if v_pages is not None:
            given["v_pages"] = v_pages
        return self._of_block("decode_flat")(
            params, tokens, positions, seq_ids, valid, k_pages,
            block_tables, **given)

    def _decode_flat_gpt2(self, params, tokens, positions, seq_ids, valid,
                          k_pages, block_tables, v_pages=None,
                          k_scales=None, v_scales=None, adapter=None,
                          axis_name=None, w_scales=None):
        """:meth:`decode_flat` for the GPT-2 block, as documented
        there."""
        import jax
        import jax.numpy as jnp
        c = self.config
        T = tokens.shape[0]
        bs = k_pages.shape[2]
        quantized = k_scales is not None
        if axis_name is None:
            attn = ragged_flat_attention
        else:
            attn = functools.partial(ragged_flat_attention_sharded,
                                     axis_name=axis_name)
        vmask = valid.astype(bool)
        bidx = jnp.where(
            vmask,
            block_tables[seq_ids, positions // bs], 0)  # null block
        slot = jnp.where(vmask, positions % bs, 0)
        ws = w_scales if w_scales is not None else {}

        def _mm(x2d, w, s):
            if s is None:
                return x2d @ w
            return quantized_matmul(x2d, w, s)

        def _lookup(table, idx, s):
            g = table[idx]
            if s is None:
                return g
            return g.astype(jnp.float32) * s
        if adapter is not None:
            la_pages, lb_pages, a_tables, a_scales = adapter
            pages_tok = a_tables[seq_ids]               # [T, P]
            scale_tok = a_scales[seq_ids]               # [T]

            def _delta(x2d, li, proj):
                return paged_lora_delta(
                    x2d, *gather_adapter(la_pages, lb_pages, pages_tok,
                                         li, proj), scale_tok)
        h = _lookup(params["embed"], tokens, ws.get("embed")) \
            + _lookup(params["pos"], positions, ws.get("pos"))
        for li, lp in enumerate(params["layers"]):
            def _lsc(n, _li=li):
                return ws.get(f"layers.{_li}.{n}")
            x = _layer_norm(h, lp["ln1_g"], lp["ln1_b"])
            q = _mm(x, lp["wq"], _lsc("wq"))
            k = _mm(x, lp["wk"], _lsc("wk"))
            v = _mm(x, lp["wv"], _lsc("wv"))
            if adapter is not None:
                if axis_name is None:
                    q = q + _delta(x, li, PROJ_Q)
                    k = k + _delta(x, li, PROJ_K)
                    v = v + _delta(x, li, PROJ_V)
                else:
                    # deltas are full-width (replicated factors);
                    # take this shard's column slice
                    d_loc = q.shape[-1]
                    col0 = jax.lax.axis_index(axis_name) * d_loc
                    q = q + jax.lax.dynamic_slice_in_dim(
                        _delta(x, li, PROJ_Q), col0, d_loc, axis=1)
                    k = k + jax.lax.dynamic_slice_in_dim(
                        _delta(x, li, PROJ_K), col0, d_loc, axis=1)
                    v = v + jax.lax.dynamic_slice_in_dim(
                        _delta(x, li, PROJ_V), col0, d_loc, axis=1)
            heads_here = q.shape[-1] // c.head_dim  # local under tp
            q = q.reshape(T, heads_here, c.head_dim)
            k = k.reshape(T, heads_here, c.head_dim)
            v = v.reshape(T, heads_here, c.head_dim)
            if quantized:
                # int8 pages round to ±127 steps; fp8-e4m3 pages
                # (ISSUE 20) scale into the ±448 finite range and let
                # the cast round — clipped first because the
                # float32→e4m3 cast does NOT saturate, it NaNs
                int8_kv = k_pages.dtype == jnp.int8
                qmax = 127.0 if int8_kv else 448.0
                ksc = jnp.maximum(
                    jnp.max(jnp.abs(k), axis=-1) / qmax, 1e-8)
                vsc = jnp.maximum(
                    jnp.max(jnp.abs(v), axis=-1) / qmax, 1e-8)
                if int8_kv:
                    kq = jnp.clip(jnp.round(k / ksc[..., None]),
                                  -127, 127).astype(jnp.int8)
                    vq = jnp.clip(jnp.round(v / vsc[..., None]),
                                  -127, 127).astype(jnp.int8)
                else:
                    kq = jnp.clip(k / ksc[..., None], -qmax,
                                  qmax).astype(k_pages.dtype)
                    vq = jnp.clip(v / vsc[..., None], -qmax,
                                  qmax).astype(v_pages.dtype)
                k_pages = k_pages.at[li, bidx, slot].set(kq)
                v_pages = v_pages.at[li, bidx, slot].set(vq)
                k_scales = k_scales.at[li, bidx, slot].set(ksc)
                v_scales = v_scales.at[li, bidx, slot].set(vsc)
                att = attn(
                    q, k_pages, v_pages, block_tables, seq_ids,
                    positions, k_scales=k_scales, v_scales=v_scales,
                    layer=li)
            else:
                k_pages = k_pages.at[li, bidx, slot].set(
                    k.astype(k_pages.dtype))
                v_pages = v_pages.at[li, bidx, slot].set(
                    v.astype(v_pages.dtype))
                att = attn(q, k_pages, v_pages, block_tables,
                           seq_ids, positions, layer=li)
            att2d = att.reshape(T, heads_here * c.head_dim)
            o = _mm(att2d, lp["wo"], _lsc("wo"))
            if axis_name is not None:
                o = jax.lax.psum(o, axis_name)
            if adapter is not None:
                if axis_name is None:
                    o = o + _delta(att2d, li, PROJ_O)
                else:
                    # reassemble the full-width att output in column
                    # order (heads are sharded contiguously) as a
                    # psum of each shard's slice placed in zeros —
                    # the same values a tiled all_gather returns,
                    # but typed replicated, so shard_map can prove
                    # the step's token outputs equal on every shard.
                    # The delta lands post-psum, replicated.
                    att_full = jax.lax.psum(
                        jax.lax.dynamic_update_slice_in_dim(
                            jnp.zeros((T, c.d_model), att2d.dtype),
                            att2d, col0, axis=1), axis_name)
                    o = o + _delta(att_full, li, PROJ_O)
            h = h + o
            x2 = _layer_norm(h, lp["ln2_g"], lp["ln2_b"])
            mlp = _mm(jax.nn.gelu(_mm(x2, lp["w1"], _lsc("w1"))
                                  + lp["b1"]), lp["w2"], _lsc("w2"))
            if axis_name is not None:
                mlp = jax.lax.psum(mlp, axis_name)
            h = h + mlp + lp["b2"]
        logits = _mm(_layer_norm(h, params["lnf_g"], params["lnf_b"]),
                     params["head"], ws.get("head"))
        if quantized:
            return logits, k_pages, v_pages, k_scales, v_scales
        return logits, k_pages, v_pages

    # ------------------------------------- the deepseek_v3 block --
    def _mlp_or_experts(self, lp, x2, valid):
        """The second half of a ``deepseek_v3`` layer for normed rows
        ``x2 [T, d]`` (float32): ``(what is added to the residual
        stream [T, d] float32, the routing's load int32 [4] or None)``.
        A dense layer is one SwiGLU; an expert layer routes each row
        with ``valid`` to ``num_experts_per_tok`` experts (no capacity
        limit, no dropped pair) and adds the shared experts."""
        c = self.config
        if "w_gate" in lp:
            return _swiglu(x2, lp["w_gate"], lp["w_up"],
                           lp["w_down"]), None
        sel, gates = route_noaux_tc(
            x2, lp["router"], lp["router_bias"], c.num_experts_per_tok,
            c.norm_topk_prob, c.routed_scaling_factor)
        wg = lp["experts_gate"]
        out = moe_experts(x2.astype(wg.dtype), sel, gates, valid, wg,
                          lp["experts_up"], lp["experts_down"])
        if "shared_gate" in lp:
            out = out + _swiglu(x2, lp["shared_gate"], lp["shared_up"],
                                lp["shared_down"])
        return out, moe_load(sel, valid, c.n_routed_experts)

    def _forward_latent(self, params, tokens):
        """:meth:`forward` for the ``deepseek_v3`` block: latent
        attention in its EXPANDED form (the latent through ``wkv_b``
        into per-head keys and values, dense causal attention), the
        expert layers through the op's plain twin off the TPU. Returns
        ``(logits [B, T, V] float32, latents [L, B, T, W] float32,
        None)``: what the paged path keeps of each token."""
        import jax
        import jax.numpy as jnp
        c = self.config
        B, T = tokens.shape
        H, rank = c.num_heads, c.kv_lora_rank
        nope, rope = c.qk_nope_head_dim, c.qk_rope_head_dim
        eps, theta = c.rms_norm_eps, c.rope_theta
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        live = jnp.ones((B * T,), bool)
        mask = jnp.tril(jnp.ones((T, T), bool))
        h = params["embed"][tokens].astype(jnp.float32)
        latents = []
        for lp in params["layers"]:
            x = _rms_norm(h, lp["norm1"], eps)
            q = _wmm(x, lp["wq"]).reshape(B, T, H, nope + rope)
            a = _wmm(x, lp["wkv_a"])
            cc = _rms_norm(a[..., :rank], lp["norm_kv"], eps)
            k_rope = _rope(a[..., rank:], pos, theta)
            latents.append(jnp.concatenate([cc, k_rope], axis=-1))
            kv = _wmm(cc, lp["wkv_b"]).reshape(B, T, H, -1)
            s = jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope],
                           kv[..., :nope]) \
                + jnp.einsum("bqhd,bkd->bhqk",
                             _rope(q[..., nope:], pos, theta), k_rope)
            s = jnp.where(mask, s * (nope + rope) ** -0.5, -1e30)
            att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                             kv[..., nope:]).reshape(B, T, -1)
            h = h + _wmm(att, lp["wo"])
            x2 = _rms_norm(h, lp["norm2"], eps)
            add, _ = self._mlp_or_experts(
                lp, x2.reshape(B * T, c.d_model), live)
            h = h + add.reshape(B, T, c.d_model)
        logits = _wmm(_rms_norm(h, params["norm_f"], eps), params["head"])
        return logits, jnp.stack(latents), None

    def _decode_flat_latent(self, params, tokens, positions, seq_ids,
                            valid, pages, block_tables):
        """:meth:`decode_flat` for the ``deepseek_v3`` block, the packed
        batch and page arithmetic the same. ``pages`` is the one
        stacked pool of latents ``[L, N, bs, Wp]``, ``Wp`` the latent's
        ``kv_lora_rank + qk_rope_head_dim`` values padded with zeros to
        whole lanes (``PagedKVCache``). Each layer writes its tokens'
        ``(rms(c), rope(k_rope))`` in place and attends in the ABSORBED
        form: ``q_nope`` goes through the key half of ``wkv_b`` into
        the latent's space, the kernel takes the WHOLE pool with
        ``layer=`` (a ``pages[li]`` handed to it would be a copy), and
        its output goes through the value half. Weights are read in
        their own dtype with float32 accumulation; norms, softmax,
        rotary positions, router scores, gates and the residual stream
        are float32. Returns ``(logits [T, V] float32, pages)`` and,
        from a model with expert layers (``expert_slots``), ``load``
        int32 ``[4]`` after them (``ops.moe.moe_load``): routed pairs
        and experts hit summed over the expert layers, the largest load
        of one expert in one layer, and the row tiles the pairs fill,
        summed likewise, over the rows with ``valid``."""
        import jax.numpy as jnp
        c = self.config
        T = tokens.shape[0]
        bs, wp = pages.shape[2], pages.shape[3]
        H, rank = c.num_heads, c.kv_lora_rank
        nope, rope, vd = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                          c.v_head_dim)
        eps, theta = c.rms_norm_eps, c.rope_theta
        scale = float((nope + rope) ** -0.5)
        vmask = valid.astype(bool)
        bidx = jnp.where(
            vmask, block_tables[seq_ids, positions // bs], 0)  # null
        slot = jnp.where(vmask, positions % bs, 0)
        pad = wp - rank - rope
        load = jnp.zeros((4,), jnp.int32)
        h = params["embed"][tokens].astype(jnp.float32)
        for li, lp in enumerate(params["layers"]):
            x = _rms_norm(h, lp["norm1"], eps)
            q = _wmm(x, lp["wq"]).reshape(T, H, nope + rope)
            a = _wmm(x, lp["wkv_a"])
            latent = jnp.concatenate(
                [_rms_norm(a[:, :rank], lp["norm_kv"], eps),
                 _rope(a[:, rank:], positions, theta),
                 jnp.zeros((T, pad), jnp.float32)], axis=-1)
            pages = pages.at[li, bidx, slot].set(
                latent.astype(pages.dtype))
            wkv_b = lp["wkv_b"].reshape(rank, H, nope + vd)
            q_lat = jnp.einsum(
                "thn,rhn->thr", q[..., :nope].astype(wkv_b.dtype),
                wkv_b[..., :nope], preferred_element_type=jnp.float32)
            qq = jnp.concatenate(
                [q_lat, _rope(q[..., nope:], positions, theta),
                 jnp.zeros((T, H, pad), jnp.float32)], axis=-1)
            o_lat = mla_flat_attention(qq, pages, block_tables, seq_ids,
                                       positions, scale, rank, layer=li)
            att = jnp.einsum(
                "thr,rhv->thv", o_lat.astype(wkv_b.dtype),
                wkv_b[..., nope:], preferred_element_type=jnp.float32)
            h = h + _wmm(att.reshape(T, H * vd), lp["wo"])
            add, hit = self._mlp_or_experts(
                lp, _rms_norm(h, lp["norm2"], eps), vmask)
            h = h + add
            if hit is not None:
                load = moe_load_sum(load, hit)
        logits = _wmm(_rms_norm(h, params["norm_f"], eps), params["head"])
        return (logits, pages, load) if self.expert_slots \
            else (logits, pages)

    # -------------------------------- the granitemoehybrid block --
    def _init_params_hybrid(self, w):
        """The ``granitemoehybrid`` tree: the leaves of
        ``benchmark/reference/granite_hybrid_decoder.py``, and its rule
        of scale. Every matrix by its fan-in; the embedding by
        ``d_model ** -0.5 / embedding_multiplier`` (the stream starts
        at norm 1): the head is the embedding itself, and at any larger
        scale a token's own row makes it every position's first choice
        by a margin that no fault in the layers could move; ``wq`` and
        ``wk`` four times their fan-in, so that scores
        under the published ``attention_multiplier`` (1/64 at a head of
        64, an eighth of the usual ``1/sqrt``) spread by about 2 over
        the keys and attention picks keys, as a trained model's does.
        The state-space layer's vectors: ``dt_bias`` the inverse
        softplus of steps from 0.001 to 0.1 (geometric over the heads)
        and ``D`` 1, as the published code starts them; ``A_log`` the
        logarithms of 1/16 to 1 over the heads (the published start, 1
        to 16, forgets within a few hundred tokens under random
        weights; here a head's state fades over 10 to 16,000 tokens, as
        a trained model's remembers over its context, so that a state
        that was not reset still shows behind a long prompt); the
        convolution's bias normal at 0.1 so that its path is exercised;
        norm gains 1."""
        c = self.config
        d, H, G, D = (c.d_model, c.num_heads, c.num_key_value_heads,
                      c.head_dim)
        di, N, Hm = c.mamba_inner, c.mamba_d_state, c.mamba_n_heads
        cd, K = c.mamba_conv_dim, c.mamba_d_conv
        steps = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), max(Hm, 1)))
        layers = []
        for kind in c.layer_types:
            lp = {"norm1": np.ones(d, np.float32),
                  "norm2": np.ones(d, np.float32)}
            if kind == ATTENTION:
                lp.update(wq=w(d, H * D, scale=4 * d ** -0.5),
                          wk=w(d, G * D, scale=4 * d ** -0.5),
                          wv=w(d, G * D), wo=w(H * D, d))
            else:
                lp.update(
                    w_in=w(d, 2 * di + 2 * N + Hm),
                    conv_w=w(cd, K, scale=K ** -0.5),
                    conv_b=w(cd, scale=0.1),
                    dt_bias=np.log(np.expm1(steps)).astype(np.float32),
                    A_log=np.log(np.linspace(1.0 / 16, 1.0, Hm)).astype(
                        np.float32),
                    D=np.ones(Hm, np.float32),
                    norm_y=np.ones(di, np.float32), w_out=w(di, d))
            lp.update(w_gate=w(d, c.d_ff), w_up=w(d, c.d_ff),
                      w_down=w(c.d_ff, d))
            layers.append(lp)
        return self._published_dtype(
            {"embed": w(c.vocab_size, d,
                        scale=d ** -0.5 / c.embedding_multiplier),
             "norm_f": np.ones(d, np.float32), "layers": layers})

    def _mamba_inputs(self, lp, x):
        """A state-space layer's input projection of normed rows ``x
        [..., d]``: ``(z, xBC before its convolution, dt before its bias
        and softplus)``."""
        c = self.config
        di, cd = c.mamba_inner, c.mamba_conv_dim
        zxbcdt = _wmm(x, lp["w_in"])
        return zxbcdt[..., :di], zxbcdt[..., di:di + cd], \
            zxbcdt[..., di + cd:]

    def _mamba_scan_inputs(self, lp, conved, dt_raw):
        """``(x as heads, dt, A, B, C)`` of the recurrence from the
        convolution's sum ``conved [..., conv_dim]`` (bias not yet
        added) and the raw ``dt [..., H]``, all float32."""
        import jax
        import jax.numpy as jnp
        c = self.config
        di, N = c.mamba_inner, c.mamba_d_state
        f32 = jnp.float32
        xbc = jax.nn.silu(conved + lp["conv_b"].astype(f32))
        xs = xbc[..., :di].reshape(
            xbc.shape[:-1] + (c.mamba_n_heads, c.mamba_d_head))
        dt = jax.nn.softplus(dt_raw + lp["dt_bias"].astype(f32))
        return (xs, dt, -jnp.exp(lp["A_log"].astype(f32)),
                xbc[..., di:di + N], xbc[..., di + N:])

    def _mamba_output(self, lp, y, xs, z):
        """A state-space layer's way out: the skip ``D x``, the gate
        BEFORE the norm (one group over the whole inner stream), the
        output projection."""
        import jax
        import jax.numpy as jnp
        c = self.config
        y = y + lp["D"].astype(jnp.float32)[:, None] * xs
        g = y.reshape(y.shape[:-2] + (c.mamba_inner,)) * jax.nn.silu(z)
        return _wmm(_rms_norm(g, lp["norm_y"], c.rms_norm_eps),
                    lp["w_out"])

    def _head_hybrid(self, params, h):
        """Logits of the stream ``h [..., d]``: the final norm against
        the embedding itself (no second copy of it), over
        ``logits_scaling``."""
        import jax
        import jax.numpy as jnp
        c = self.config
        x = _rms_norm(h, params["norm_f"], c.rms_norm_eps)
        embed = params["embed"]
        return jax.lax.dot_general(
            x.astype(embed.dtype), embed,
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / c.logits_scaling

    def _forward_hybrid(self, params, tokens):
        """:meth:`forward` for the ``granitemoehybrid`` block: dense
        causal grouped-query attention, the state-space layers as one
        sequential scan over the positions from a state of zeros.
        Returns ``(logits [B, T, V] float32, None, None)``."""
        import jax
        import jax.numpy as jnp
        c = self.config
        B, T = tokens.shape
        H, G, D = c.num_heads, c.num_key_value_heads, c.head_dim
        K, eps, r = c.mamba_d_conv, c.rms_norm_eps, c.residual_multiplier
        mask = jnp.tril(jnp.ones((T, T), bool))
        h = params["embed"][tokens].astype(jnp.float32) \
            * c.embedding_multiplier
        for kind, lp in zip(c.layer_types, params["layers"]):
            x = _rms_norm(h, lp["norm1"], eps)
            if kind == ATTENTION:
                q = _wmm(x, lp["wq"]).reshape(B, T, G, H // G, D)
                k = _wmm(x, lp["wk"]).reshape(B, T, G, D)
                v = _wmm(x, lp["wv"]).reshape(B, T, G, D)
                s = jnp.einsum("bqgmd,bkgd->bgmqk", q, k) \
                    * c.attention_multiplier
                p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
                att = jnp.einsum("bgmqk,bkgd->bqgmd", p, v)
                mix = _wmm(att.reshape(B, T, H * D), lp["wo"])
            else:
                z, xbc, dt_raw = self._mamba_inputs(lp, x)
                w = lp["conv_w"].astype(jnp.float32)
                before = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
                conved = sum(w[:, j] * before[:, j:j + T]
                             for j in range(K))
                xs, dt, A, Bm, Cm = self._mamba_scan_inputs(
                    lp, conved, dt_raw)

                def token(S, at):
                    x_t, dt_t, b_t, c_t = at
                    S = jnp.exp(dt_t * A)[..., None, None] * S \
                        + (dt_t[..., None] * x_t)[..., None] \
                        * b_t[:, None, None, :]
                    return S, jnp.einsum("bhpn,bn->bhp", S, c_t)

                _, y = jax.lax.scan(
                    token, jnp.zeros((B,) + xs.shape[2:]
                                     + (c.mamba_d_state,), jnp.float32),
                    tuple(jnp.moveaxis(a, 1, 0)
                          for a in (xs, dt, Bm, Cm)))
                mix = self._mamba_output(lp, jnp.moveaxis(y, 0, 1), xs, z)
            h = h + r * mix
            h = h + r * _swiglu(_rms_norm(h, lp["norm2"], eps),
                                lp["w_gate"], lp["w_up"], lp["w_down"])
        return self._head_hybrid(params, h), None, None

    def _decode_flat_hybrid(self, params, tokens, positions, seq_ids,
                            valid, pages, block_tables, state):
        """:meth:`decode_flat` for the ``granitemoehybrid`` block, the
        packed batch and page arithmetic the same.

        ``pages`` is the ONE pool of the attention layers alone, ``[La,
        N, bs, 2 x G x D]``: attention layer ``a`` (in the order the
        layers come) writes each token's ``G`` value heads and then its
        ``G`` key heads as one row and attends through
        :func:`~mxnet_tpu.ops.ragged_attention.gqa_flat_attention` with
        ``layer=a`` and the published ``attention_multiplier`` as the
        scale; no positions enter anything but the causal mask.

        ``state`` is ``(ssm [Lm, S, N, H x P], conv [Lm, S, K - 1,
        conv_dim])`` float32, indexed by state-space layer and by SLOT
        (``seq_ids``, the row of ``block_tables``). A slot's valid
        tokens lie side by side in the batch (a run). The convolution
        takes each token's ``K - 1`` predecessors from its run or, for
        the run's first tokens, from the slot's window; the window then
        becomes the last ``K - 1`` inputs of window and run together (a
        run shorter than the window shifts it through). The recurrence
        is :func:`~mxnet_tpu.ops.ssd.ssd_state_step` on the WHOLE pool
        with ``layer=``. A run whose first token sits at position 0
        reads a window and a state of zeros, whatever the slot's last
        occupant left, and a token with ``valid == 0`` touches neither.

        Weights are read in their own dtype with float32 accumulation;
        norms, softmax, softplus, the decay, the recurrence, the gate
        and the residual stream are float32. Returns ``(logits [T, V]
        float32, pages, ssm, conv)``."""
        import jax.numpy as jnp
        c = self.config
        ssm, conv = state
        T, S = tokens.shape[0], block_tables.shape[0]
        bs = pages.shape[2]
        H, G, D = c.num_heads, c.num_key_value_heads, c.head_dim
        K, eps, r = c.mamba_d_conv, c.rms_norm_eps, c.residual_multiplier
        vmask = valid.astype(bool)
        bidx = jnp.where(
            vmask, block_tables[seq_ids, positions // bs], 0)  # null
        slot = jnp.where(vmask, positions % bs, 0)
        # where the convolution finds each token's predecessors, once
        # for all layers
        taps, keeps = _conv_windows(seq_ids, valid, positions, S, K)
        h = params["embed"][tokens].astype(jnp.float32) \
            * c.embedding_multiplier
        att_layer = ssm_layer = 0
        for kind, lp in zip(c.layer_types, params["layers"]):
            x = _rms_norm(h, lp["norm1"], eps)
            if kind == ATTENTION:
                q = _wmm(x, lp["wq"]).reshape(T, H, D)
                row = jnp.concatenate(
                    [_wmm(x, lp["wv"]), _wmm(x, lp["wk"])], axis=-1)
                pages = pages.at[att_layer, bidx, slot].set(
                    row.astype(pages.dtype))
                att = gqa_flat_attention(
                    q, pages, block_tables, seq_ids, positions,
                    c.attention_multiplier, G, layer=att_layer)
                mix = _wmm(att.reshape(T, H * D), lp["wo"])
                att_layer += 1
            else:
                z, xbc, dt_raw = self._mamba_inputs(lp, x)
                conved, window = _causal_conv(xbc, conv[ssm_layer],
                                              lp["conv_w"], taps, keeps)
                conv = conv.at[ssm_layer].set(window)
                xs, dt, A, Bm, Cm = self._mamba_scan_inputs(
                    lp, conved, dt_raw)
                y, ssm = ssd_state_step(xs, dt, A, Bm, Cm, ssm, seq_ids,
                                        positions, valid, ssm_layer)
                mix = self._mamba_output(lp, y, xs, z)
                ssm_layer += 1
            h = h + r * mix
            h = h + r * _swiglu(_rms_norm(h, lp["norm2"], eps),
                                lp["w_gate"], lp["w_up"], lp["w_down"])
        return self._head_hybrid(params, h), pages, ssm, conv

    # ---------------------------------------- the sdar_moe block --
    def _init_params_blockdiff(self, w):
        """The ``sdar_moe`` tree: the leaves of
        ``benchmark/reference/sdar_moe_decoder.py``, and its rule of
        scale (``WEIGHT_RULE`` there says why each): fan-in, and the
        embedding 0.5 (a masked position starts as the mask token's
        row, and what the layers add has to decide its first choice);
        the matrices that write to the residual stream (``wo``,
        ``experts_down``) by 1/sqrt(2 x num_layers) besides,
        ``experts_down`` twice that (normalised gates average a
        token's experts); the router three times its fan-in (a token's
        gates then fall off as a trained router's do, the eighth a few
        per cent of the first); the gains of ``q_norm`` and ``k_norm``
        1.5 (scores spread by about 2 over the keys); the other gains
        1."""
        c = self.config
        d, H, G, D = (c.d_model, c.num_heads, c.num_key_value_heads,
                      c.head_dim)
        E, m = c.num_experts, c.moe_intermediate_size
        out = (2 * c.num_layers) ** -0.5
        layers = []
        for _ in range(c.num_layers):
            layers.append({
                "norm1": np.ones(d, np.float32),
                "norm2": np.ones(d, np.float32),
                "q_norm": np.full(D, 1.5, np.float32),
                "k_norm": np.full(D, 1.5, np.float32),
                "wq": w(d, H * D), "wk": w(d, G * D), "wv": w(d, G * D),
                "wo": w(H * D, d, scale=out * (H * D) ** -0.5),
                "router": w(d, E, scale=3 * d ** -0.5),
                "experts_gate": w(E, d, m, scale=d ** -0.5),
                "experts_up": w(E, d, m, scale=d ** -0.5),
                "experts_down": w(E, m, d, scale=2 * out * m ** -0.5)})
        return self._published_dtype(
            {"embed": w(c.vocab_size, d, scale=0.5),
             "norm_f": np.ones(d, np.float32),
             "head": w(d, c.vocab_size), "layers": layers})

    def _qkv_blockdiff(self, lp, x, positions):
        """A layer's queries ``[..., H, D]`` and a token's page row ``[...,
        2 x G x D]`` (its value heads, then its key heads) from normed
        rows ``x [..., d]``: each query and key head RMS-normed over its
        own values, then turned by its own position."""
        import jax.numpy as jnp
        c = self.config
        H, G, D = c.num_heads, c.num_key_value_heads, c.head_dim
        eps, theta = c.rms_norm_eps, c.rope_theta
        lead = x.shape[:-1]
        q = _rope(_rms_norm(_wmm(x, lp["wq"]).reshape(lead + (H, D)),
                            lp["q_norm"], eps), positions, theta)
        k = _rope(_rms_norm(_wmm(x, lp["wk"]).reshape(lead + (G, D)),
                            lp["k_norm"], eps), positions, theta)
        return q, k, _wmm(x, lp["wv"])

    def _experts_blockdiff(self, lp, x2, valid):
        """The expert layer for normed rows ``x2 [T, d]`` (float32):
        ``(what is added to the stream, the routing's load int32 [4])``."""
        c = self.config
        sel, gates = route_softmax_topk(
            x2, lp["router"], c.num_experts_per_tok, c.norm_topk_prob)
        wg = lp["experts_gate"]
        out = moe_experts(x2.astype(wg.dtype), sel, gates, valid, wg,
                          lp["experts_up"], lp["experts_down"])
        return out, moe_load(sel, valid, c.num_experts)

    def _forward_blockdiff(self, params, tokens):
        """:meth:`forward` for the ``sdar_moe`` block: dense grouped-query
        attention under the BLOCK-CAUSAL mask, the expert layers through
        the op's plain twin off the TPU. The logits at a position are
        for that position itself. Returns ``(logits [B, T, V] float32,
        None, None)``."""
        import jax
        import jax.numpy as jnp
        c = self.config
        B, T = tokens.shape
        H, G, D = c.num_heads, c.num_key_value_heads, c.head_dim
        eps = c.rms_norm_eps
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        live = jnp.ones((B * T,), bool)
        t = jnp.arange(T)
        mask = t[None, :] <= _block_last(t, c.block_length)[:, None]
        h = params["embed"][tokens].astype(jnp.float32)
        for lp in params["layers"]:
            q, k, v = self._qkv_blockdiff(
                lp, _rms_norm(h, lp["norm1"], eps), pos)
            s = jnp.einsum("bqgmd,bkgd->bgmqk",
                           q.reshape(B, T, G, H // G, D), k) * D ** -0.5
            p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
            att = jnp.einsum("bgmqk,bkgd->bqgmd", p,
                             v.reshape(B, T, G, D))
            h = h + _wmm(att.reshape(B, T, H * D), lp["wo"])
            add, _ = self._experts_blockdiff(
                lp, _rms_norm(h, lp["norm2"], eps).reshape(B * T, -1),
                live)
            h = h + add.reshape(B, T, c.d_model)
        logits = _wmm(_rms_norm(h, params["norm_f"], eps), params["head"])
        return logits, None, None

    def _decode_flat_blockdiff(self, params, tokens, positions, seq_ids,
                               valid, pages, block_tables):
        """:meth:`decode_flat` for the ``sdar_moe`` block, the packed
        batch and page arithmetic the same. ``pages`` is the ONE pool of
        packed rows ``[L, N, bs, 2 x G x D]``: each layer writes its
        tokens' value heads and then their normed, turned key heads as
        one row and attends through
        :func:`~mxnet_tpu.ops.ragged_attention.gqa_flat_attention`.
        The mask is BLOCK-CAUSAL: the kernel is given each token's
        block's last position (``_block_last``) where a causal model
        gives the token's own, which enters the rotary term alone; the
        engine feeds whole blocks, so the keys a token may see beyond
        itself were written by this very call. A row the engine feeds a
        block of (a denoising or a settling pass) overwrites the block's
        rows in place. Weights are read in their own dtype with float32
        accumulation; norms, softmax, rotary positions, router scores,
        gates and the residual stream are float32. Returns ``(logits
        [T, V] float32, pages, load)``, ``load`` as the ``deepseek_v3``
        block leaves it."""
        import jax.numpy as jnp
        c = self.config
        T = tokens.shape[0]
        bs = pages.shape[2]
        H, G, D = c.num_heads, c.num_key_value_heads, c.head_dim
        eps = c.rms_norm_eps
        vmask = valid.astype(bool)
        bidx = jnp.where(
            vmask, block_tables[seq_ids, positions // bs], 0)  # null
        slot = jnp.where(vmask, positions % bs, 0)
        sees = _block_last(positions, c.block_length)
        load = jnp.zeros((4,), jnp.int32)
        h = params["embed"][tokens].astype(jnp.float32)
        for li, lp in enumerate(params["layers"]):
            q, k, v = self._qkv_blockdiff(
                lp, _rms_norm(h, lp["norm1"], eps), positions)
            row = jnp.concatenate([v, k.reshape(T, G * D)], axis=-1)
            pages = pages.at[li, bidx, slot].set(row.astype(pages.dtype))
            att = gqa_flat_attention(q, pages, block_tables, seq_ids, sees,
                                     D ** -0.5, G, layer=li)
            h = h + _wmm(att.reshape(T, H * D), lp["wo"])
            add, hit = self._experts_blockdiff(
                lp, _rms_norm(h, lp["norm2"], eps), vmask)
            h = h + add
            load = moe_load_sum(load, hit)
        logits = _wmm(_rms_norm(h, params["norm_f"], eps), params["head"])
        return logits, pages, load

    # ------------------------------------- the gigachat3_5 block --
    def _norm_gdn(self, lp, name, x):
        """``x`` through the layer's zero-centred gated norm ``name``."""
        c = self.config
        return _zc_norm(x, lp[name], c.rms_norm_eps, lp[name + "_gate"],
                        c.layernorm_gating_weight)

    def _rope_gdn(self):
        """``(inverse frequencies [rope / 2] float32, softmax scale)`` of
        the latent layers: YaRN's where ``rope_scaling`` names it (its
        ``mscale_all_dim`` factor squared on the scale; the published
        ``mscale`` equal to it, cos and sin are not rescaled), else the
        plain ones of ``rope_theta``."""
        c = self.config
        r = c.qk_rope_head_dim
        scale = (c.qk_nope_head_dim + r) ** -0.5
        sc = c.rope_scaling
        if not sc:
            return (c.rope_theta ** (-np.arange(r // 2, dtype=np.float64)
                                     / (r // 2))).astype(np.float32), scale
        m = yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 1.0))
        return yarn_inv_freq(r, c.rope_theta, sc), scale * m * m

    def _mlp_gdn(self, lp, x, valid):
        """The MLP sublayer for normed rows ``x [T, d]``: ``(its output
        [T, d] float32, the routing's load int32 [5] or None)``. A dense
        layer is one SwiGLU; an expert layer routes each row over all
        ``router_experts`` and computes the held experts' pairs alone
        (``ops.moe.moe_experts`` with ``first=``), then the shared
        experts; every SwiGLU held to ``swiglu_limit``."""
        c = self.config
        lim = c.swiglu_limit
        if "w_gate" in lp:
            return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"],
                           lim), None
        sel, gates = route_noaux_tc(
            x, lp["router"], lp["router_bias"], c.num_experts_per_tok,
            c.norm_topk_prob, c.routed_scaling_factor)
        wg = lp["experts_gate"]
        out = moe_experts(x.astype(wg.dtype), sel, gates, valid, wg,
                          lp["experts_up"], lp["experts_down"],
                          first=c.first_routed_expert, limit=lim)
        out = out + _swiglu(x, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"], lim)
        return out, moe_load(sel, valid, c.n_routed_experts,
                             first=c.first_routed_expert)

    def _gdn_inputs(self, lp, x):
        """A GatedDeltaNet layer's projections of normed rows ``x [T,
        d]``: ``(q, k and v before the convolution [T, conv_dim], z [T,
        Hv x Dv], the decay's log g [T, Hv], beta [T, Hv])``."""
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        qkv = jnp.concatenate([_wmm(x, lp[n]) for n in ("wq", "wk", "wv")],
                              axis=-1)
        g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(
            _wmm(x, lp["wa"]) + lp["dt_bias"].astype(f32))
        return qkv, _wmm(x, lp["wz"]), g, jax.nn.sigmoid(_wmm(x, lp["wb"]))

    def _gdn_heads(self, conved):
        """``(q, k [T, Hk, Dk], v [T, Hv, Dv])`` of the recurrence from
        the convolution's sums: SiLU, then ``q`` and ``k`` L2-normed per
        head and ``q`` times ``Dk ** -0.5``."""
        import jax
        c = self.config
        T = conved.shape[0]
        hk, dk = c.linear_num_key_heads, c.linear_key_head_dim
        act = jax.nn.silu(conved)
        q = act[:, :hk * dk].reshape(T, hk, dk)
        k = act[:, hk * dk:2 * hk * dk].reshape(T, hk, dk)
        v = act[:, 2 * hk * dk:].reshape(T, c.linear_num_value_heads,
                                          c.linear_value_head_dim)
        return _l2_norm(q) * dk ** -0.5, _l2_norm(k), v

    def _gdn_output(self, lp, o, z):
        """A GatedDeltaNet layer's way out: each head's output normed
        over its values, times ``linear_sigmoid_gate_scale x
        sigmoid(z)``, then the output projection."""
        import jax
        c = self.config
        T = o.shape[0]
        gate = c.linear_sigmoid_gate_scale * jax.nn.sigmoid(
            z.reshape(o.shape))
        y = _zc_norm(o, lp["norm_o"], c.rms_norm_eps) * gate
        return _wmm(y.reshape(T, -1), lp["wo"])

    def _forward_gdn(self, params, tokens):
        """:meth:`forward` is not written for the ``gigachat3_5`` block:
        its oracle is ``benchmark/reference/gigachat35_decoder.py``."""
        self._refuse("the dense oracle: compare with the benchmark's "
                     "reference decoder")

    def _decode_flat_gdn(self, params, tokens, positions, seq_ids, valid,
                         pages, block_tables, state):
        """:meth:`decode_flat` for the ``gigachat3_5`` block, the packed
        batch and page arithmetic the same.

        ``pages`` is the ONE pool of latents of the full-attention layers
        alone, ``[La, N, bs, Wp]``: attention layer ``a`` (in the order
        the layers come) writes each token's ``(norm(c_kv), rope(k_rope))``
        and attends in the absorbed form through
        :func:`~mxnet_tpu.ops.ragged_attention.mla_flat_attention` with
        ``layer=a``, as the ``deepseek_v3`` block does, its queries from
        the normed query latent, its rotary pairs interleaved under YaRN,
        the heads' outputs gated by ``sigmoid(x W_gate_attn)`` before
        ``wo``.

        ``state`` is ``(gdn [Ll, S, Hv, Dk, Dv], conv [Ll, S, K - 1,
        conv_dim])`` float32, indexed by GatedDeltaNet layer and by
        SLOT, as the ``granitemoehybrid`` block's: the convolution
        over ``[q | k | v]`` takes each token's predecessors from its run
        or its slot's window (:func:`_conv_windows`), the delta rule is
        :func:`~mxnet_tpu.ops.gdn.gdn_state_step` on the WHOLE pool with
        ``layer=``, and a run that starts at position 0 reads a window
        and a state of zeros.

        Every sublayer is normed before and after; the MLP sublayer is
        dense or routed (:meth:`_mlp_gdn`). Weights are read in their own
        dtype with float32 accumulation; norms, softmax, rotary
        positions, the recurrence, router scores, gates and the residual
        stream are float32. Returns ``(logits [T, V] float32, pages,
        gdn, conv)`` and, from a model with expert layers, ``load`` int32
        ``[5]`` after them: the ``deepseek_v3`` block's four over the held
        experts, then the pairs routed to experts held elsewhere, summed
        over the expert layers."""
        import jax
        import jax.numpy as jnp
        c = self.config
        gdn, conv = state
        T, S = tokens.shape[0], block_tables.shape[0]
        bs, wp = pages.shape[2], pages.shape[3]
        H, rank = c.num_heads, c.kv_lora_rank
        nope, rope, vd = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                          c.v_head_dim)
        eps = c.rms_norm_eps
        freqs, scale = self._rope_gdn()
        freqs = jnp.asarray(freqs)
        vmask = valid.astype(bool)
        bidx = jnp.where(
            vmask, block_tables[seq_ids, positions // bs], 0)  # null
        slot = jnp.where(vmask, positions % bs, 0)
        pad = wp - rank - rope
        taps, keeps = _conv_windows(seq_ids, valid, positions, S,
                                    c.linear_conv_kernel_dim)
        load = jnp.zeros((5,), jnp.int32)
        h = params["embed"][tokens].astype(jnp.float32)
        att_layer = lin_layer = 0
        for lp in params["layers"]:
            x = self._norm_gdn(lp, "norm1", h)
            if "wq_a" in lp:
                q = _wmm(_zc_norm(_wmm(x, lp["wq_a"]), lp["norm_q"], eps),
                         lp["wq_b"]).reshape(T, H, nope + rope)
                a = _wmm(x, lp["wkv_a"])
                latent = jnp.concatenate(
                    [_zc_norm(a[:, :rank], lp["norm_kv"], eps),
                     _rope_interleaved(a[:, rank:], positions, freqs),
                     jnp.zeros((T, pad), jnp.float32)], axis=-1)
                pages = pages.at[att_layer, bidx, slot].set(
                    latent.astype(pages.dtype))
                wkv_b = lp["wkv_b"].reshape(rank, H, nope + vd)
                q_lat = jnp.einsum(
                    "thn,rhn->thr", q[..., :nope].astype(wkv_b.dtype),
                    wkv_b[..., :nope], preferred_element_type=jnp.float32)
                qq = jnp.concatenate(
                    [q_lat, _rope_interleaved(q[..., nope:], positions,
                                              freqs),
                     jnp.zeros((T, H, pad), jnp.float32)], axis=-1)
                o_lat = mla_flat_attention(qq, pages, block_tables,
                                           seq_ids, positions, scale, rank,
                                           layer=att_layer)
                att = jnp.einsum(
                    "thr,rhv->thv", o_lat.astype(wkv_b.dtype),
                    wkv_b[..., nope:], preferred_element_type=jnp.float32)
                att = att.reshape(T, H * vd) * jax.nn.sigmoid(
                    _wmm(x, lp["w_gate_attn"]))
                mix = _wmm(att, lp["wo"])
                att_layer += 1
            else:
                qkv, z, g, beta = self._gdn_inputs(lp, x)
                conved, window = _causal_conv(qkv, conv[lin_layer],
                                              lp["conv_w"], taps, keeps)
                conv = conv.at[lin_layer].set(window)
                q, k, v = self._gdn_heads(conved)
                o, gdn = gdn_state_step(q, k, v, g, beta, gdn, seq_ids,
                                        positions, valid, lin_layer)
                mix = self._gdn_output(lp, o, z)
                lin_layer += 1
            h = h + self._norm_gdn(lp, "norm2", mix)
            add, hit = self._mlp_gdn(lp, self._norm_gdn(lp, "norm3", h),
                                     vmask)
            h = h + self._norm_gdn(lp, "norm4", add)
            if hit is not None:
                load = moe_load_sum(load, hit)
        logits = _wmm(_zc_norm(h, params["norm_f"], eps,
                               params["norm_f_gate"],
                               c.layernorm_gating_weight), params["head"])
        return (logits, pages, gdn, conv, load) if self.expert_slots \
            else (logits, pages, gdn, conv)

    # ------------------------------------- the cohere2_moe block --
    def _init_params_window(self, w):
        """The ``cohere2_moe`` tree: the leaves of
        ``benchmark/reference/command_a_plus_decoder.py``, and its rule of
        scale (``WEIGHT_RULE`` there says why each): every matrix by its
        fan-in (the router and the experts too), ``wq`` and ``wk`` twice
        that (scores spread by about 4 over the keys, so that which keys a
        window leaves out shows), the embedding ``d ** -0.5`` (the head
        is the embedding: logits of about unit spread, decided by the
        layers, not by a token's own row); the LayerNorm gains 1 plus a
        normal at 0.1."""
        c = self.config
        d, H, G, D = (c.d_model, c.num_heads, c.num_key_value_heads,
                      c.head_dim)
        E, m = c.n_routed_experts, c.moe_intermediate_size
        sh = c.num_shared_experts * m
        layers = []
        for _ in range(c.num_layers):
            lp = {"norm": 1.0 + w(d, scale=0.1),
                  "wq": w(d, H * D, scale=2 * d ** -0.5),
                  "wk": w(d, G * D, scale=2 * d ** -0.5),
                  "wv": w(d, G * D), "wo": w(H * D, d),
                  "router": w(d, c.router_experts),
                  "experts_gate": w(E, d, m, scale=d ** -0.5),
                  "experts_up": w(E, d, m, scale=d ** -0.5),
                  "experts_down": w(E, m, d, scale=m ** -0.5)}
            if sh:
                lp.update(shared_gate=w(d, sh), shared_up=w(d, sh),
                          shared_down=w(sh, d, scale=m ** -0.5))
            layers.append(lp)
        return self._published_dtype(
            {"embed": w(c.vocab_size, d, scale=d ** -0.5),
             "norm_f": 1.0 + w(d, scale=0.1), "layers": layers})

    def _norm_window(self, x, g):
        """LayerNorm with a gain and no bias, in float32."""
        import jax
        import jax.numpy as jnp
        x = x.astype(jnp.float32)
        x = x - x.mean(axis=-1, keepdims=True)
        return x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True)
                                 + self.config.layer_norm_eps) \
            * g.astype(jnp.float32)

    def _qkv_window(self, lp, u, positions, sliding):
        """A layer's queries ``[..., H, D]``, keys ``[..., G, D]`` and
        values ``[..., G x D]`` from normed rows ``u [..., d]``; on a
        sliding layer queries and keys turned by their positions on
        interleaved pairs (GPT-J), on a full layer not at all."""
        c = self.config
        H, G, D = c.num_heads, c.num_key_value_heads, c.head_dim
        lead = u.shape[:-1]
        q = _wmm(u, lp["wq"]).reshape(lead + (H, D))
        k = _wmm(u, lp["wk"]).reshape(lead + (G, D))
        if sliding:
            freqs = c.rope_theta ** (-np.arange(D // 2, dtype=np.float32)
                                     / (D // 2))
            q = _rope_interleaved(q, positions, freqs)
            k = _rope_interleaved(k, positions, freqs)
        return q, k, _wmm(u, lp["wv"])

    def _experts_window(self, lp, u, valid):
        """The expert layer for normed rows ``u [T, d]`` (float32): ``(the
        routed pairs held here plus the mean of the shared experts [T, d]
        float32, the routing's load int32 [5])``: the sigmoid router over
        all ``router_experts`` with no selection bias, the held experts'
        pairs through ``ops.moe.moe_experts`` told their range (a pair
        routed elsewhere adds nothing here)."""
        c = self.config
        sel, gates = route_noaux_tc(u, lp["router"], None,
                                    c.num_experts_per_tok, c.norm_topk_prob)
        wg = lp["experts_gate"]
        out = moe_experts(u.astype(wg.dtype), sel, gates, valid, wg,
                          lp["experts_up"], lp["experts_down"],
                          first=c.first_routed_expert)
        if c.num_shared_experts:
            out = out + _swiglu(u, lp["shared_gate"], lp["shared_up"],
                                lp["shared_down"]) / c.num_shared_experts
        return out, moe_load(sel, valid, c.n_routed_experts,
                             first=c.first_routed_expert)

    def _head_window(self, params, h):
        """Logits of the stream ``h [..., d]``: the final LayerNorm
        against the embedding (tied), times ``logit_scale``."""
        import jax.numpy as jnp
        e = params["embed"]
        x = self._norm_window(h, params["norm_f"]).astype(e.dtype)
        return jnp.einsum("...d,vd->...v", x, e,
                          preferred_element_type=jnp.float32) \
            * self.config.logit_scale

    def _forward_window(self, params, tokens):
        """:meth:`forward` for the ``cohere2_moe`` block: dense grouped-query
        attention under each layer's mask (the window's on a sliding
        layer, causal on a full one), the expert layers through the op's
        plain twin off the TPU. Returns ``(logits [B, T, V] float32, None,
        None)``."""
        import jax
        import jax.numpy as jnp
        c = self.config
        B, T = tokens.shape
        H, G, D = c.num_heads, c.num_key_value_heads, c.head_dim
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        live = jnp.ones((B * T,), bool)
        t = jnp.arange(T)
        causal = t[None, :] <= t[:, None]
        h = params["embed"][tokens].astype(jnp.float32)
        for kind, lp in zip(c.layer_types, params["layers"]):
            sliding = kind == SLIDING
            mask = jnp.logical_and(
                causal, t[None, :] > t[:, None] - c.sliding_window) \
                if sliding else causal
            u = self._norm_window(h, lp["norm"])
            q, k, v = self._qkv_window(lp, u, pos, sliding)
            s = jnp.einsum("bqgmd,bkgd->bgmqk",
                           q.reshape(B, T, G, H // G, D), k) * D ** -0.5
            p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
            att = jnp.einsum("bgmqk,bkgd->bqgmd", p,
                             v.reshape(B, T, G, D))
            add, _ = self._experts_window(lp, u.reshape(B * T, -1), live)
            h = h + _wmm(att.reshape(B, T, H * D), lp["wo"]) \
                + add.reshape(B, T, c.d_model)
        return self._head_window(params, h), None, None

    def _decode_flat_window(self, params, tokens, positions, seq_ids,
                            valid, pages, block_tables):
        """:meth:`decode_flat` for the ``cohere2_moe`` block, the packed
        batch the same. ``pages`` is ONE pool of packed rows ``[1, N, bs,
        2 x G x D]`` (a token's value heads, then its key heads) whose
        pages every layer draws on, and ``block_tables [L, S, MB]`` holds
        each LAYER's table: layer ``l`` writes its tokens' rows through
        ``block_tables[l]`` and attends through
        :func:`~mxnet_tpu.ops.ragged_attention.gqa_window_attention`, a
        sliding layer under its window (the engine gives the pages behind
        a window back: its table names the null block there), a full
        layer causally. Attention and the expert layer both read the
        layer's one LayerNorm and are added to the stream together.
        Weights are read in their own dtype with float32 accumulation;
        norms, softmax, rotary positions, router scores, gates and the
        residual stream are float32. Returns ``(logits [T, V] float32,
        pages, load)``, ``load`` as the ``gigachat3_5`` block leaves it
        (the held experts' four numbers, then the pairs routed
        elsewhere)."""
        import jax.numpy as jnp
        c = self.config
        T = tokens.shape[0]
        bs = pages.shape[2]
        H, G, D = c.num_heads, c.num_key_value_heads, c.head_dim
        vmask = valid.astype(bool)
        slot = jnp.where(vmask, positions % bs, 0)
        load = jnp.zeros((5,), jnp.int32)
        h = params["embed"][tokens].astype(jnp.float32)
        for li, (kind, lp) in enumerate(zip(c.layer_types,
                                            params["layers"])):
            sliding = kind == SLIDING
            tables = block_tables[li]
            bidx = jnp.where(vmask, tables[seq_ids, positions // bs],
                             0)                                 # null
            u = self._norm_window(h, lp["norm"])
            q, k, v = self._qkv_window(lp, u, positions, sliding)
            row = jnp.concatenate([v, k.reshape(T, G * D)], axis=-1)
            pages = pages.at[0, bidx, slot].set(row.astype(pages.dtype))
            att = gqa_window_attention(
                q, pages, tables, seq_ids, positions, D ** -0.5, G,
                window=c.sliding_window if sliding else None, valid=vmask,
                layer=0)
            add, hit = self._experts_window(lp, u, vmask)
            h = h + _wmm(att.reshape(T, H * D), lp["wo"]) + add
            load = moe_load_sum(load, hit)
        return self._head_window(params, h), pages, load


def _block_last(positions, block_length):
    """The last position of each position's block: what a position of a
    block-diffusion model sees up to."""
    return (positions // block_length + 1) * block_length - 1


def _gpt2_oracle_only(model):
    """The incremental oracle below is the GPT-2 block's (a model that
    is no :class:`TinyDecoder` is taken for one)."""
    if getattr(getattr(model, "config", None), "model_type", GPT2) != GPT2:
        model._refuse("the incremental oracle: compare with "
                      "TinyDecoder.forward over the whole sequence")


def _incremental_step(model, params, token, pos, k_cache, v_cache,
                      lora=None):
    """One appended token against a dense (non-paged) KV cache —
    the eager oracle's decode step. token/pos: int32 scalars; caches:
    [L, max_context, H, Dh]. Writes the token's K/V at ``pos``, then
    attends over positions ``<= pos``. ``lora``: optional
    ``(a_sel, b_sel, scale)`` single-adapter factors (same layout as
    :meth:`TinyDecoder.forward`). Returns (logits [V], k_cache,
    v_cache). Pure function of its inputs (jitted once per model)."""
    import jax
    import jax.numpy as jnp
    from ...ops.flash_attention import _NEG_INF
    c = model.config
    _gpt2_oracle_only(model)
    scale = 1.0 / (c.head_dim ** 0.5)      # python float: config-time
    mask = jnp.arange(c.max_context, dtype=jnp.int32) <= pos
    if lora is not None:
        la, lb, lscale = lora

        def _ldelta(x1d, li, proj):
            return _lora_all_rows(x1d[None], la, lb, li, proj,
                                  lscale)[0]
    h = params["embed"][token] + params["pos"][pos]
    for li, lp in enumerate(params["layers"]):
        x = _layer_norm(h, lp["ln1_g"], lp["ln1_b"])
        q = x @ lp["wq"]
        k = x @ lp["wk"]
        v = x @ lp["wv"]
        if lora is not None:
            q = q + _ldelta(x, li, PROJ_Q)
            k = k + _ldelta(x, li, PROJ_K)
            v = v + _ldelta(x, li, PROJ_V)
        q = q.reshape(c.num_heads, c.head_dim)
        k = k.reshape(c.num_heads, c.head_dim)
        v = v.reshape(c.num_heads, c.head_dim)
        k_cache = k_cache.at[li, pos].set(k)
        v_cache = v_cache.at[li, pos].set(v)
        s = jnp.einsum("hd,thd->ht", q.astype(jnp.float32),
                       k_cache[li].astype(jnp.float32)) * scale
        s = jnp.where(mask[None, :], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum("ht,thd->hd", p,
                         v_cache[li].astype(jnp.float32)).astype(h.dtype)
        att1d = att.reshape(c.d_model)
        o = att1d @ lp["wo"]
        if lora is not None:
            o = o + _ldelta(att1d, li, PROJ_O)
        h = h + o
        x2 = _layer_norm(h, lp["ln2_g"], lp["ln2_b"])
        h = h + jax.nn.gelu(x2 @ lp["w1"] + lp["b1"]) @ lp["w2"] \
            + lp["b2"]
    logits = _layer_norm(h, params["lnf_g"],
                         params["lnf_b"]) @ params["head"]
    return logits, k_cache, v_cache


def greedy_decode_reference(model, params, prompt_tokens,
                            max_new_tokens, stop_token=None,
                            lora=None):
    """Per-sequence eager greedy decoding — the oracle continuous
    batching must match token for token.

    Incremental (append-only KV): ONE dense causal forward over the
    ``max_context``-padded prompt fills a per-layer KV cache and emits
    the first token; every later token runs a single-position
    incremental step (:func:`_incremental_step`, jitted once per
    model — fixed shape, so repeated oracle calls never recompile)
    that appends its K/V and attends over the cached prefix. Same
    greedy stream as the old recompute-everything oracle at a small
    fraction of the work — parity suites stop paying a full padded
    forward per emitted token. Returns the generated tokens (prompt
    excluded) as a list.

    ``lora``: optional single-adapter ``(a_sel, b_sel, scale)`` from
    ``AdapterBank.adapter_arrays`` — the per-adapter oracle for
    mixed-adapter engine batches (the factors ride the separately
    cached ``_incr_jit_lora`` as traced arguments, so sweeping
    adapters never recompiles either oracle).
    """
    import jax
    import jax.numpy as jnp
    _gpt2_oracle_only(model)
    toks = [int(t) for t in prompt_tokens]
    out = []
    ctx = model.max_context
    if lora is None:
        step = getattr(model, "_incr_jit", None)
        if step is None:
            step = jax.jit(functools.partial(_incremental_step, model))
            model._incr_jit = step
    else:
        la, lb, lscale = (jnp.asarray(lora[0]), jnp.asarray(lora[1]),
                          np.float32(lora[2]))
        lstep = getattr(model, "_incr_jit_lora", None)
        if lstep is None:
            def _lora_step(params, token, pos, kc, vc, a, b, s,
                           _model=model):
                return _incremental_step(_model, params, token, pos,
                                         kc, vc, lora=(a, b, s))
            lstep = jax.jit(_lora_step)
            model._incr_jit_lora = lstep

        def step(params, token, pos, kc, vc):
            return lstep(params, token, pos, kc, vc, la, lb, lscale)
    padded = np.zeros(ctx, np.int32)
    padded[:len(toks)] = toks
    logits, k, v = model.forward(
        params, jnp.asarray(padded[None]),
        lora=None if lora is None else (la, lb, lscale))
    # positions past the prompt hold pad garbage; each is overwritten
    # by the incremental step that lands there before any mask
    # exposes it
    k_cache, v_cache = k[:, 0], v[:, 0]
    nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
    for i in range(max_new_tokens):
        out.append(nxt)
        toks.append(nxt)
        if stop_token is not None and nxt == stop_token:
            break
        if len(toks) >= ctx or i == max_new_tokens - 1:
            break
        logits, k_cache, v_cache = step(
            params, jnp.int32(nxt), jnp.int32(len(toks) - 1),
            k_cache, v_cache)
        nxt = int(jnp.argmax(logits))
    return out
