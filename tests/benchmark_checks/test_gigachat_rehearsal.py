"""The serving rehearsal of ``test_benchmark.py`` on the
``gigachat3_5_432b_a28b`` configuration, at a toy size on the CPU: the
configuration's own driver, reference and limits' keys, a
GigaChat-3.5-shaped toy model (a dense GatedDeltaNet layer, then a
GatedDeltaNet and a latent expert layer, hidden 64, 4 of 16 routed experts
held, top-4) in float32. A sound run comes out correct and the control in
the precision below does not, through the harness's own run and verdict;
each planted fault of ``fault_readings_gdn.py`` reaches the program and not
the reference, and what it serves comes out not correct by the harness's
verdict. Also the configuration's file against the catalog's row key by
key, the new traffic and metric files by the rules ``benchmark/README.md``
states, and the arithmetic of ``work_gdn_mla_moe.py`` against hand-worked
values.
"""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (fault_readings_gdn, run as run_mod,  # noqa: E402
                       traffic as traffic_mod, work_gdn_mla_moe as work)

BENCH = run_mod.load_json("BENCHMARK.json")
ENTRY = next(c for c in BENCH["configs"]
             if c["name"] == "gigachat3_5_432b_a28b")
CELL = next(c for c in BENCH["workloads"] if c["config"] == ENTRY["name"])
CONFIG = run_mod.load_json(ENTRY["file"])
TRAFFIC = run_mod.load_json("benchmark", "traffic", CELL["traffic"] + ".json")
MINE = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL["name"]]]

TOY_MODEL = dict(CONFIG["model"], vocab_size=97, d_model=64, num_layers=3,
                 num_heads=4, d_ff=96, max_context=128,
                 full_attention_layers=[2], q_lora_rank=24,
                 kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
                 v_head_dim=8, rope_theta=10000.0,
                 rope_scaling=dict(CONFIG["model"]["rope_scaling"],
                                   original_max_position_embeddings=16),
                 linear_num_key_heads=2, linear_num_value_heads=4,
                 linear_key_head_dim=16, linear_value_head_dim=16,
                 router_experts=16, first_routed_expert=4,
                 n_routed_experts=4, num_experts_per_tok=4,
                 moe_intermediate_size=32, torch_dtype="float32")
TOY_CHAT = {"arrivals": {"law": "closed", "clients": 8,
                         "requests_per_second": 16},
            "lengths_seed": 24,
            "prompt_tokens": {"law": "lognormal", "median": 20,
                              "sigma": 0.8, "min": 4, "max": 64},
            "output_tokens": {"law": "lognormal", "median": 12,
                              "sigma": 0.5, "min": 4, "max": 24},
            "checked_requests": 4, "trace_after_share": 0.1,
            "trace_seconds": 0.3}
#: float32 against float32 at a toy size: round-off only
TOY_LIMIT = 1e-4


#: the published config.json (the configuration's ``source``), key by key
PUBLISHED = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 40,
    "nextn_is_sparse": False, "num_attention_heads": 64,
    "n_shared_experts": 1, "n_routed_experts": 256,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "qk_head_dim": 192, "n_group": 1, "topk_group": 1,
    "num_experts_per_tok": 8, "first_k_dense_replace": 3,
    "norm_topk_prob": True, "rope_interleave": True,
    "num_key_value_heads": 64, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32768,
                     "type": "yarn"},
    "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm",
    "layernorm_type": "pre_post", "layernorm_gating_weight": 2,
    "gated_attention": True, "use_shared_expert_sigmoid": False,
    "use_mla_scaling_factor": True,
    "linear_attention_type": "GigaChat35GatedDeltaNet",
    "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
    "linear_num_value_heads": 64,
    "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06,
    "swiglu_limit": 10, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 2, "model_type": "gigachat3_5",
    "tf_legacy_loss": False}


def toy_config():
    config = json.loads(json.dumps(CONFIG))
    config["model"] = dict(TOY_MODEL)
    # two slots for eight clients: every slot is used again and again
    config["engine"] = {"dtype": "float32", "max_seqs": 2, "block_size": 16}
    config["limits"] = dict(config["limits"], served_logit_gap=TOY_LIMIT)
    return config


def test_configuration_file_keeps_the_published_numbers():
    row = {"config": PUBLISHED}
    assert CONFIG["source"] == ENTRY["source"] == (
        "https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/"
        "config.json")
    assert CONFIG["reduced"] == ENTRY["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "full_attention_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"]
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["full_attention_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"], CONFIG["max_position_embeddings"]) \
        == (5, 1, [4], 16, 16032, 4096)
    assert CONFIG["n_routed_experts_published"] \
        == row["config"]["n_routed_experts"] == 256
    assert row["config"]["vocab_size"] == 8 * CONFIG["vocab_size"]
    model = CONFIG["model"]
    for ours, theirs in (("d_model", "hidden_size"),
                         ("num_layers", "num_hidden_layers"),
                         ("num_heads", "num_attention_heads"),
                         ("d_ff", "intermediate_size"),
                         ("max_context", "max_position_embeddings"),
                         ("router_experts", "n_routed_experts_published"),
                         ("vocab_size", "vocab_size")):
        assert model[ours] == CONFIG[theirs], ours
    for key in model:
        if key in row["config"] and key not in CONFIG["reduced"]:
            assert model[key] == row["config"][key], key
    assert model["n_routed_experts"] == 16 \
        and model["first_routed_expert"] == 0
    from mxnet_tpu.serving.llm.model import DecoderConfig, TinyDecoder
    assert set(model) == set(DecoderConfig.FIELDS
                             + DecoderConfig.FIELDS_OF[model["model_type"]])
    decoder = TinyDecoder(DecoderConfig(**model))
    assert decoder.cache_layout["state"]["gdn"] == (4, 64, 128, 128)
    # the four readings and MTP, said so
    assert {"norm_type", "swiglu_limit", "gated_attention",
            "router"} <= set(CONFIG["assumed"])
    assert "mtp" in CONFIG["departures"]
    reckoned = CONFIG["reckoned_bytes"]
    layers = reckoned["layer0_params"] \
        + 3 * reckoned["gdn_expert_layer_params"] \
        + reckoned["latent_expert_layer_params"]
    assert 2 * (layers + reckoned["embedding_and_head_params"]) \
        == reckoned["weights_bf16"] == 9463746560
    assert reckoned["state_pools_f32"] == 128 * 4 * (
        64 * 128 * 128 + 3 * 16384) * 4
    assert reckoned["latent_pool_bf16"] == CONFIG["observed_defaults"][
        "num_blocks"] * 16 * 640 * 2
    assert CONFIG["engine"] == {"dtype": "bfloat16", "max_seqs": 128}
    assert CONFIG["control_precision"] == "float8_e5m2"


def test_the_parameters_the_program_is_given_are_the_reckoned_ones():
    """The reference's tree at the cell's shapes, by shape alone: 4.732B
    parameters, as ``reckoned_bytes`` says."""
    import importlib
    import jax
    import numpy as np
    ref = importlib.import_module("benchmark.reference."
                                  + CONFIG["reference"])
    shapes = jax.eval_shape(lambda: ref.init_params(0, CONFIG["model"]))
    total = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    reckoned = CONFIG["reckoned_bytes"]
    assert 2 * total == reckoned["weights_bf16"]


def test_new_traffic_and_metric_files_load_by_the_readme_rules():
    assert TRAFFIC["arrivals"] == dict(TRAFFIC["arrivals"], law="closed",
                                       clients=256)
    assert TRAFFIC["arrivals"]["clients"] == 2 * CONFIG["engine"]["max_seqs"]
    assert TRAFFIC["prompt_tokens"] == {"law": "lognormal", "median": 256,
                                        "sigma": 0.8, "min": 16,
                                        "max": 2048}
    assert TRAFFIC["output_tokens"] == {"law": "lognormal", "median": 512,
                                        "sigma": 0.5, "min": 64,
                                        "max": 1024}
    assert TRAFFIC["checked_requests"] == 6
    assert {"source", "lengths_seed", "checked_requests",
            "trace_after_share", "trace_seconds", "assumed",
            "why"} <= set(TRAFFIC)
    plan = traffic_mod.requests(3, TRAFFIC, CONFIG["model"]["vocab_size"],
                                45)
    assert len(plan) == max(256, round(
        TRAFFIC["arrivals"]["requests_per_second"] * 45))
    assert all(due == 0 and 64 <= n <= 1024 and 16 <= len(p) <= 2048
               for due, p, n in plan)
    assert max(len(p) + n for _, p, n in plan) \
        <= CONFIG["model"]["max_context"]
    assert all(t < CONFIG["model"]["vocab_size"] for _, p, _ in plan
               for t in p)
    # the cell, its metrics and their files
    assert CELL["chips"] == 1 \
        and CELL["traffic"] == "reason_in256_out512_closed256"
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "serve.out_tokens_per_s")
    assert CELL["name"] in e2e["workloads"]
    assert [m["name"] for m in MINE] == [
        "step.mfu.serve.gdn_mla_moe", "kernel.gdn_state_roofline.serve",
        "kernel.moe_experts_roofline.serve.gdn_mla_moe",
        "moe.rows_per_expert_hit.serve.gdn_mla_moe"]
    for m in MINE:
        assert m["moves"] == "serve.out_tokens_per_s"
        assert callable(run_mod.layer_reader(m["name"]))


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark", "reference",
                        CONFIG["reference"] + ".py")
    source = open(path).read()
    assert "import mxnet_tpu" not in source \
        and "from mxnet_tpu" not in source


def test_operations_and_bytes_of_the_published_shapes():
    model = CONFIG["model"]
    assert work.layer_counts(model) == (4, 1, 1, 4)
    # q, k 2 x 7168 x 4096; v, z 2 x 7168 x 8192; a, b 2 x 7168 x 64;
    # out 8192 x 7168
    assert work.gdn_projection_macs(model) == 235864064 - 16384 * 4
    assert work.gdn_conv_macs(model) == 16384 * 4
    assert work.delta_rule_flops(model) == 64 * 7 * 128 * 128
    assert work.latent_projection_macs(model) == 101122048 + 58720256
    at0 = work.decoder_flops_per_token(model, 0)
    assert at0 == 4 * (2 * 235864064 + 64 * 7 * 16384) \
        + 2 * (101122048 + 58720256) + 2 * 396361728 \
        + 4 * 2 * (7168 * 256 + 44040192) + 2 * 7168 * 16032
    # scores and values: 2 x ctx x 64 x (192 + 128), one latent layer
    assert work.decoder_flops_per_token(model, 1000) - at0 \
        == 2 * 1000 * 64 * 320
    assert work.expert_bytes(model) == 2 * 44040192
    assert work.expert_pair_flops(model) == 2 * 44040192
    assert work.state_bytes_per_row(model) == 4 * 64 * 128 * 128 * 4
    assert work.state_step_bytes(model, 128) == 2 * 128 * 16777216
    assert work.delta_rule_step_flops(model, 10) == 10 * 4 * 64 * 7 * 16384


def test_readers_of_the_new_metrics_find_nothing_in_an_empty_run():
    seen = {"traced": None, "peak": run_mod.peak_of("TPU v5 lite"),
            "trace": {"by_family": {}, "by_category": {}, "window_s": 1.0},
            "config": CONFIG, "counters": {}, "chips": 1}
    assert len(MINE) == 4
    for m in MINE:
        assert run_mod.layer_reader(m["name"])(seen) is None, m["name"]
    # nor in another block's traced run: they read their own model alone
    other = next(run_mod.load_json(c["file"]) for c in BENCH["configs"]
                 if c["name"] == "moonlight_16b_a3b")
    seen = dict(seen, config=other, traced={"steps": [[(1, 10)]]},
                trace={"by_family": {"_grouped_swiglu_pallas": 1.0},
                       "window_s": 1.0})
    for m in MINE:
        assert run_mod.layer_reader(m["name"])(seen) is None, m["name"]


@pytest.fixture(scope="module")
def rehearsal():
    """One short traced run of the toy cell with the control read beside
    it, the recorded trace one that names the two kernels: ``(what the
    driver returned, the result line)``."""
    from benchmark import trace as trace_mod
    from mxnet_tpu.observability.tracing import get_tracer
    import contextlib

    @contextlib.contextmanager
    def recording():
        held = {}
        yield held
        held["trace"] = {"devices": {"/device:TPU:0": [
            ["_grouped_swiglu_pallas.3", 0, 400_000_000,
             "custom-call.tpu_custom_call"],
            ["_gdn_state_pallas.2", 400_000_000, 500_000_000,
             "custom-call.tpu_custom_call"]]}, "host": []}
    patch = pytest.MonkeyPatch()
    patch.setattr(trace_mod, "recording", recording)
    tracer = get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enable()
    try:
        config = toy_config()
        out, devices = run_mod.drive(
            CELL, config, dict(TOY_CHAT), 2 ** 31 + 17, 1.0, True,
            require_chip=False, t_start=time.monotonic(),
            control=CONFIG["control_precision"])
        result = run_mod.assemble(BENCH, CELL, config, TOY_CHAT, out,
                                  devices, True,
                                  peak=run_mod.peak_of("TPU v5 lite"))
    finally:
        patch.undo()
        if not was_on:
            tracer.disable()
        tracer.clear()
    return out, result


def test_sound_rehearsal_is_correct_and_the_control_is_not(rehearsal):
    out, result = rehearsal
    program, control = out["checks"], out["control_checks"]
    assert out["notes"]["tokens_compared"] > 0
    assert out["notes"]["requests"] == 16 > 2   # slots are used again
    gap = program["served_logit_gap"]
    assert gap["value"] <= gap["limit"] == TOY_LIMIT
    assert run_mod.verdict(program) is True and result["correct"] is True
    assert control["served_logit_gap"]["value"] > 100 * gap["limit"]
    assert run_mod.verdict(control) is False
    assert out["notes"]["observed_defaults"]["prefix_cache"] is False
    assert out["notes"]["counts"]["prefix_hits"] == 0


def test_traced_rehearsal_reads_the_new_metrics(rehearsal):
    """The four new metrics and the list-less serving metrics are read,
    no share over 100%, and the other blocks' shares are not asked of
    this cell."""
    out, result = rehearsal
    assert out["traced"]["steps"]
    got = result["metrics"]
    for m in MINE:
        assert got[m["name"]]["value"] > 0, m["name"]
        if m["unit"] == "%":
            assert got[m["name"]]["value"] <= 100, m["name"]
    for name in ("scheduler.decode_rows_per_step.serve",
                 "scheduler.step_fill_share.serve",
                 "engine.launch_ms.serve", "engine.commit_ms.serve",
                 "kv_cache.blocks_used_share.serve"):
        assert got[name]["value"] > 0, name
    # 4 of 16 experts held, 4 a token: a held expert takes a few rows
    assert 1 <= got["moe.rows_per_expert_hit.serve.gdn_mla_moe"][
        "value"] <= 2 * 16 * 4
    for name in ("step.mfu.serve", "step.mfu.serve.mla_moe",
                 "step.mfu.serve.ssm_hybrid", "ssm.state_rows_per_step.serve",
                 "kernel.moe_experts_roofline.serve",
                 "kernel.ssd_state_roofline.serve",
                 "moe.experts_hit_share.serve"):
        assert name not in got


#: two requests through one slot, the second after the first
PROMPTS = [list(range(5, 85)), list(range(50, 58))]


def _served(params, model=None, prompts=PROMPTS):
    """``prompts`` served one after the other through ONE slot, by
    ``model`` or by a model of its own (a model keeps its traced step
    programs: a fault planted in the step needs a new one)."""
    from mxnet_tpu.serving.llm import LLMEngine, Sequence
    from mxnet_tpu.serving.llm import model as program
    if model is None:
        model = program.TinyDecoder(program.DecoderConfig(**TOY_MODEL))
    engine = LLMEngine(model, params, max_seqs=1, block_size=16)
    out = []
    for prompt in prompts:
        seq = Sequence(list(prompt), 6)
        engine.add(seq)
        while engine.has_work():
            engine.step()
        out.append(list(seq.generated))
    return out


@pytest.fixture(scope="module")
def sound_served():
    """The sound weights and what the sound program serves them."""
    import importlib
    ref = importlib.import_module("benchmark.reference."
                                  + CONFIG["reference"])
    from mxnet_tpu.serving.llm import model as program
    sound = ref.init_params(7, TOY_MODEL)
    model = program.TinyDecoder(program.DecoderConfig(**TOY_MODEL))
    return sound, model, _served(sound, model)


@pytest.mark.parametrize("fault", fault_readings_gdn.FAULTS)
def test_planted_fault_reaches_the_program_and_is_not_correct(
        fault, sound_served):
    """``benchmark/fault_readings_gdn.py`` at a toy size, without a run:
    two requests through ONE slot, one after the other, with the fault
    planted. What each plant hands the program moves what it serves off
    the sound model's first choices by far more than the toy's limit, so
    the harness's verdict on the number compared is not correct; the
    reference is never handed the fault, and the plant comes out again."""
    import importlib
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import gdn
    from mxnet_tpu.serving.llm import model as program
    ref = importlib.import_module("benchmark.reference."
                                  + CONFIG["reference"])
    sound, model, want = sound_served
    before = (program.gdn_state_step, program.DecoderConfig,
              gdn._delta_write, ref.init_params, ref.served_token_gaps)
    unplant = fault_readings_gdn.plant(fault, ref, TOY_MODEL)
    try:
        given = ref.init_params(7, TOY_MODEL)
        # a fault in the weights runs the sound model's programs
        got = _served(given, model if fault in (
            "decay_dropped", "shared_dropped") else None)
        gaps = [float(ref.served_token_gaps(given, p, g, TOY_MODEL,
                                            128).max())
                for p, g in zip(PROMPTS, got)]
        sound_gaps = ref.served_token_gaps(given, PROMPTS[1], want[1],
                                           TOY_MODEL, 128)
    finally:
        unplant()
    assert (program.gdn_state_step, program.DecoderConfig,
            gdn._delta_write, ref.init_params,
            ref.served_token_gaps) == before
    # the reference is never handed the fault ...
    assert float(sound_gaps.max()) <= TOY_LIMIT
    # ... and the program is: in its weights, its configuration or its
    # step
    moved = {k for lp, lq in zip(sound["layers"], given["layers"])
             for k in lp if not np.array_equal(
                 np.asarray(lp[k], np.float32),
                 np.asarray(lq[k], np.float32))}
    assert moved == {"decay_dropped": {"A_log"},
                     "shared_dropped": {"shared_down"}}.get(fault, set())
    assert bool(jnp.array_equal(ref.init_params(7, TOY_MODEL)["layers"][0][
        "A_log"], sound["layers"][0]["A_log"]))
    # what the slot's second occupant is served (the first's, where the
    # fault shows from a sequence's first token) is not correct
    checks = {"served_logit_gap": {"value": max(gaps), "limit": TOY_LIMIT}}
    assert run_mod.verdict(checks) is False, gaps
    assert max(gaps) > 100 * TOY_LIMIT
