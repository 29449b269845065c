"""The serving rehearsal of ``test_benchmark.py`` on the
``command_a_plus_05_2026`` configuration, at a toy size on the CPU: the
configuration's own driver, reference and limits' keys, a
Command-A+-shaped toy model (three sliding-window layers of window 8 and a
full layer, hidden 64, 4 of 16 routed experts held, top-4, two shared
experts averaged) in float32, over pages of 4 tokens. A sound run comes
out correct and the control in the precision below does not, through the
harness's own run and verdict; each planted fault of
``fault_readings_window.py`` reaches the program and not the reference,
and what it serves comes out not correct by the harness's verdict. Also
the configuration's file against the catalog's row key by key, the new
traffic and metric files by the rules ``benchmark/README.md`` states, and
the arithmetic of ``work_window_moe.py`` against hand-worked values.
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

from benchmark import (fault_readings_window, run as run_mod,  # noqa: E402
                       traffic as traffic_mod, work_window_moe as work)

BENCH = run_mod.load_json("BENCHMARK.json")
ENTRY = next(c for c in BENCH["configs"]
             if c["name"] == "command_a_plus_05_2026")
CELL = next(c for c in BENCH["workloads"] if c["config"] == ENTRY["name"])
CONFIG = run_mod.load_json(ENTRY["file"])
TRAFFIC = run_mod.load_json("benchmark", "traffic", CELL["traffic"] + ".json")
MINE = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL["name"]]]

TOY_MODEL = dict(CONFIG["model"], vocab_size=97, d_model=64, num_layers=4,
                 num_heads=4, d_ff=64, max_context=128, sliding_window=8,
                 num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
                 router_experts=16, first_routed_expert=4,
                 n_routed_experts=4, num_experts_per_tok=4,
                 num_shared_experts=2, moe_intermediate_size=32,
                 torch_dtype="float32")
TOY_BLOCK = 4
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
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4,
    # three sliding layers, then a full one, 8 times
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "logit_scale": 1, "max_position_embeddings": 200000,
    "model_type": "cohere2_moe", "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 8,
    "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average",
    "sliding_window": 4096, "tf_legacy_loss": False,
    "tie_word_embeddings": True, "use_embedding_sharing": True,
    "use_gated_activation": True, "use_parallel_block": True,
    "use_parallel_embedding": False, "use_qk_norm": False,
    "vocab_size": 262144}


def toy_config():
    config = json.loads(json.dumps(CONFIG))
    config["model"] = dict(TOY_MODEL)
    # two slots for eight clients: every slot is used again and again
    config["engine"] = {"dtype": "float32", "max_seqs": 2,
                        "block_size": TOY_BLOCK}
    config["limits"] = dict(config["limits"], served_logit_gap=TOY_LIMIT)
    return config


def test_configuration_file_keeps_the_published_numbers():
    assert CONFIG["source"] == ENTRY["source"] == (
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/"
        "main/config.json")
    assert CONFIG["reduced"] == ENTRY["reduced"] == [
        "num_hidden_layers", "layer_types", "n_routed_experts",
        "vocab_size", "max_position_embeddings"]
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    for key, value in PUBLISHED.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["layer_types"],
            CONFIG["n_routed_experts"], CONFIG["vocab_size"],
            CONFIG["max_position_embeddings"]) \
        == (4, PUBLISHED["layer_types"][:4], 16, 32768, 32768)
    assert CONFIG["n_routed_experts_published"] \
        == PUBLISHED["num_experts"] == 128
    assert PUBLISHED["vocab_size"] == 8 * CONFIG["vocab_size"]
    model = CONFIG["model"]
    for ours, theirs in (("d_model", "hidden_size"),
                         ("num_layers", "num_hidden_layers"),
                         ("num_heads", "num_attention_heads"),
                         ("d_ff", "intermediate_size"),
                         ("moe_intermediate_size", "intermediate_size"),
                         ("max_context", "max_position_embeddings"),
                         ("router_experts", "num_experts"),
                         ("layer_types", "layer_types"),
                         ("vocab_size", "vocab_size")):
        assert model[ours] == CONFIG[theirs], ours
    for key in model:
        if key in PUBLISHED and key not in CONFIG["reduced"]:
            assert model[key] == PUBLISHED[key], key
    assert model["n_routed_experts"] == 16 \
        and model["first_routed_expert"] == 0
    from mxnet_tpu.serving.llm.model import DecoderConfig, TinyDecoder
    assert set(model) == set(DecoderConfig.FIELDS
                             + DecoderConfig.FIELDS_OF[model["model_type"]])
    layout = TinyDecoder(DecoderConfig(**model)).cache_layout
    assert layout == {"kind": "kv_packed", "num_heads": 8, "head_dim": 128,
                      "window_layers": [0, 1, 2], "window": 4096}
    # the uncertain readings of the published keys, and what is left out
    assert {"shared_experts", "layer_norm", "positions", "window",
            "block_size", "num_blocks"} <= set(CONFIG["assumed"])
    assert {"vision_tower", "expert_exchange"} <= set(CONFIG["departures"])
    reckoned = CONFIG["reckoned_bytes"]
    assert reckoned["layer_params"] == reckoned["attention_params"] \
        + reckoned["router_params"] + reckoned["shared_expert_params"] \
        + reckoned["held_routed_expert_params"] + 4096
    assert reckoned["total_params"] == 4 * reckoned["layer_params"] \
        + reckoned["embedding_params"] + 4096 == 4733292544
    assert reckoned["weights_bytes"] == 2 * reckoned["total_params"] \
        + 2 * 4 * reckoned["router_params"]
    assert reckoned["page_bytes"] == 64 * 2 * 8 * 128 * 2
    assert reckoned["pool_bytes"] == CONFIG["engine"]["num_blocks"] \
        * reckoned["page_bytes"]
    assert CONFIG["engine"] == {"dtype": "bfloat16", "max_seqs": 64,
                                "block_size": 64, "num_blocks": 18433}
    assert CONFIG["control_precision"] == "float8_e5m2"


def test_the_parameters_the_program_is_given_are_the_reckoned_ones():
    """The reference's tree at the cell's shapes, by shape alone: 4.733B
    parameters and 9.47 GB, as ``reckoned_bytes`` says."""
    import importlib
    import jax
    import numpy as np
    ref = importlib.import_module("benchmark.reference."
                                  + CONFIG["reference"])
    shapes = jax.eval_shape(lambda: ref.init_params(0, CONFIG["model"]))
    leaves = jax.tree_util.tree_leaves(shapes)
    reckoned = CONFIG["reckoned_bytes"]
    assert sum(int(np.prod(a.shape)) for a in leaves) \
        == reckoned["total_params"]
    assert sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves) \
        == reckoned["weights_bytes"]


def test_new_traffic_and_metric_files_load_by_the_readme_rules():
    # 64 clients on the 64 slots: at 96 the clients' floor alone closes
    # the batch above 0.95 of --seconds (the traffic file's `assumed`)
    assert TRAFFIC["arrivals"] == dict(TRAFFIC["arrivals"], law="closed",
                                       clients=64)
    assert TRAFFIC["prompt_tokens"] == {"law": "lognormal", "median": 3072,
                                        "sigma": 1.0, "min": 128,
                                        "max": 24576}
    assert TRAFFIC["output_tokens"] == {"law": "lognormal", "median": 320,
                                        "sigma": 0.8, "min": 16,
                                        "max": 1536}
    assert {"source", "lengths_seed", "checked_requests",
            "trace_after_share", "trace_seconds", "assumed",
            "why"} <= set(TRAFFIC)
    plan = traffic_mod.requests(3, TRAFFIC, CONFIG["model"]["vocab_size"],
                                45)
    assert len(plan) == max(64, round(
        TRAFFIC["arrivals"]["requests_per_second"] * 45))
    assert all(due == 0 and 16 <= n <= 1536 and 128 <= len(p) <= 24576
               for due, p, n in plan)
    longest = max(len(p) + n for _, p, n in plan)
    # the longest request passes the window, and fits the configuration
    assert CONFIG["model"]["sliding_window"] < longest \
        <= CONFIG["model"]["max_context"]
    assert all(t < CONFIG["model"]["vocab_size"] for _, p, _ in plan
               for t in p)
    # the cell, its metrics and their files
    assert CELL["chips"] == 1 \
        and CELL["traffic"] == "rag_mixed_ctx24k_closed96"
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "serve.out_tokens_per_s")
    assert e2e["workloads"].index(CELL["name"]) \
        < e2e["workloads"].index("sdar30b-chat-gen256-closed64")
    assert [m["name"] for m in MINE] == [
        "step.mfu.serve.window_moe", "kernel.window_attn_roofline.serve",
        "kernel.moe_experts_roofline.serve.window_moe",
        "kv_cache.window_pages_saved_share.serve",
        "moe.rows_per_expert_hit.serve.window_moe"]
    for m in MINE:
        assert m["moves"] == "serve.out_tokens_per_s"
        assert callable(run_mod.layer_reader(m["name"]))


def test_reference_imports_nothing_of_the_program():
    for name in (CONFIG["reference"], ):
        source = open(os.path.join(ROOT, "benchmark", "reference",
                                   name + ".py")).read()
        assert "import mxnet_tpu" not in source \
            and "from mxnet_tpu" not in source


def test_operations_and_bytes_of_the_published_shapes():
    model = CONFIG["model"]
    assert work.layer_counts(model) == (3, 1)
    # q and o 2 x 4096 x 16384, k and v 2 x 4096 x 1024
    assert work.projection_macs(model) == 142606336
    dense = 4 * 2 * (142606336 + 4096 * 128 + 201326592) \
        + 2 * 4096 * 32768
    assert work.dense_flops_per_token(model) == dense
    # a token at a context of 10,000 sees 4,096 keys on the 3 sliding
    # layers and 10,000 on the full one
    assert work.decoder_flops_per_token(model, 10000) - dense \
        == 4 * 128 * 128 * (3 * 4096 + 10000)
    assert work.expert_bytes(model) == 2 * 3 * 4096 * 4096
    assert work.expert_pair_flops(model) == 2 * 3 * 4096 * 4096
    assert work.page_bytes(model, 64) == 262144
    # one decode token at context 10,000: the window's 4,096 keys lie in
    # 65 pages of 64 (position 5,904 on), the full layer's in 157
    nbytes, ops = work.attention_row(model, 64, 1, 10000)
    assert nbytes == (3 * 65 + 157) * 262144
    assert ops == 4 * 128 * 128 * (3 * 4096 + 10000)
    # a chunk of 100 from position 0: every layer sees p + 1 keys
    nbytes, ops = work.attention_row(model, 64, 100, 100)
    assert nbytes == 4 * 2 * 262144
    assert ops == 4 * 4 * 128 * 128 * sum(range(1, 101))


def test_readers_of_the_new_metrics_find_nothing_in_an_empty_run():
    seen = {"traced": None, "peak": run_mod.peak_of("TPU v5 lite"),
            "trace": {"by_family": {}, "by_category": {}, "window_s": 1.0},
            "config": CONFIG, "counters": {}, "chips": 1}
    assert len(MINE) == 5
    for m in MINE:
        assert run_mod.layer_reader(m["name"])(seen) is None, m["name"]
    # nor in another block's traced run: they read their own model alone
    other = next(run_mod.load_json(c["file"]) for c in BENCH["configs"]
                 if c["name"] == "gigachat3_5_432b_a28b")
    seen = dict(seen, config=other, traced={"steps": [[(1, 10)]]},
                trace={"by_family": {"_grouped_swiglu_pallas": 1.0,
                                     "_gqa_window_pallas": 1.0},
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
            ["_gqa_window_pallas.2", 400_000_000, 500_000_000,
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
            CELL, config, dict(TOY_CHAT), 2 ** 31 + 19, 1.0, True,
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
    """The five new metrics and the list-less serving metrics are read,
    no share over 100%, and the other blocks' shares are not asked of
    this cell."""
    out, result = rehearsal
    assert out["traced"]["steps"]
    got = result["metrics"]
    for m in MINE:
        if m["unit"] == "%":
            assert 0 < got[m["name"]]["value"] <= 100, m["name"]
        else:       # rows a held expert serves: one at the least
            assert got[m["name"]]["value"] >= 1, m["name"]
    for name in ("scheduler.decode_rows_per_step.serve",
                 "scheduler.step_fill_share.serve",
                 "engine.launch_ms.serve", "engine.commit_ms.serve",
                 "kv_cache.blocks_used_share.serve"):
        assert got[name]["value"] > 0, name
    for name in ("step.mfu.serve", "step.mfu.serve.gdn_mla_moe",
                 "kernel.gqa_attn_roofline.serve",
                 "kernel.moe_experts_roofline.serve",
                 "moe.rows_per_expert_hit.serve.gdn_mla_moe"):
        assert name not in got


#: two requests through one slot, the second after the first; the first
#: passes the toy's window of 8 many times over
PROMPTS = [list(range(5, 85)), list(range(50, 58))]


def _served(params, model=None, prompts=PROMPTS):
    """``prompts`` served one after the other through ONE slot, by
    ``model`` or by a model of its own (a model keeps its traced step
    programs: a fault planted in the step needs a new one)."""
    from mxnet_tpu.serving.llm import LLMEngine, Sequence
    from mxnet_tpu.serving.llm import model as program
    if model is None:
        model = program.TinyDecoder(program.DecoderConfig(**TOY_MODEL))
    engine = LLMEngine(model, params, max_seqs=1, block_size=TOY_BLOCK)
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


@pytest.mark.parametrize("fault", fault_readings_window.FAULTS)
def test_planted_fault_reaches_the_program_and_is_not_correct(
        fault, sound_served):
    """``benchmark/fault_readings_window.py`` at a toy size, without a
    run: two requests through ONE slot, the first of 80 positions against
    a window of 8, with the fault planted. What each plant hands the
    program moves what it serves off the sound model's first choices by
    far more than the toy's limit, so the harness's verdict on the number
    compared is not correct; the reference is never handed the fault, and
    the plant comes out again."""
    import importlib
    import numpy as np
    from mxnet_tpu.serving.llm import model as program
    ref = importlib.import_module("benchmark.reference."
                                  + CONFIG["reference"])
    sound, model, want = sound_served
    before = (program.DecoderConfig, program.TinyDecoder._qkv_window,
              ref.init_params, ref.served_token_gaps)
    unplant = fault_readings_window.plant(fault, ref, TOY_MODEL, TOY_BLOCK)
    try:
        given = ref.init_params(7, TOY_MODEL)
        # a fault in the weights runs the sound model's programs
        got = _served(given, model if fault == "shared_summed" else None)
        gaps = [float(ref.served_token_gaps(given, p, g, TOY_MODEL,
                                            128).max())
                for p, g in zip(PROMPTS, got)]
        sound_gaps = ref.served_token_gaps(given, PROMPTS[0], want[0],
                                           TOY_MODEL, 128)
    finally:
        unplant()
    assert (program.DecoderConfig, program.TinyDecoder._qkv_window,
            ref.init_params, ref.served_token_gaps) == before
    # the reference is never handed the fault ...
    assert float(sound_gaps.max()) <= TOY_LIMIT
    # ... and the program is: in its weights, its configuration or its
    # block
    moved = {k for lp, lq in zip(sound["layers"], given["layers"])
             for k in lp if not np.array_equal(
                 np.asarray(lp[k], np.float32),
                 np.asarray(lq[k], np.float32))}
    assert moved == ({"shared_down"} if fault == "shared_summed"
                     else set())
    # what the first request, past the window, is served is not correct
    checks = {"served_logit_gap": {"value": max(gaps), "limit": TOY_LIMIT}}
    assert run_mod.verdict(checks) is False, gaps
    assert max(gaps) > 100 * TOY_LIMIT
