"""The serving rehearsal of ``test_benchmark.py`` on the
``sdar_30b_a3b_chat`` configuration, at a toy size on the CPU: the
configuration's own driver, reference and limits' keys, an
SDAR-shaped toy model (two layers, hidden 64, 4 query heads over 2 of 16
with q/k norms, 8 experts of 32 top-2, blocks of 4 revealed in 4 passes)
in float32. A sound run comes out correct; the control in the precision
below, each planted fault of ``fault_readings_blockdiff.py`` and a token
altered where it is produced do not, each through the harness's own
verdict. Also the configuration's file against the catalog's row key by
key, the new traffic and metric files by the rules ``benchmark/README.md``
states, and the arithmetic of ``work_blockdiff_moe.py`` against
hand-worked values.
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

from benchmark import (fault_readings_blockdiff, run as run_mod,  # noqa: E402
                       traffic as traffic_mod, work_blockdiff_moe as work)

BENCH = run_mod.load_json("BENCHMARK.json")
ENTRY = next(c for c in BENCH["configs"] if c["name"] == "sdar_30b_a3b_chat")
CELL = next(c for c in BENCH["workloads"] if c["config"] == ENTRY["name"])
CONFIG = run_mod.load_json(ENTRY["file"])
TRAFFIC = run_mod.load_json("benchmark", "traffic", CELL["traffic"] + ".json")
MINE = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL["name"]]]

TOY_MODEL = dict(CONFIG["model"], vocab_size=509, d_model=64, num_layers=2,
                 num_heads=4, num_key_value_heads=2, head_dim=16, d_ff=96,
                 max_context=128, num_experts=8, num_experts_per_tok=2,
                 moe_intermediate_size=32, mask_token_id=508,
                 torch_dtype="float32")
TOY_CHAT = {"arrivals": {"law": "closed", "clients": 8,
                         "requests_per_second": 16},
            "lengths_seed": 24,
            "prompt_tokens": {"law": "lognormal", "median": 20,
                              "sigma": 0.7, "min": 2, "max": 64},
            "output_tokens": {"law": "fixed", "value": 18},
            "checked_requests": 4, "trace_after_share": 0.1,
            "trace_seconds": 0.3}
#: float32 against float32 at a toy size: round-off only
TOY_LIMIT = 1e-4


def toy_config():
    config = json.loads(json.dumps(CONFIG))
    config["model"] = dict(TOY_MODEL)
    # three slots for eight clients: every slot is used again and again
    config["engine"] = {"dtype": "float32", "max_seqs": 3, "block_size": 8}
    config["observed_defaults"] = dict(config["observed_defaults"],
                                       block_size=8)
    config["limits"] = dict(config["limits"], served_logit_gap=TOY_LIMIT)
    return config


def test_configuration_file_keeps_the_published_numbers():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not in this sandbox")
    row = next(json.loads(line) for line in open(catalog)
               if '"SDAR-30B-A3B-Chat"' in line)
    assert CONFIG["source"] == ENTRY["source"] == row["source_url"]
    assert CONFIG["reduced"] == ENTRY["reduced"] \
        == ["num_hidden_layers", "max_position_embeddings"]
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 7 \
        < row["config"]["num_hidden_layers"]
    assert CONFIG["max_position_embeddings"] == 4096 \
        < row["config"]["max_position_embeddings"]
    model = CONFIG["model"]
    for ours, theirs in (("d_model", "hidden_size"),
                         ("num_layers", "num_hidden_layers"),
                         ("num_heads", "num_attention_heads"),
                         ("d_ff", "intermediate_size"),
                         ("max_context", "max_position_embeddings")):
        assert model[ours] == CONFIG[theirs]
    for key in model:
        if key in row["config"]:
            assert model[key] == row["config"][key], key
    from mxnet_tpu.serving.llm.model import DecoderConfig, TinyDecoder
    assert set(model) == set(DecoderConfig.FIELDS
                             + DecoderConfig.FIELDS_OF[model["model_type"]])
    decoder = TinyDecoder(DecoderConfig(**model))
    assert decoder.cache_layout == {"kind": "kv_packed", "num_heads": 4,
                                    "head_dim": 128}
    assert decoder.block_diffusion == {
        "block_length": 4, "mask_token_id": 151669, "denoising_steps": 4,
        "remasking_strategy": "sequential", "confidence_threshold": 0.9}
    for key in ("block_length", "denoising_steps", "mask_token_id",
                "confidence_threshold", "remasking_strategy"):
        assert key not in row["config"]         # assumed, and said so
    assert {"generation", "remasking_strategy", "q_norm_k_norm",
            "logits"} <= set(CONFIG["assumed"])
    # the file's own reckoning of what the chip holds
    reckoned = CONFIG["reckoned_bytes"]
    assert reckoned["layer_params"] == 18874368 + 262144 + 603979776 \
        + 2 * 2048 + 2 * 128
    assert reckoned["weights_bf16"] == 2 * (
        7 * reckoned["layer_params"]
        + reckoned["embedding_and_head_params"] + 2048)
    assert reckoned["kv_pool_bf16"] == CONFIG["observed_defaults"][
        "num_blocks"] * 16 * 7 * 2 * 4 * 128 * 2
    assert CONFIG["engine"] == {"dtype": "bfloat16", "max_seqs": 32}
    assert CONFIG["control_precision"] == "float8_e5m2"


def test_new_traffic_and_metric_files_load_by_the_readme_rules():
    # the mix: the one generator's laws, a closed batch, fixed outputs
    assert TRAFFIC["arrivals"]["law"] in traffic_mod.ARRIVAL_LAWS
    assert TRAFFIC["arrivals"]["clients"] == 2 * CONFIG["engine"]["max_seqs"]
    for part in ("prompt_tokens", "output_tokens"):
        assert TRAFFIC[part]["law"] in traffic_mod.LENGTH_LAWS
    assert TRAFFIC["output_tokens"] == {"law": "fixed", "value": 256}
    assert {"source", "lengths_seed", "checked_requests",
            "trace_after_share", "trace_seconds", "assumed",
            "why"} <= set(TRAFFIC)
    plan = traffic_mod.requests(3, TRAFFIC, 151936, 45)
    assert len(plan) == round(
        TRAFFIC["arrivals"]["requests_per_second"] * 45)
    assert all(due == 0 and n == 256 and 16 <= len(p) <= 1024
               for due, p, n in plan)
    assert max(len(p) + n for _, p, n in plan) \
        <= CONFIG["model"]["max_context"]
    # the cell, its metrics and their files
    assert CELL["chips"] == 1 and CELL["traffic"] == "chat_gen256_closed64"
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "serve.out_tokens_per_s")
    assert e2e["workloads"][-1] == CELL["name"]
    assert [m["name"] for m in MINE] == [
        "step.mfu.serve.blockdiff_moe",
        "kernel.moe_experts_roofline.serve.blockdiff_moe",
        "kernel.gqa_attn_roofline.serve.blockdiff_moe",
        "blockdiff.tokens_per_pass.serve",
        "moe.experts_hit_share.serve.blockdiff_moe"]
    layers = {m["layer"] for m in BENCH["per_layer"] if m not in MINE}
    for m in MINE:
        assert m["moves"] == "serve.out_tokens_per_s"
        assert m["layer"] in layers         # a layer PERF.md already names
        assert callable(run_mod.layer_reader(m["name"]))


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark", "reference",
                        CONFIG["reference"] + ".py")
    source = open(path).read()
    assert "import mxnet_tpu" not in source \
        and "from mxnet_tpu" not in source


def test_operations_and_bytes_of_the_published_shapes():
    model = CONFIG["model"]
    assert work.attention_projection_macs(model) == 18874368
    assert work.expert_macs(model) == 3 * 2048 * 768
    at0 = work.decoder_flops_per_token(model, 0)
    assert at0 == 2 * (7 * (18874368 + 2048 * 128 + 8 * 4718592)
                       + 2048 * 151936)
    # scores and values: 2 x ctx x 32 x 256 a layer
    assert work.decoder_flops_per_token(model, 1000) - at0 \
        == 7 * 2 * 1000 * 32 * 256
    # a block fed whole sees its context; a chunk of 388 half of itself less
    assert work.mean_context(model, 4, 300) == 300
    assert work.mean_context(model, 388, 388) == 388 - 192
    assert work.attention_flops(model, 4, 300) == 7 * 4 * 2 * 300 * 32 * 256
    # 32 sequences at 330 tokens: 21 pages each, 7 layers of 2 x 4 x 128
    assert work.kv_cache_bytes(model, [330] * 32, 16) \
        == 7 * 32 * 21 * 16 * 1024 * 2
    assert work.expert_bytes(model) == 9437184
    assert work.expert_pair_flops(model) == 2 * 4718592
    assert work.experts_held(model) == 7 * 128


def test_readers_of_the_new_metrics_find_nothing_in_an_empty_run():
    seen = {"traced": None, "peak": run_mod.peak_of("TPU v5 lite"),
            "trace": {"by_family": {}, "by_category": {}, "window_s": 1.0},
            "config": CONFIG, "counters": {}, "chips": 1}
    assert len(MINE) == 5
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
            ["_gqa_flat_pallas.2", 400_000_000, 500_000_000,
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
    assert out["notes"]["tokens_compared"] == 4 * 18
    assert out["notes"]["requests"] == 16 > 3   # slots are used again
    gap = program["served_logit_gap"]
    assert gap["value"] <= gap["limit"] == TOY_LIMIT
    assert program["wrong_length"]["value"] == 0
    assert run_mod.verdict(program) is True and result["correct"] is True
    assert control["served_logit_gap"]["value"] > 100 * gap["limit"]
    assert run_mod.verdict(control) is False
    assert out["notes"]["observed_defaults"]["prefix_cache"] is False
    assert out["notes"]["counts"]["prefix_hits"] == 0
    # every answer 18 tokens: 288 of them, through steps that each held a
    # generating row
    counts = out["notes"]["counts"]
    assert counts["tokens_generated"] == 16 * 18
    assert 0 < counts["decode_steps"] < counts["tokens_generated"]


def test_traced_rehearsal_reads_the_new_metrics(rehearsal):
    """The five new metrics and the list-less serving metrics are read,
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
                 "scheduler.schedule_ms.serve", "engine.build_ms.serve",
                 "engine.launch_ms.serve", "engine.commit_ms.serve",
                 "server.queue_wait_ms.serve",
                 "kv_cache.blocks_used_share.serve"):
        assert got[name]["value"] > 0, name
    # a block of 4 in 4 passes and a settling pass: 0.8, less where a
    # first block holds prompt tokens (the registry is the process's: a
    # test file run before this one may have left passes of its own)
    assert 0.5 < got["blockdiff.tokens_per_pass.serve"]["value"] < 4
    for name in ("step.mfu.serve", "step.mfu.serve.mla_moe",
                 "step.mfu.serve.ssm_hybrid",
                 "kernel.moe_experts_roofline.serve",
                 "kernel.gqa_attn_roofline.serve",
                 "moe.experts_hit_share.serve"):
        assert name not in got


@pytest.mark.parametrize("fault", fault_readings_blockdiff.FAULTS)
def test_planted_fault_is_not_correct_by_the_harness_verdict(fault):
    """``benchmark/fault_readings_blockdiff.py`` at a toy size: the cell's
    own run with the fault planted comes out not correct, by the number
    compared and by no other check, and the plant is taken out again."""
    from mxnet_tpu.serving.llm import engine as engine_mod, model as program
    before = (program._block_last, program.route_softmax_topk,
              engine_mod.LLMEngine._commit)
    got = fault_readings_blockdiff.read_fault(
        CELL, toy_config(), dict(TOY_CHAT), 2 ** 31 + 29, 0.5, fault,
        require_chip=False)
    assert (program._block_last, program.route_softmax_topk,
            engine_mod.LLMEngine._commit) == before
    assert got["correct"] is False
    assert got["served_logit_gap"] > 100 * TOY_LIMIT == 100 * got["limit"]
    assert got["tokens"] == 4 * 18


def test_fault_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from mxnet_tpu.serving.llm import server as server_mod
    real = server_mod.GenerationResult.__init__

    def altered(self, tokens, *a, **k):
        tokens = list(tokens)
        tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 509
        real(self, tokens, *a, **k)
    monkeypatch.setattr(server_mod.GenerationResult, "__init__", altered)
    config = toy_config()
    out, devices = run_mod.drive(CELL, config, dict(TOY_CHAT),
                                 2 ** 31 + 23, 0.5, False,
                                 require_chip=False,
                                 t_start=time.monotonic())
    result = run_mod.assemble(BENCH, CELL, config, TOY_CHAT, out, devices,
                              False)
    assert result["correct"] is False
    assert result["checks"]["served_logit_gap"]["value"] > 1e-3
    assert set(result["metrics"]) == {"serve.out_tokens_per_s", "setup_s"}
