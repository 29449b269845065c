"""The serving rehearsal of ``test_benchmark.py`` on the
``moonlight_16b_a3b`` configuration, at a toy size on the CPU: the
configuration's own driver, reference and limits' keys, a
DeepSeek-V3-shaped toy model (2 expert layers after a dense one, 8
experts top-2, hidden 64, latent 16 + 8 rotary) in float32. A sound run
comes out correct; the control in the precision below and a token altered
where it is produced do not. Also the configuration's file against the
catalog's rules and the arithmetic of ``work_mla_moe.py`` against
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

from benchmark import (fault_readings, run as run_mod,  # noqa: E402
                       work_mla_moe)

BENCH = run_mod.load_json("BENCHMARK.json")
ENTRY = next(c for c in BENCH["configs"] if c["name"] == "moonlight_16b_a3b")
CELL = next(c for c in BENCH["workloads"] if c["config"] == ENTRY["name"])
CONFIG = run_mod.load_json(ENTRY["file"])

TOY_MODEL = dict(CONFIG["model"], vocab_size=97, d_model=64, num_layers=3,
                 num_heads=4, d_ff=96, max_context=128, kv_lora_rank=16,
                 qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                 n_routed_experts=8, num_experts_per_tok=2,
                 moe_intermediate_size=32, torch_dtype="float32")
TOY_CHAT = {"arrivals": {"law": "closed", "clients": 16,
                         "requests_per_second": 48},
            "lengths_seed": 24,
            "prompt_tokens": {"law": "lognormal", "median": 20,
                              "sigma": 0.9, "min": 4, "max": 64},
            "output_tokens": {"law": "geometric", "mean": 8, "min": 2,
                              "max": 24},
            "checked_requests": 4, "trace_after_share": 0.1,
            "trace_seconds": 0.3}


def toy_config():
    config = json.loads(json.dumps(CONFIG))
    config["model"] = dict(TOY_MODEL)
    # two slots: a ladder of 12 programs to warm in place of 16
    config["engine"] = {"dtype": "float32", "max_seqs": 2}
    # float32 against float32 at a toy size: round-off only
    config["limits"] = dict(config["limits"], served_logit_gap=1e-4)
    return config


def test_configuration_file_keeps_the_published_numbers():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not in this sandbox")
    row = next(json.loads(line) for line in open(catalog)
               if '"Moonlight-16B-A3B"' in line)
    assert CONFIG["source"] == ENTRY["source"] == row["source_url"]
    assert CONFIG["reduced"] == ENTRY["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == CONFIG["model"]["num_layers"] == 9
    model = CONFIG["model"]
    for ours, theirs in (("d_model", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("d_ff", "intermediate_size"),
                         ("max_context", "max_position_embeddings")):
        assert model[ours] == row["config"][theirs]
    for key in model:
        if key in row["config"] and key != "num_hidden_layers":
            assert model[key] == row["config"][key], key
    from mxnet_tpu.serving.llm.model import DecoderConfig
    assert set(model) <= set(DecoderConfig.FIELDS
                             + DecoderConfig.BLOCK_FIELDS)
    assert DecoderConfig(**model).latent_dim == 576


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark", "reference",
                        CONFIG["reference"] + ".py")
    source = open(path).read()
    assert "mxnet_tpu" not in source.replace(
        "``mxnet_tpu", "").replace("mxnet_tpu``", "") \
        or "import mxnet_tpu" not in source and "from mxnet_tpu" not in source


def test_operations_and_bytes_of_the_published_shapes():
    model = CONFIG["model"]
    assert work_mla_moe.expert_layers(model) == 8
    assert work_mla_moe.attention_projection_macs(model) == 13762560
    assert work_mla_moe.expert_bytes(model) == 3 * 2048 * 1408 * 2
    assert work_mla_moe.expert_pair_flops(model) == 2 * 3 * 2048 * 1408
    # a step of 8 sequences at 1000 tokens: 63 pages each
    assert work_mla_moe.latent_cache_bytes(model, [1000] * 8, 16) \
        == 9 * 8 * 63 * 16 * 576 * 2
    at0 = work_mla_moe.decoder_flops_per_token(model, 0)
    by_hand = 9 * 2 * 13762560 + 2 * 3 * 2048 * 11264 \
        + 8 * 2 * (2048 * 64 + 3 * 2048 * 1408 * 8) \
        + 2 * 2048 * 163840
    assert at0 == by_hand
    assert work_mla_moe.decoder_flops_per_token(model, 1000) - at0 \
        == 9 * 2 * 1000 * 16 * (192 + 128)


def test_readers_of_the_new_metrics_find_nothing_in_an_empty_run():
    seen = {"traced": None, "peak": run_mod.peak_of("TPU v5 lite"),
            "trace": {"by_family": {}, "by_category": {}, "window_s": 1.0},
            "config": CONFIG, "counters": {}, "chips": 1}
    for m in BENCH["per_layer"]:
        if m.get("workloads") == [CELL["name"]]:
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
            ["_mla_flat_pallas.3", 0, 400_000_000,
             "custom-call.tpu_custom_call"],
            ["_grouped_swiglu_pallas.2", 400_000_000, 500_000_000,
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
    gap = program["served_logit_gap"]
    assert gap["value"] <= gap["limit"] == 1e-4
    assert run_mod.verdict(program) is True and result["correct"] is True
    assert control["served_logit_gap"]["value"] > 100 * gap["limit"]
    assert run_mod.verdict(control) is False


def test_traced_rehearsal_reads_the_new_metrics(rehearsal):
    """The four new metrics are read, and the two GPT-2 shares are not
    asked of this cell."""
    out, result = rehearsal
    assert out["traced"]["steps"]
    got = result["metrics"]
    for name in ("step.mfu.serve.mla_moe", "kernel.mla_attn_roofline.serve",
                 "kernel.moe_experts_roofline.serve",
                 "moe.experts_hit_share.serve",
                 "scheduler.decode_rows_per_step.serve",
                 "kv_cache.blocks_used_share.serve"):
        assert got[name]["value"] > 0, name
    assert 0 < got["moe.experts_hit_share.serve"]["value"] <= 100
    assert "step.mfu.serve" not in got
    assert "kernel.ragged_attn_roofline.serve" not in got


@pytest.mark.parametrize("fault", fault_readings.FAULTS)
def test_planted_faults_reach_the_program_and_not_the_reference(fault):
    """``benchmark/fault_readings.py`` at a toy size, without a run: what
    each plant hands the program, and that the reference still computes
    the sound model."""
    import importlib
    import numpy as np
    from mxnet_tpu.serving.llm import LLMEngine, Sequence
    from mxnet_tpu.serving.llm.model import DecoderConfig, TinyDecoder
    ref = importlib.import_module("benchmark.reference."
                                  + CONFIG["reference"])
    sound = ref.init_params(7, TOY_MODEL)
    prompt, served = list(range(5, 13)), list(range(40, 72))
    want = ref.served_token_gaps(sound, prompt, served, TOY_MODEL, 128)
    unplant = fault_readings.plant(fault, ref)
    try:
        given = ref.init_params(7, TOY_MODEL)
        gaps = ref.served_token_gaps(given, prompt, served, TOY_MODEL,
                                     128)
        engine = LLMEngine(TinyDecoder(DecoderConfig(**TOY_MODEL)), given,
                           block_size=16)
        seq = Sequence(list(range(1, 41)), 2)
        seq.slot, seq.block_ids = 0, [3, 4, 5]
        seq.seq_len = 36
        table = engine._build_batch(
            [seq], {seq: {"tokens": [1], "k": 0, "cl": 0}}, 8, 8)[4]
    finally:
        unplant()
    # the reference is never handed the fault ...
    np.testing.assert_allclose(gaps, want, atol=1e-5)
    # ... and the program is
    moved = {k for lp, lq in zip(sound["layers"], given["layers"])
             for k in lp if lp[k].shape and not np.array_equal(
                 np.asarray(lp[k]), np.asarray(lq[k]))}
    assert moved == {"routed_halved": {"experts_down"},
                     "shared_dropped": {"shared_down"},
                     "page_stale": set()}[fault]
    assert list(table[0, :3]) == ([3, 3, 5] if fault == "page_stale"
                                  else [3, 4, 5])
    assert ref.init_params(7, TOY_MODEL)["layers"][1]["experts_down"][
        0, 0, 0] == sound["layers"][1]["experts_down"][0, 0, 0]


def test_fault_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from mxnet_tpu.serving.llm import server as server_mod
    real = server_mod.GenerationResult.__init__

    def altered(self, tokens, *a, **k):
        tokens = list(tokens)
        tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 97
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
    assert result["checks"]["served_logit_gap"]["value"] > 1e-2
    assert set(result["metrics"]) == {"serve.out_tokens_per_s", "setup_s"}
