"""The serving rehearsal of ``test_benchmark.py`` on the
``granite_4_0_h_micro`` configuration, at a toy size on the CPU: the
configuration's own driver, reference and limits' keys, a
Granite-4.0-H-shaped toy model (two periods of ``mamba mamba attention
mamba``, hidden 64, 4 query heads over 2 of 16, 8 state-space heads of 16
with a state of 16) in float32. A sound run comes out correct; the control
in the precision below and a token altered where it is produced do not.
Also the configuration's file against the catalog's row key by key, the
planted faults of ``fault_readings_ssm.py`` without a run, and the
arithmetic of ``work_ssm_hybrid.py`` against hand-worked values.
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

from benchmark import (fault_readings_ssm, run as run_mod,  # noqa: E402
                       work_ssm_hybrid)

BENCH = run_mod.load_json("BENCHMARK.json")
ENTRY = next(c for c in BENCH["configs"]
             if c["name"] == "granite_4_0_h_micro")
CELL = next(c for c in BENCH["workloads"] if c["config"] == ENTRY["name"])
CONFIG = run_mod.load_json(ENTRY["file"])

TOY_MODEL = dict(CONFIG["model"], vocab_size=97, d_model=64, num_layers=8,
                 num_heads=4, num_key_value_heads=2, d_ff=96,
                 max_context=128, mamba_n_heads=8, mamba_d_head=16,
                 mamba_d_state=16, attention_multiplier=1.0 / 16,
                 layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
                 torch_dtype="float32")
TOY_CHAT = {"arrivals": {"law": "closed", "clients": 8,
                         "requests_per_second": 24},
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
    # three slots for eight clients: every slot is used again and again
    config["engine"] = {"dtype": "float32", "max_seqs": 3}
    # float32 against float32 at a toy size: round-off only, of logits
    # that spread by 0.01 (the tied head's scale)
    config["limits"] = dict(config["limits"], served_logit_gap=1e-5)
    return config


def test_configuration_file_keeps_the_published_numbers():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not in this sandbox")
    row = next(json.loads(line) for line in open(catalog)
               if '"granite-4.0-h-micro"' in line)
    assert CONFIG["source"] == ENTRY["source"] == row["source_url"]
    assert CONFIG["reduced"] == ENTRY["reduced"] \
        == ["max_position_embeddings"]
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["max_position_embeddings"] == 8192 \
        < row["config"]["max_position_embeddings"]
    model = CONFIG["model"]
    for ours, theirs in (("d_model", "hidden_size"),
                         ("num_layers", "num_hidden_layers"),
                         ("num_heads", "num_attention_heads"),
                         ("d_ff", "shared_intermediate_size"),
                         ("max_context", "max_position_embeddings")):
        assert model[ours] == CONFIG[theirs]
    for key in model:
        if key in row["config"]:
            assert model[key] == row["config"][key], key
    from mxnet_tpu.serving.llm.model import DecoderConfig, TinyDecoder
    assert set(model) == set(DecoderConfig.FIELDS
                             + DecoderConfig.FIELDS_OF[model["model_type"]])
    layout = TinyDecoder(DecoderConfig(**model)).cache_layout
    assert layout == {"kind": "kv_packed", "num_heads": 8, "head_dim": 64,
                      "layers": 4, "state": {"ssm": (36, 128, 4096),
                                             "conv": (36, 3, 4352)}}
    # the file's own reckoning of what the pools hold
    slots = CONFIG["engine"]["max_seqs"]
    reckoned = CONFIG["reckoned_bytes"]
    assert reckoned["state_pools_f32"] == slots * 36 * 4 * (
        128 * 4096 + 3 * 4352)
    assert reckoned["kv_pool_bf16"] == CONFIG["observed_defaults"][
        "num_blocks"] * 16 * 4 * 2 * 8 * 64 * 2
    assert 36 * reckoned["mamba_layer_params"] \
        + 4 * reckoned["attention_layer_params"] \
        + reckoned["embedding_params"] + 2048 == 3191396096


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark", "reference",
                        CONFIG["reference"] + ".py")
    source = open(path).read()
    assert "import mxnet_tpu" not in source \
        and "from mxnet_tpu" not in source


def test_operations_and_bytes_of_the_published_shapes():
    model = CONFIG["model"]
    assert work_ssm_hybrid.layer_counts(model) == (36, 4)
    # in_proj 2048 x 8512, conv 4352 x 4, recurrence 3 x 4096 x 128,
    # out_proj 4096 x 2048
    assert work_ssm_hybrid.mamba_macs_per_token(model) \
        == 17432576 + 17408 + 1572864 + 8388608
    assert work_ssm_hybrid.attention_macs_per_token(model, 0) \
        == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert work_ssm_hybrid.attention_macs_per_token(model, 1000) \
        - work_ssm_hybrid.attention_macs_per_token(model, 0) \
        == 2 * 1000 * 32 * 64
    at0 = work_ssm_hybrid.decoder_flops_per_token(model, 0)
    assert at0 == 2 * (36 * 27411456 + 4 * 10485760
                       + 40 * 3 * 2048 * 8192 + 2048 * 100352)
    assert work_ssm_hybrid.decoder_flops_per_token(model, 1000) - at0 \
        == 2 * 4 * 2 * 1000 * 32 * 64
    assert work_ssm_hybrid.state_bytes_per_row(model) == 2097152
    # a step of 32 rows: 36 layers' states read once and written once
    assert work_ssm_hybrid.state_step_bytes(model, 32) \
        == 2 * 32 * 36 * 2097152
    # 8 sequences at 1000 tokens: 63 pages each, 4 layers of 2 x 8 x 64
    assert work_ssm_hybrid.kv_cache_bytes(model, [1000] * 8, 16) \
        == 4 * 8 * 63 * 16 * 1024 * 2


def test_readers_of_the_new_metrics_find_nothing_in_an_empty_run():
    seen = {"traced": None, "peak": run_mod.peak_of("TPU v5 lite"),
            "trace": {"by_family": {}, "by_category": {}, "window_s": 1.0},
            "config": CONFIG, "counters": {}, "chips": 1}
    mine = [m["name"] for m in BENCH["per_layer"]
            if m.get("workloads") == [CELL["name"]]]
    assert len(mine) == 4
    for name in mine:
        assert run_mod.layer_reader(name)(seen) is None, name


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
            ["_ssd_state_pallas.3", 0, 400_000_000,
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
    assert out["notes"]["tokens_compared"] > 0
    assert out["notes"]["requests"] == 24 > 3   # slots are used again
    gap = program["served_logit_gap"]
    assert gap["value"] <= gap["limit"] == 1e-5
    assert run_mod.verdict(program) is True and result["correct"] is True
    assert control["served_logit_gap"]["value"] > 100 * gap["limit"]
    assert run_mod.verdict(control) is False
    assert out["notes"]["observed_defaults"]["prefix_cache"] is False
    assert out["notes"]["counts"]["prefix_hits"] == 0


def test_traced_rehearsal_reads_the_new_metrics(rehearsal):
    """The four new metrics are read, and the other blocks' shares are
    not asked of this cell."""
    out, result = rehearsal
    assert out["traced"]["steps"]
    got = result["metrics"]
    for name in ("step.mfu.serve.ssm_hybrid",
                 "kernel.ssd_state_roofline.serve",
                 "kernel.gqa_attn_roofline.serve",
                 "ssm.state_rows_per_step.serve",
                 "scheduler.decode_rows_per_step.serve",
                 "kv_cache.blocks_used_share.serve"):
        assert got[name]["value"] > 0, name
    assert 1 <= got["ssm.state_rows_per_step.serve"]["value"] <= 3
    for name in ("step.mfu.serve", "step.mfu.serve.mla_moe",
                 "kernel.ragged_attn_roofline.serve",
                 "kernel.mla_attn_roofline.serve"):
        assert name not in got


@pytest.mark.parametrize("fault", fault_readings_ssm.FAULTS)
def test_planted_faults_reach_the_program_and_not_the_reference(fault):
    """``benchmark/fault_readings_ssm.py`` at a toy size, without a run:
    what each plant hands the program, that the program's logits then
    leave the sound model's, and that the reference still computes the
    sound model."""
    import importlib
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.serving.llm import LLMEngine, Sequence
    from mxnet_tpu.serving.llm.model import DecoderConfig, TinyDecoder
    ref = importlib.import_module("benchmark.reference."
                                  + CONFIG["reference"])
    sound = ref.init_params(7, TOY_MODEL)

    def served(params, prompts):
        """Two requests through ONE slot, one after the other (a model
        of its own: a model keeps its traced step programs)."""
        engine = LLMEngine(TinyDecoder(DecoderConfig(**TOY_MODEL)), params,
                           max_seqs=1, block_size=16)
        out = []
        for prompt in prompts:
            seq = Sequence(list(prompt), 6)
            engine.add(seq)
            while engine.has_work():
                engine.step()
            out.append(list(seq.generated))
        return out

    prompts = [list(range(5, 85)), list(range(50, 58))]
    want = served(sound, prompts)
    gaps_want = ref.served_token_gaps(sound, prompts[1], want[1],
                                      TOY_MODEL, 128)
    unplant = fault_readings_ssm.plant(fault, ref, TOY_MODEL)
    try:
        given = ref.init_params(7, TOY_MODEL)
        got = served(given, prompts)
        gaps = ref.served_token_gaps(given, prompts[1], want[1],
                                     TOY_MODEL, 128)
        worst = float(ref.served_token_gaps(
            given, prompts[1], got[1], TOY_MODEL, 128).max())
    finally:
        unplant()
    # the reference is never handed the fault ...
    np.testing.assert_allclose(gaps, gaps_want, atol=1e-7)
    assert float(gaps_want.max()) <= 1e-5
    # ... and the program is: in its weights, or in its step
    moved = {k for lp, lq in zip(sound["layers"], given["layers"])
             for k in lp if not np.array_equal(
                 np.asarray(lp[k], np.float32),
                 np.asarray(lq[k], np.float32))}
    assert moved == {"decay_dropped": {"A_log"},
                     "group_shifted": {"wk", "wv"},
                     "state_not_reset": set()}[fault]
    # what the second occupant of the slot is served is no longer the
    # sound model's first choice
    assert worst > 1e-4, worst
    assert bool(jnp.array_equal(
        ref.init_params(7, TOY_MODEL)["layers"][0]["A_log"],
        sound["layers"][0]["A_log"]))


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
    assert result["checks"]["served_logit_gap"]["value"] > 1e-3
    assert set(result["metrics"]) == {"serve.out_tokens_per_s", "setup_s"}
