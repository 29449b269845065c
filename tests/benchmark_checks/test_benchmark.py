"""The benchmark's own tests: BENCHMARK.json against its rules, the
yardstick's arithmetic (traffic, work, trace, peaks, compare) against
hand-worked values, the training driver rehearsed end to end on the CPU at
a toy size, the control and each fault that the cell can have seen to come
out as not correct. Nothing here loads libtpu or describes a topology.
"""
import contextlib
import copy
import json
import os
import re
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, run as run_mod, trace, work  # noqa: E402

BENCH = run_mod.load_json("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {c["name"]: c for c in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
RECORDED = os.path.join(ROOT, "benchmark", "testdata",
                        "resnet50_train_2steps.json.gz")


def reporting_cells(metric):
    """Cells that have to report a metric."""
    if "workloads" in metric:
        return metric["workloads"]
    if "moves" in metric:
        return reporting_cells(E2E[metric["moves"]])
    return list(CELLS)


# ------------------------------------------------------- BENCHMARK.json --

def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    for word in BENCH["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")
        assert ".." not in word
    assert any(os.path.exists(os.path.join(ROOT, w))
               for w in BENCH["command"])
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    # a full check has to fit with all 24 cells a later PR may add
    per_run = BENCH["run_seconds"] + 60
    assert (2 + 14 * 24) * per_run + 24 * 2 * 90 + 1200 <= 43200


def test_all_names_differ():
    for group in (BENCH["configs"], BENCH["workloads"],
                  BENCH["end_to_end"] + BENCH["per_layer"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_configuration_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert 1 <= len(entry["source"]) <= 200 and "\n" not in entry["source"]
    assert 1 <= len(entry["why"]) <= 200
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    config = run_mod.load_json(entry["file"])
    assert config["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16
    for key in ("driver", "reference", "source", "assumed", "departures",
                "reckoned_bytes", "limits"):
        assert key in config, key
    for module in ("drivers/" + config["driver"],
                   "reference/" + config["reference"]):
        assert os.path.exists(os.path.join(ROOT, "benchmark",
                                           module + ".py"))
    assert any(c["config"] == entry["name"] for c in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\t" not in cell["why"]
    assert cell["config"] in CONFIGS
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
    reported = [m for m in BENCH["end_to_end"]
                if cell["name"] in reporting_cells(m)]
    assert len(reported) >= 2 and any(m["name"] == "setup_s"
                                      for m in reported)
    assert any(cell["name"] in reporting_cells(m)
               for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")
    assert set(metric.get("workloads", [])) <= set(CELLS)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    # it moves an end-to-end metric that each reporting cell reports
    moved = E2E[metric["moves"]]
    assert set(reporting_cells(metric)) <= set(reporting_cells(moved))
    # and has a reader of its own, found by its name
    assert callable(run_mod.layer_reader(metric["name"]))
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_no_name_of_the_benchmark_in_a_conditional():
    names = set(CELLS) | set(CONFIGS) | set(E2E) - {"setup_s"} \
        | {m["name"] for m in BENCH["per_layer"]} \
        | {c["traffic"] for c in BENCH["workloads"]}
    for folder, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        for f in files:
            if not f.endswith(".py") or "layer_metrics" in folder:
                continue
            for line in open(os.path.join(folder, f)):
                if re.match(r"\s*(if|elif|while)\b", line):
                    assert not any(n in line for n in names), (f, line)


# --------------------------------------------------------------- peaks --

def test_peaks_table_refuses_an_unknown_device():
    v5e = run_mod.peak_of("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    with pytest.raises(KeyError):
        run_mod.peak_of("cpu")


# ---------------------------------------------------------------- work --

R50 = {"layers": [3, 4, 6, 3], "channels": [64, 256, 512, 1024, 2048],
       "classes": 1000, "image": 224}


def test_resnet50_operations_against_hand_worked_values():
    one_block = {"layers": [1], "channels": [64, 256], "classes": 10,
                 "image": 224}
    stem = 112 * 112 * 7 * 7 * 3 * 64
    block = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    assert work.resnet_conv_macs(one_block) == stem + block
    # He et al. give 3.8e9 multiply-adds for the 50-layer net
    assert 3.8e9 < work.resnet_conv_macs(R50) < 3.9e9
    assert work.resnet_dense_macs(R50) == 2048000
    assert work.resnet_train_flops_per_image(R50) == 6 * (
        work.resnet_conv_macs(R50) + 2048000)


def test_decoder_operations_and_bytes_from_the_six_widths():
    m = {"vocab_size": 50257, "d_model": 2048, "num_layers": 24,
         "num_heads": 16, "d_ff": 8192, "max_context": 2048}
    per_layer = 2 * (4 * 2048 * 2048 + 2 * 2048 * 8192)
    assert work.decoder_flops_per_token(m, 0) == \
        24 * per_layer + 2 * 2048 * 50257
    assert work.decoder_flops_per_token(m, 100) \
        - work.decoder_flops_per_token(m, 0) == 24 * 4 * 100 * 2048
    assert work.decoder_weight_bytes(m) == 4 * (
        24 * 12 * 2048 * 2048 + 2048 * 50257)
    # 17 tokens take two pages of 16
    assert work.ragged_attention_bytes(m, [17, 16], 16) == \
        2 * 24 * 3 * 16 * 2048 * 4


# --------------------------------------------------------------- trace --

def test_busy_union_counts_overlaps_once():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert trace.union_ns([]) == 0
    assert trace.gaps_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == \
        [(20, 30), (40, 50)]


def test_reduce_nested_events_and_host_attribution():
    rows = [["early.0", -40, 60, "copy"],
            ["while.1", 20, 80, "while"], ["fusion.2", 30, 30,
                                           "fusion.Loop"],
            ["fusion.3", 70, 20, "fusion.Output"],
            ["copy.4", 200, 50, "copy"], ["late.5", 290, 100, "copy"]]
    # the window is the hull of the benchmark's host annotations, on the
    # trace's own clock; what the device did outside it is cut off
    got = trace.reduce({"devices": {"/device:TPU:0": rows},
                        "host": [["bench.step", 0, 110],
                                 ["bench.step", 180, 120]]})
    assert got["window_s"] == pytest.approx(300e-9)
    # early.0 counts from 0 to 20, late.5 from 290 to 300
    assert got["busy_s"] == pytest.approx((20 + 80 + 50 + 10) * 1e-9)
    assert got["idle_share"] == pytest.approx(1 - 160 / 300)
    assert got["by_name"]["while.1"] == pytest.approx(30e-9)
    assert got["by_name"]["early.0"] == pytest.approx(20e-9)
    assert got["by_family"]["fusion.Loop"] == pytest.approx(30e-9)
    assert trace.family("slice_bitcast_fusion.21.remat2") == \
        "slice_bitcast_fusion"
    assert got["by_category"]["fusion.Loop"] == pytest.approx(30e-9)
    # idle 100-200 and 250-290: 10 + 20 + 40 under an annotation, 70 not
    gaps = dict(got["idle_gaps"])
    assert gaps["bench.step"] == pytest.approx(70e-9)
    assert gaps["unattributed"] == pytest.approx(70e-9)
    assert trace.reduce({"devices": {}, "host": []}) is None
    # no annotation: first device event to the end of the last
    bare = trace.reduce({"devices": {"/device:TPU:0": rows}, "host": []})
    assert bare["window_s"] == pytest.approx(430e-9)


def test_reduce_on_the_recorded_chip_trace():
    rec = trace.load_recorded(RECORDED)
    got = trace.reduce(rec)
    rows = rec["devices"]["/device:TPU:0"]
    # this lane does not nest: the union is the plain sum
    assert got["busy_s"] == pytest.approx(sum(r[2] for r in rows) / 1e9)
    assert 0 <= got["idle_share"] < 0.01
    assert sum(got["by_name"].values()) == pytest.approx(got["busy_s"])
    # two steps of 47.6 ms, the convolution fusions about two thirds
    assert got["window_s"] == pytest.approx(0.0951, rel=0.01)
    share = got["by_category"]["fusion.Output"] / got["busy_s"]
    assert 0.6 < share < 0.7


def test_categorize_recorded_instructions():
    samples = trace.load_recorded(RECORDED)["hlo_samples"]
    for category, text in samples.items():
        assert trace.categorize(text) == category
        assert " " not in trace.short_name(text)
    assert trace.short_name(samples["fusion.Output"]).startswith(
        ("fusion", "multiply", "convolution"))


# ------------------------------------------------------------- traffic --

TOY_MODEL = {"layers": [1, 1, 1, 1], "channels": [8, 16, 32, 64, 128],
             "classes": 10, "image": 32}
TOY_TRAFFIC = {"batch": 8, "batches": 4,
               "fetch_every": 5, "check_steps": 3, "trace_steps": 4}


def test_traffic_is_made_from_the_seed():
    import numpy as np
    from benchmark import traffic
    big = 2 ** 31 + 12345
    xs, ys = traffic.train_batches(big, TOY_TRAFFIC, TOY_MODEL, "bfloat16")
    xs2, ys2 = traffic.train_batches(big, TOY_TRAFFIC, TOY_MODEL,
                                     "bfloat16")
    xs3, ys3 = traffic.train_batches(12345, TOY_TRAFFIC, TOY_MODEL,
                                     "bfloat16")
    assert xs.shape == (4, 8, 32, 32, 3) and ys.shape == (4, 8)
    assert np.array_equal(np.asarray(xs, np.float32),
                          np.asarray(xs2, np.float32))
    assert np.array_equal(np.asarray(ys), np.asarray(ys2))
    assert not np.array_equal(np.asarray(xs, np.float32),
                              np.asarray(xs3, np.float32))
    assert not np.array_equal(np.asarray(ys), np.asarray(ys3))
    assert 0 <= int(ys.min()) and int(ys.max()) < 10
    # rows all differ
    flat = np.asarray(xs, np.float32).reshape(32, -1)
    assert len({row.tobytes() for row in flat}) == 32


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_traffic_file_of_each_cell(cell):
    mix = run_mod.load_json("benchmark", "traffic",
                            cell["traffic"] + ".json")
    assert mix["why"]
    assert all(isinstance(v, (int, float, str, list, dict))
               for v in mix.values())


# ------------------------------------------------------------- compare --

def test_norm_gaps_take_the_worst_leaf_against_the_median():
    want = {"a": 1.0, "b": 2.0, "tiny": 1e-6}
    got = {"a": 1.1, "b": 2.0, "tiny": 0.5}
    worst, leaf, _ = compare.norm_gaps(got, want)
    # the all-but-zero leaf is measured against the median leaf's norm
    assert leaf == "tiny" and worst == pytest.approx(0.5 - 1e-6)
    worst, leaf, med = compare.norm_gaps(got, want, ["a", "b"])
    assert leaf == "a" and worst == pytest.approx(0.1 / 1.5)


def test_training_comparison_leaves_out_nought_gradients():
    want = {"losses": [2.0, 1.0], "grad_norms": {"w": 1.0, "v": 1.0,
                                                  "bias": 1e-9},
            "update_norms": {"w": 0.1, "v": 0.1, "bias": 1e-7},
            "row_losses": [1.0, 2.0, 4.0]}
    seen = copy.deepcopy(want)
    seen["row_losses"] = [1.1, 2.0, 4.0]
    seen["losses"][1] = 1.01
    seen["update_norms"]["bias"] = 0.05       # round-off under Adam or SGD
    seen["update_norms"]["w"] = 0.0           # a leaf that did not move
    out = compare.training(seen, want, matrices=["w", "v", "bias"])
    assert out["loss_gap_step1"] == 0 and out["leaves_left_out"] == 1
    # medians over the matrices that moved: w reads 1, v reads 0
    assert out["update_norm_gap_weights"] == pytest.approx(0.5)
    assert out["grad_norm_gap_weights"] == 0
    assert out["row_loss_gap_step1"] == pytest.approx(0.1 / 3 ** 0.5)
    assert out["row_loss_gap_step1_max"] == pytest.approx(0.1)
    assert out["loss_gap_step2"] == pytest.approx(0.01)
    assert out["update_norm_gap"] == pytest.approx(1.0)
    assert out["update_norm_gap_leaf"] == "w"


# ------------------------------------- the training driver, at toy size --

def toy_net(**kwargs):
    from mxnet_tpu.gluon.model_zoo.vision.resnet import (BottleneckV1,
                                                          ResNetV1)
    return ResNetV1(BottleneckV1, TOY_MODEL["layers"],
                    TOY_MODEL["channels"], **kwargs)


TRAIN_CELL = next(c for c in BENCH["workloads"]
                  if run_mod.load_json(CONFIGS[c["config"]]["file"])["driver"]
                  == "train")


def toy_config():
    """The cell's configuration with the sizes overridden: one bottleneck
    a stage, 32x32 images, float32 (so the comparison is near exact and
    bfloat16 is the precision below)."""
    config = run_mod.load_json(CONFIGS[TRAIN_CELL["config"]]["file"])
    config["net"] = {"entry": "test_benchmark:toy_net",
                     "kwargs": {"layout": "NHWC", "stem_s2d": True,
                                "classes": TOY_MODEL["classes"]}}
    config["model"] = dict(TOY_MODEL)
    config["dtype"] = "float32"
    config["control_precision"] = "bfloat16"
    config["limits"] = {"grad_norm_gap_weights": 1e-3,
                        "update_norm_gap_weights": 1e-3,
                        "grad_norm_gap_median": 1e-3,
                        "update_norm_gap_median": 1e-3,
                        "compiles_in_window": 0}
    return config


@contextlib.contextmanager
def recorded_trace():
    """The profiler switched off and the trace read from the recording
    (the CPU has no device lane)."""
    import jax
    saved = (jax.profiler.start_trace, jax.profiler.stop_trace,
             trace.find_xplane, trace.load_xplane)
    jax.profiler.start_trace = lambda *a, **k: None
    jax.profiler.stop_trace = lambda: None
    trace.find_xplane = lambda d: RECORDED
    trace.load_xplane = trace.load_recorded
    try:
        yield
    finally:
        (jax.profiler.start_trace, jax.profiler.stop_trace,
         trace.find_xplane, trace.load_xplane) = saved


def rehearse(seconds=0.3, seed=2 ** 31 + 7):
    """Drive a traced run past the harness's look for a chip."""
    with recorded_trace():
        return run_mod.drive(TRAIN_CELL, toy_config(),
                             dict(TOY_TRAFFIC), seed, seconds, True,
                             require_chip=False, t_start=time.monotonic())


@pytest.fixture(scope="module")
def sound_run():
    return rehearse()


def result_of(run, traced):
    out, devices = run
    return run_mod.assemble(BENCH, TRAIN_CELL, toy_config(),
                            TOY_TRAFFIC, out, devices, traced,
                            peak=run_mod.peak_of("TPU v5 lite"))


def test_rehearsal_prints_the_contracts_keys(sound_run):
    result = json.loads(json.dumps(result_of(sound_run, False)))
    assert list(result)[-1] == "checks"
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "checks"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"train.images_per_s", "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and UNIT.match(m["unit"])
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for c in result["checks"].values():
        assert c["value"] <= c["limit"]


def test_rehearsal_matches_the_reference_at_toy_size(sound_run):
    notes = sound_run[0]["notes"]
    # float32 against float32: rounding order only
    checks = sound_run[0]["checks"]
    for name in ("grad_norm_gap_weights", "update_norm_gap_weights",
                 "grad_norm_gap_median", "update_norm_gap_median"):
        assert checks[name]["value"] < 1e-4, name
    assert notes["not_compared"]["row_loss_gap_step1"] < 1e-5
    assert notes["not_compared"]["grad_norm_gap"] < 1e-3
    assert notes["not_compared"]["update_norm_gap"] < 1e-3
    assert notes["step_programs"] == 1 and notes["eager_fallbacks"] == 0
    # the biases in front of a BatchNorm: two a block
    assert notes["not_compared"]["leaves_left_out"] == 8


def test_traced_rehearsal_reports_the_per_layer_metrics(sound_run):
    result = result_of(sound_run, True)
    named = {m["name"] for m in BENCH["per_layer"]
             if TRAIN_CELL["name"] in reporting_cells(m)}
    assert set(result["metrics"]) == named
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] > 0
    assert len(result["breakdown"]["device_ops"]) == 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10
    for name, m in result["metrics"].items():
        if "roofline" in name or "mfu" in name:
            assert 0 < m["value"] <= 100


def test_layer_reader_with_nothing_to_read_returns_nothing():
    empty = {"trace": {"by_category": {}, "idle_share": 0.0}, "spans": {},
             "traced": None, "peak": None, "config": toy_config(),
             "chips": 1, "counters": {}}
    for m in BENCH["per_layer"]:
        if "idle_share" not in m["name"]:
            assert run_mod.layer_reader(m["name"])(empty) is None


def _broken_step(monkeypatch, wrap):
    from mxnet_tpu.gluon import Trainer
    real = Trainer.compile_step

    def compile_step(self, loss_fn, **kw):
        return wrap(self, real(self, loss_fn, donate=False, **kw))
    monkeypatch.setattr(Trainer, "compile_step", compile_step)


class _Wrapped:
    def __init__(self, step, call):
        self._step, self._call = step, call

    def __call__(self, x, y):
        return self._call(self._step, x, y)

    def __getattr__(self, name):
        return getattr(self._step, name)


def test_fault_state_left_unchanged_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    def wrap(trainer, step):
        def call(step, x, y):
            params = [p.data() for p in trainer._params]
            before = [p._data for p in params]
            loss = step(x, y)
            for p, b in zip(params, before):
                p._data = b
            for mom in trainer._updaters[0].states.values():
                mom._data = jnp.zeros_like(mom._data)
            return loss
        return _Wrapped(step, call)
    _broken_step(monkeypatch, wrap)
    result = result_of(rehearse(), False)
    assert result["correct"] is False
    assert result["checks"]["update_norm_gap_median"]["value"] == \
        pytest.approx(1.0, abs=1e-3)


def test_fault_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    def wrap(trainer, step):
        def call(step, x, y):
            half = x.shape[0] // 2
            return step(x[:half], y[:half])
        return _Wrapped(step, call)
    _broken_step(monkeypatch, wrap)
    result = result_of(rehearse(), False)
    assert result["correct"] is False
    over = [k for k, c in result["checks"].items()
            if c["value"] > c["limit"]]
    assert "grad_norm_gap_median" in over


def test_control_in_the_precision_below_is_not_correct():
    from benchmark import readings
    config = toy_config()
    got = readings.read(config, TOY_TRAFFIC, 2 ** 31 + 7,
                        config["control_precision"])
    for name in ("control", "half_batch"):
        over = [k for k, limit in config["limits"].items()
                if got[name].get(k, 0) > limit]
        assert over, (name, got[name])


# --------------------------------------- the serving driver, at toy size --

TOY_DECODER = {"vocab_size": 64, "d_model": 32, "num_layers": 2,
               "num_heads": 2, "d_ff": 64, "max_context": 128}
TOY_CHAT = {"arrivals": {"law": "closed", "clients": 16,
                         "requests_per_second": 48},
            "lengths_seed": 24,
            "prompt_tokens": {"law": "lognormal", "median": 20,
                              "sigma": 0.9, "min": 4, "max": 64},
            "output_tokens": {"law": "geometric", "mean": 8, "min": 2,
                              "max": 24},
            "checked_requests": 4, "trace_after_share": 0.1,
            "trace_seconds": 0.3}
TOY_OPEN = dict(TOY_CHAT, arrivals={"law": "open", "rate_per_s": 40,
                                    "cv": 2.0})
SERVE_CELLS = [c for c in BENCH["workloads"]
               if run_mod.load_json(CONFIGS[c["config"]]["file"])["driver"]
               == "serve"]
needs_serve_cell = pytest.mark.skipif(
    not SERVE_CELLS, reason="BENCHMARK.json holds no serving cell")


def toy_serve_config():
    config = run_mod.load_json(CONFIGS[SERVE_CELLS[0]["config"]]["file"])
    config["model"] = dict(TOY_DECODER)
    config["limits"] = {"served_logit_gap": 1e-4, "wrong_length": 0,
                        "unanswered": 0, "compiles_in_window": 0}
    return config


def test_requests_are_made_from_the_seed_with_one_set_of_sizes():
    from benchmark import traffic
    a = traffic.requests(2 ** 31 + 3, TOY_CHAT, 64, 2.0)
    b = traffic.requests(2 ** 31 + 3, TOY_CHAT, 64, 2.0)
    c = traffic.requests(3, TOY_CHAT, 64, 2.0)
    assert a == b and a != c and len(a) == 96
    # every seed serves the same sizes in the same order
    assert [(len(p), o) for _, p, o in a] == [(len(p), o) for _, p, o in c]
    # and a longer run's requests begin with a shorter run's
    assert traffic.requests(3, TOY_CHAT, 64, 1.0) == c[:48]
    assert len(traffic.requests(3, TOY_CHAT, 64, 0.01)) == 16
    for due, prompt, n_out in a:
        assert due == 0 and 4 <= len(prompt) <= 64 and 2 <= n_out <= 24
        assert all(0 <= t < 64 for t in prompt)
    assert len({len(p) for _, p, _ in a}) > 10


@pytest.mark.parametrize("law,spec,mean", [
    ("geometric", {"mean": 19.31, "min": 4, "max": 1024}, 19.31),
    ("lognormal", {"median": 160, "sigma": 0.5, "min": 1, "max": 4096},
     160 * 2.718281828 ** 0.125),
    ("fixed", {"value": 7}, 7)])
def test_length_laws_have_the_stated_mean(law, spec, mean):
    import numpy as np
    from benchmark import traffic
    got = traffic.LENGTH_LAWS[law](np.random.default_rng(0), 20000, spec)
    assert got.mean() == pytest.approx(mean, rel=0.03)
    assert got.min() >= spec.get("min", 7) and got.dtype == np.int64


def test_open_arrivals_and_shared_prefixes():
    import numpy as np
    from benchmark import traffic
    mix = dict(TOY_OPEN, shared_prefix={"groups": 3, "tokens": 5})
    got = traffic.requests(11, mix, 64, 10.0)
    due = [t for t, _, _ in got]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 10.0
    assert 300 < len(got) < 500                 # 40 a second
    gaps = np.diff(due)
    assert 1.6 < gaps.std() / gaps.mean() < 2.4     # burstier than Poisson
    heads = {tuple(p[:5]) for _, p, _ in got}
    assert len(heads) == 3
    # the same arrivals and sizes for another seed, other prefixes
    other = traffic.requests(12, mix, 64, 10.0)
    assert [t for t, _, _ in other] == due
    assert not heads & {tuple(p[:5]) for _, p, _ in other}
    poisson = traffic.requests(11, dict(TOY_OPEN, arrivals={
        "law": "open", "rate_per_s": 40}), 64, 10.0)
    gaps = np.diff([t for t, _, _ in poisson])
    assert 0.85 < gaps.std() / gaps.mean() < 1.15


def serve_rehearsal(seconds=1.0, seed=2 ** 31 + 11, traced=False,
                    mix=TOY_CHAT):
    return run_mod.drive(SERVE_CELLS[0], toy_serve_config(), dict(mix),
                         seed, seconds, traced, require_chip=False,
                         t_start=time.monotonic())


@needs_serve_cell
def test_serving_rehearsal_prints_the_contracts_keys():
    out, devices = serve_rehearsal()
    result = json.loads(json.dumps(run_mod.assemble(
        BENCH, SERVE_CELLS[0], toy_serve_config(), TOY_CHAT, out, devices,
        False)))
    assert list(result)[-1] == "checks" and result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] == 48
    # the window runs from the first request to the last answer
    assert out["notes"]["answered"] == 48 and out["notes"]["window_s"] > 0
    assert out["end_to_end"]["serve.out_tokens_per_s"] == pytest.approx(
        out["notes"]["out_tokens"] / out["notes"]["window_s"])
    assert 0 < out["counters"]["kv_blocks_used_peak"] \
        <= out["counters"]["kv_blocks_pool"]
    named = {m["name"] for m in BENCH["end_to_end"]
             if SERVE_CELLS[0]["name"] in reporting_cells(m)}
    assert set(result["metrics"]) == named
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # float32 against float32 at a toy size: every served token is the
    # reference's first choice
    assert result["checks"]["served_logit_gap"]["value"] == 0
    assert out["notes"]["tokens_compared"] > 0
    assert out["notes"]["counts"]["decode_steps"] > 0


@needs_serve_cell
def test_traced_serving_rehearsal_reports_the_per_layer_metrics():
    with recorded_trace():
        out, devices = serve_rehearsal(traced=True)
    result = run_mod.assemble(BENCH, SERVE_CELLS[0], toy_serve_config(),
                              TOY_CHAT, out, devices, True,
                              peak=run_mod.peak_of("TPU v5 lite"))
    named = {m["name"] for m in BENCH["per_layer"]
             if SERVE_CELLS[0]["name"] in reporting_cells(m)}
    # the recording is of a training step: it holds no Mosaic kernel, so
    # that reader finds nothing and its metric is left out
    silent = {n for n in named if "roofline" in n}
    assert set(result["metrics"]) == named - silent
    assert result["correct"] is True and out["traced"]["steps"]
    for name, m in result["metrics"].items():
        assert m["value"] > 0, name
    assert 0 < result["metrics"]["step.mfu.serve"]["value"] <= 100


@needs_serve_cell
def test_recording_starts_at_the_stated_share_of_answers():
    from benchmark.drivers import serve
    mix = dict(TOY_CHAT, trace_after_share=0.5)
    with recorded_trace():
        out, _ = serve_rehearsal(traced=True, mix=mix)
    traced, notes = out["traced"], out["notes"]
    # 48 requests: the recording starts with the 24th answer, so at most
    # the other 24 complete inside it, and it holds steps
    assert traced["after_answers"] == 24 and notes["answered"] == 48
    assert 0 < traced["counts"]["requests_completed"] <= 24
    assert len(traced["steps"]) >= serve.MIN_TRACED_STEPS
    assert 0 < traced["started_s"] < notes["window_s"]
    assert 0 < traced["recorded_s"] <= mix["trace_seconds"] + 0.2
    assert notes["traced"]["steps"] == len(traced["steps"])
    # the notes say how the batch filled its window, second by second
    assert notes["window_share"] == pytest.approx(notes["window_s"] / 1.0)
    assert notes["answers_by_second"][-1] == 48
    assert notes["answers_by_second"] == sorted(notes["answers_by_second"])


@needs_serve_cell
@pytest.mark.parametrize("share,trace_seconds", [(1.0, 0.3), (0.1, 0.0)],
                         ids=["batch_over_at_the_share", "recording_too_short"])
def test_recording_with_too_few_steps_fails_with_the_drivers_message(
        share, trace_seconds):
    from benchmark.drivers import serve
    mix = dict(TOY_CHAT, trace_after_share=share, trace_seconds=trace_seconds)
    with recorded_trace(), pytest.raises(RuntimeError) as err:
        serve_rehearsal(traced=True, mix=mix)
    said = str(err.value)
    # the mix by name, the recording's length, the steps it held
    assert repr(SERVE_CELLS[0]["traffic"]) in said
    assert re.search(r"held \d+ steps in a window_s of \d+\.\d+", said)
    assert f"{serve.MIN_TRACED_STEPS} are the least" in said
    assert "README.md" in said


@pytest.mark.parametrize("cell", SERVE_CELLS, ids=lambda c: c["name"])
def test_request_counts_are_what_perf_md_states(cell):
    from benchmark import traffic
    mix = run_mod.load_json("benchmark", "traffic", cell["traffic"] + ".json")
    plan = traffic.requests(1, mix, 64, BENCH["run_seconds"])
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        section = fh.read().split("\n## 4. Cells", 1)[1].split("\n## 5.")[0]
    bullet = section.split(f"- `{cell['name']}` (traffic `{cell['traffic']}`",
                           1)[1].split("\n- `")[0].split("\n**")[0]
    stated = int(re.search(r"([\d,]+)\s+requests", bullet).group(1)
                 .replace(",", ""))
    assert len(plan) == stated
    # where the cell's line in BENCHMARK.json states a count, the same
    in_why = re.search(r"([\d,]+) requests", cell["why"])
    assert in_why is None or int(in_why.group(1).replace(",", "")) == stated
    assert 0 < mix["trace_after_share"] < 1 and mix["trace_seconds"] > 0


@pytest.mark.parametrize("cell", SERVE_CELLS, ids=lambda c: c["name"])
def test_lengths_seed_is_the_one_the_files_own_rule_picks(cell):
    """A mix that says its seed is "of seeds 1 to 8" the draw nearest the
    published statistics holds the seed that rule picks at the size
    ``run_seconds`` gives it: the mean where the law is stated by a mean,
    the median where by a median, the two relative distances added."""
    import numpy as np
    from benchmark import traffic
    mix = run_mod.load_json("benchmark", "traffic", cell["traffic"] + ".json")
    if not mix["assumed"]["lengths_seed"].startswith("of seeds 1 to 8"):
        pytest.skip("the mix states another rule for its seed")

    def lengths(seed):
        plan = traffic.requests(1, dict(mix, lengths_seed=seed), 64,
                                BENCH["run_seconds"])
        return {"prompt_tokens": [len(p) for _, p, _ in plan],
                "output_tokens": [o for _, _, o in plan]}

    def distance(seed):
        total = 0.0
        for part, drawn in lengths(seed).items():
            stat = "mean" if "mean" in mix[part] else "median"
            total += abs(getattr(np, stat)(drawn) / mix[part][stat] - 1)
        return total
    assert min(range(1, 9), key=distance) == mix["lengths_seed"]
    # the file says at what size the choice was made
    n = len(lengths(mix["lengths_seed"])["output_tokens"])
    assert str(n) in mix["assumed"]["lengths_seed"]


def test_readme_lists_the_keys_of_a_serving_mix_that_are_read():
    """One trigger, not two: what README.md says a serving mix holds is
    what ``serve.py`` and ``traffic.requests`` read, beside the keys that
    only document the mix."""
    import inspect
    from benchmark import traffic
    from benchmark.drivers import serve
    read = set()
    for source in (inspect.getsource(serve), inspect.getsource(
            traffic.requests)):
        read |= set(re.findall(r'traffic(?:\[|\.get\()"(\w+)"', source))
        # ``traffic[part]`` for each part of the loop's tuple
        for parts in re.findall(r"part in enumerate\(\(([^)]*)\)\)", source):
            read |= set(re.findall(r'"(\w+)"', parts))
    with open(os.path.join(ROOT, "benchmark", "README.md")) as fh:
        row = next(line for line in fh if line.startswith(
            "| `traffic/<traffic>.json`"))
    listed = set(re.findall(r"`(\w+)`", row.split("A serving mix", 1)[1]))
    assert listed - {"source", "assumed", "why"} == read
    assert "trace_after_share" in read and len(read) == 8
    for cell in SERVE_CELLS:
        mix = run_mod.load_json("benchmark", "traffic",
                                cell["traffic"] + ".json")
        assert read - {"shared_prefix"} <= set(mix) <= listed | {"why"}


@needs_serve_cell
def test_fault_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from mxnet_tpu.serving.llm import server as server_mod
    real = server_mod.GenerationResult.__init__

    def altered(self, tokens, *a, **k):
        tokens = list(tokens)
        tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 64
        real(self, tokens, *a, **k)
    monkeypatch.setattr(server_mod.GenerationResult, "__init__", altered)
    out, devices = serve_rehearsal()
    result = run_mod.assemble(BENCH, SERVE_CELLS[0], toy_serve_config(),
                              TOY_CHAT, out, devices, False)
    assert result["correct"] is False
    assert result["checks"]["served_logit_gap"]["value"] > 1e-2


def test_decoder_control_in_bfloat16_puts_other_tokens_first():
    import numpy as np
    from benchmark.reference import gpt2_decoder as ref
    params = ref.init_params(5, TOY_DECODER)
    rs = np.random.RandomState(5)
    widest = 0.0
    for _ in range(4):
        tokens = rs.randint(0, 64, 120).tolist()
        exact = ref.served_token_gaps(params, tokens[:8], tokens[8:],
                                      TOY_DECODER, 128)
        assert exact.shape == (112,) and float(exact.min()) >= 0
        control = ref.served_token_gaps(params, tokens[:8], tokens[8:],
                                        TOY_DECODER, 128, quant="float8_e5m2")
        widest = max(widest, float(control.max()))
    assert widest > 1e-4          # the toy limit of the rehearsal


@needs_serve_cell
def test_serving_control_comes_out_not_correct_through_the_harness():
    from benchmark import readings
    got = readings.read_serve(SERVE_CELLS[0], toy_serve_config(),
                              dict(TOY_CHAT), 2 ** 31 + 13, "float8_e5m2",
                              0.5, require_chip=False)
    assert got["program"] == 0 and got["tokens"] > 0
    assert got["program_correct"] is True
    assert got["control"] > got["limit"]
    assert got["control_correct"] is False


@needs_serve_cell
def test_open_loop_rehearsal_sends_each_request_when_it_is_due():
    out, _ = serve_rehearsal(seconds=1.0, mix=TOY_OPEN)
    notes = out["notes"]
    assert notes["requests"] == notes["answered"] > 16
    assert run_mod.verdict(out["checks"]) is True
    # the last request is due near the end of the second, so the window
    # cannot close before it; a tail by the client's clock is on offer
    assert notes["window_s"] > 0.8
    assert out["end_to_end"]["serve.request_p90_ms"] > 0


@needs_serve_cell
def test_fault_an_answer_that_never_comes_is_not_correct(monkeypatch):
    from concurrent.futures import Future
    from benchmark.drivers import serve
    from mxnet_tpu.serving.llm import LLMServer
    real, calls = LLMServer.submit, [0]

    def lossy(self, *a, **k):
        calls[0] += 1
        return Future() if calls[0] == 5 else real(self, *a, **k)
    monkeypatch.setattr(LLMServer, "submit", lossy)
    monkeypatch.setattr(serve, "STRAGGLER_S", 1.0)
    out, devices = serve_rehearsal(seconds=0.5)
    result = run_mod.assemble(BENCH, SERVE_CELLS[0], toy_serve_config(),
                              TOY_CHAT, out, devices, False)
    assert result["correct"] is False and result["failed"] == 1
    assert result["checks"]["unanswered"]["value"] == 1
