"""Every example script must run end to end at a tiny configuration.

The reference CI runs its example/ scripts the same way
(tests/nightly/test_tutorial etc.); a broken example is a broken
user-facing surface. Each case is a real subprocess — fresh
interpreter, argparse, import path — not an in-process import.
"""
import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")

CASES = [
    ("recommender_mf.py", ["--steps", "4", "--batch-size", "32",
                           "--users", "20", "--items", "15"]),
    pytest.param("dcgan.py", ["--steps", "2", "--batch-size", "4"],
                 marks=pytest.mark.slow),   # ~8s (tier-1 budget);
    # GAN/conv-training coverage stays fast via recommender/vae/mnist
    pytest.param("bert_pretrain_mlm.py",
                 ["--steps", "2", "--batch-size", "4",
                  "--seq-len", "8", "--vocab", "16"],
                 marks=pytest.mark.slow),   # ~11s (tier-1 budget)
    pytest.param("train_cifar_gluon.py",
                 ["--steps", "2", "--batch-size", "4",
                  "--model", "resnet18_v1"],
                 marks=pytest.mark.slow),   # ~11s (tier-1 budget);
    # gluon-training coverage stays fast via mnist/multi_task/lenet
    ("train_mnist_mlp.py", ["--epochs", "1", "--batch-size", "32"]),
    ("char_lstm.py", ["--epochs", "1", "--seq-len", "8",
                      "--batch-size", "4"]),
    ("lstm_ocr.py", ["--epochs", "1", "--num-samples", "32",
                     "--batch-size", "16", "--width", "24"]),
    ("dqn_cartpole.py", ["--episodes", "6", "--batch-size", "32"]),
    ("multi_task.py", ["--epochs", "1", "--num-samples", "128",
                       "--batch-size", "32"]),
    ("bucketing_lm.py", ["--epochs", "1", "--batch-size", "4",
                         "--buckets", "6,9"]),
    pytest.param("bi_lstm_sort.py",
                 ["--epochs", "1", "--num-samples", "64",
                  "--batch-size", "16", "--seq-len", "4",
                  "--vocab", "8"],
                 marks=pytest.mark.slow),   # ~6s (tier-1 budget);
    # seq2seq/bucketing coverage stays fast via char_lstm/bucketing_lm
    ("sparse_linear_classification.py",
     ["--epochs", "2", "--num-samples", "256", "--num-features", "100",
      "--batch-size", "64", "--min-acc", "0.6"]),
    ("vae_mnist.py", ["--epochs", "1", "--num-samples", "128",
                      "--batch-size", "32", "--max-loss", "110"]),
    ("adversary_fgsm.py", ["--epochs", "2", "--num-samples", "256",
                           "--batch-size", "64", "--min-drop", "0.02"]),
    pytest.param("ssd_detect.py", ["--steps", "2", "--batch-size", "2"],
                 marks=pytest.mark.slow),   # ~49s (tier-1 budget)
    ("svm_digits.py", ["--epochs", "3", "--num-samples", "256",
                       "--batch-size", "64", "--min-acc", "0.12"]),
    # the L1-hinge branch is the other half of SVMOutput; pytest
    # disambiguates the duplicate id with a numeric suffix
    ("svm_digits.py", ["--epochs", "3", "--num-samples", "256",
                       "--batch-size", "64", "--min-acc", "0.12",
                       "--hinge", "l1"]),
    pytest.param("multi_threaded_inference.py",
                 ["--threads", "4", "--requests", "2",
                  "--batch-size", "2", "--image-size", "32"],
                 marks=pytest.mark.slow),   # ~7s (tier-1 budget);
    # threaded-inference coverage stays fast via serve_predictor +
    # test_threadsafe
    ("serve_predictor.py", ["--threads", "4", "--requests", "8",
                            "--max-batch", "4", "--feature-dim", "16"]),
    pytest.param("llm_serve_decode.py",
                 ["--threads", "4", "--requests", "4",
                  "--max-context", "32", "--max-new-tokens", "6"],
                 marks=pytest.mark.slow),   # ~18s (tier-1 budget);
    # test_llm_serving's decoder-artifact roundtrip keeps fast coverage
    pytest.param("nce_lm.py", ["--epochs", "3", "--max-ppl", "120"],
                 marks=pytest.mark.slow),   # ~22s (tier-1 budget)
    ("rbm_digits.py", ["--epochs", "3", "--num-samples", "256",
                       "--max-recon-err", "0.12"]),
    # --check-uncertainty needs a longer trajectory than CI affords;
    # the 0.6 RMSE gate beats the constant-zero baseline (0.64 on this
    # eval set), so a non-learning regression cannot pass it
    pytest.param("bayesian_sgld.py",
                 ["--epochs", "100", "--burn-in", "70",
                  "--lr", "2e-4", "--max-rmse", "0.6"],
                 marks=pytest.mark.slow),   # ~18s (tier-1 budget)
    pytest.param("stochastic_depth.py",
                 ["--epochs", "5", "--num-samples", "1024",
                  "--min-acc", "0.5"],
                 marks=pytest.mark.slow),   # ~36s (tier-1 budget)
    pytest.param("train_imagenet.py",
                 ["--benchmark", "1", "--num-layers", "18",
                  "--num-classes", "4", "--image-shape", "3,16,16",
                  "--batch-size", "4", "--num-examples", "8",
                  "--num-epochs", "1", "--lr", "0.01",
                  "--lr-step-epochs", "", "--kv-store", "local"],
                 marks=pytest.mark.slow),   # ~22s (tier-1 budget);
    # symbolic fit/kvstore coverage stays fast via svm/rbm_digits +
    # test_distributed launcher tests
]


@pytest.mark.parametrize(
    "script,args", CASES,
    ids=[getattr(c, "values", c)[0] for c in CASES])
def test_example_runs(script, args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script)] + list(args),
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, \
        f"{script} failed:\n{p.stdout[-2000:]}\n{p.stderr[-2000:]}"


def test_serve_bench_smoke():
    """tools/serve_bench.py --smoke: the closed-loop load generator
    must complete losslessly with zero recompiles during load (it
    exits 1 otherwise)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    tools = os.path.join(os.path.dirname(EXAMPLES), "tools")
    p = subprocess.run(
        [sys.executable, os.path.join(tools, "serve_bench.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, \
        f"serve_bench --smoke failed:\n{p.stdout[-2000:]}\n" \
        f"{p.stderr[-2000:]}"
    assert "SMOKE PASS" in p.stdout


@pytest.mark.slow   # ~32s on 1 CPU (tier-1 budget); the exposition
# path keeps fast coverage via test_metrics_dump_smoke and the fleet
# replay variant stays pinned in test_fleet's slow tier
def test_load_replay_smoke():
    """tools/load_replay.py --smoke: a tiny seeded trace replayed
    against BOTH serving front ends must be deterministic (bit-
    identical schedule), recompile-free, exactly accounted (typed
    served/shed/expired partition sums to submitted), and must emit a
    well-formed CAPACITY json plus a clean exposition carrying the
    mxtpu_slo_*/mxtpu_ts_*/tenant series (it exits 1 otherwise)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    tools = os.path.join(os.path.dirname(EXAMPLES), "tools")
    p = subprocess.run(
        [sys.executable, os.path.join(tools, "load_replay.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, \
        f"load_replay --smoke failed:\n{p.stdout[-2000:]}\n" \
        f"{p.stderr[-2000:]}"
    assert "SMOKE PASS" in p.stdout


def test_metrics_dump_smoke():
    """tools/metrics_dump.py --smoke: the observability exposition path
    (registry -> 4-subsystem instrumentation -> Prometheus text ->
    JSONL round-trip) must hold end to end (it exits 1 otherwise)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    tools = os.path.join(os.path.dirname(EXAMPLES), "tools")
    p = subprocess.run(
        [sys.executable, os.path.join(tools, "metrics_dump.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, \
        f"metrics_dump --smoke failed:\n{p.stdout[-2000:]}\n" \
        f"{p.stderr[-2000:]}"
    assert "SMOKE PASS" in p.stdout


@pytest.mark.slow   # ~160s of XLA CPU compile for the 4-stage ResNet
def test_pipeline_parallel_example_runs():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = subprocess.run(
        [sys.executable,
         os.path.join(EXAMPLES, "pipeline_parallel_resnet.py"),
         "--steps", "1"],
        capture_output=True, text=True, timeout=700, env=env)
    assert p.returncode == 0, \
        f"pipeline example failed:\n{p.stdout[-2000:]}\n" \
        f"{p.stderr[-2000:]}"
