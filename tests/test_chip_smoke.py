"""chip_smoke.py, rehearsed where there is no chip.

The script itself only runs on a TPU (``chiprun -- python chip_smoke.py``).
What the CPU can hold to account:

- the control flow of the train and serve phases, by calling the phase
  functions at toy sizes (their checks run as they do on the chip; only
  the kernel dispatch differs: the ``jnp`` reference instead of Mosaic);
- that ``python chip_smoke.py`` refuses to run without a TPU, before
  anything is built;
- that every kernel of the kernels phase still LOWERS for a TPU at
  head_dim 128, and both flat kernels at their cells' shapes
  (``jax.export`` cross-lowering: the Pallas front end, not Mosaic's
  own compile);
- the compile-cache placement rule of ``runtime.enable_compile_cache``.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402
from mxnet_tpu import runtime  # noqa: E402


def test_train_phase_toy():
    from mxnet_tpu.gluon.model_zoo import vision
    line = chip_smoke.train_phase(
        lambda: vision.resnet18_v1(layout="NHWC", stem_s2d=True),
        image=32, batch=8, dtype="float32", warmup=1, steps=2)
    assert line["platform"] == "cpu" and line["step_programs"] == 1
    assert line["block_until_ready_waits"] is True


def test_serve_phase_toy():
    from mxnet_tpu.serving.llm.model import DecoderConfig
    # two slots for three requests, so one waits its turn; a context of
    # one block keeps the ladder of warmed programs short (tier-1 budget)
    line = chip_smoke.serve_phase(
        DecoderConfig(max_context=64), prompt_lens=(4, 20, 40),
        new_tokens=6, max_seqs=2, block_size=64)
    assert line["compiles_during_load"] == 0
    # off the chip the engine must have been built on the reference,
    # and the phase must have seen that from the program itself
    assert line["attention"] == "jnp_reference"


@pytest.mark.parametrize("T,lens", [
    (8, [1] * 8), (23, [16] + [1] * 7), (38, [16, 16] + [1] * 6),
    (128, [16] * 8), (5, [1] * 5)])
def test_latent_batch_packs_chunks_beside_decodes(T, lens):
    """The step's own tokens: every slot but 0, chunks first, a row's
    positions ascending; behind them the tail an earlier step left,
    whose sequence the step holds too (what `_build_batch` leaves)."""
    import numpy as np
    sids, poss, valid = chip_smoke.latent_batch(
        np.random.RandomState(T), T, 32, 16)
    assert len(sids) == len(poss) == T
    assert valid == sum(lens[:-1]) and 0 not in sids[:valid]
    at = 0
    for n in lens[:-1]:
        assert len(set(sids[at:at + n])) == 1
        assert (np.diff(poss[at:at + n]) == 1).all()
        assert 0 <= poss[at] and poss[at + n - 1] < 32 * 16
        at += n
    assert len(set(sids[:valid])) == len(lens) - 1
    assert set(sids[valid:]) == {len(lens) - 1}      # the stale tail
    assert len(lens) - 1 in sids[:valid]


def test_latent_phase_toy():
    """The phase's control flow, the kernel in interpret mode: a pool of
    pages of 4, a step of 23 tokens (a chunk astride two query blocks at
    16 heads) and one of 8."""
    line = chip_smoke.latent_phase(
        cases=((8, 8), (23, 16)), layers=2, num_blocks=129, block_size=4,
        width=128, heads=16, v_dim=64, dtype="float32", reps=1,
        interpret=True)
    assert line["phase"] == "latent" and line["interpret"] is True
    assert set(line["ms_per_call"]) == {"T8_MB8", "T23_MB16"}
    assert max(line["rel_err"].values()) < 1e-4


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_ragged_phase_toy(dtype):
    """The phase's control flow, the kernel in interpret mode at 8 heads
    of 128 (so a chunk takes a head at a time, a decode row the whole
    buffer): pages of 4, a step of 23 tokens and one of 8."""
    line = chip_smoke.ragged_phase(
        cases=((8, 8, 12.0), (23, 40, 100.0)), layers=2, num_blocks=321,
        block_size=4, heads=8, head_dim=128, dtype=dtype, reps=1,
        interpret=True)
    assert line["phase"] == "ragged" and line["dtype"] == dtype
    assert set(line["ms_per_call"]) == set(line["ms_per_call_clean_tail"]) \
        == {"T8_MB8", "T23_MB40"}
    assert max(line["rel_err"].values()) < 1e-4


def test_gqa_phase_toy():
    """The grouped case of the ``ragged`` phase, interpret mode: 8 query
    heads over 2 of 16, packed rows of 64, a step of 23 tokens and one
    of 8."""
    line = chip_smoke.gqa_phase(
        cases=((8, 8), (23, 16)), layers=2, num_blocks=129, block_size=4,
        heads=8, kv_heads=2, head_dim=16, dtype="float32", reps=1,
        interpret=True)
    assert line["phase"] == "ragged_gqa" and line["kv_heads"] == 2
    assert set(line["ms_per_call"]) == {"T8_MB8", "T23_MB16"}
    assert max(line["rel_err"].values()) < 1e-4


def test_ssd_phase_toy():
    """The ``ssd`` phase's control flow, the kernel in interpret mode: 4
    slots, steps of 4 decode rows, of a chunk of 5 beside 3 decode rows
    and padding, and of 4 chunks."""
    line = chip_smoke.ssd_phase(
        cases=(4, 9, 20), layers=3, slots=4, heads=4, head_dim=8,
        state=16, chunk=5, reps=3, interpret=True)
    assert line["phase"] == "ssd" and line["pool"] == [3, 4, 16, 32]
    assert set(line["ms_per_call"]) == {"T4", "T9", "T20"}
    assert max(line["rel_err"].values()) <= chip_smoke.SSD_TOL


def test_blockdiff_phase_toy():
    """The ``blockdiff`` phase's control flow, both kernels in interpret
    mode: 4 rows of a block of 4, and 3 beside a prompt chunk of 20."""
    line = chip_smoke.blockdiff_phase(
        cases=(16, 32), layers=2, num_blocks=65, block_size=8, heads=4,
        kv_heads=2, head_dim=16, block_length=4, experts=8, top_k=2,
        d_model=32, width=128, slots=4, median=40.0, dtype="float32",
        reps=2, interpret=True)
    assert line["phase"] == "blockdiff" and line["pool"] == [2, 65, 8, 64]
    assert set(line["ms_per_call_attn"]) == {"T16", "T32"} \
        == set(line["ms_per_call_experts"])
    assert line["experts_hit"] == {"T16": 8, "T32": 8}
    assert line["tiles"] == {"T16": 8, "T32": 8}
    assert max(line["rel_err"].values()) < 1e-4


def test_experts_phase_toy():
    """The ``experts`` phase's control flow, the kernel in interpret mode:
    a width of three lane tiles, and at T 80 experts of two tiles."""
    line = chip_smoke.experts_phase(
        cases=(4, 80), experts=8, top_k=2, d_model=32, width=384,
        dtype="float32", reps=2, interpret=True)
    assert line["phase"] == "experts" and line["experts"] == [8, 32, 384]
    assert set(line["ms_per_call"]) == {"T4", "T80"}
    assert line["experts_hit"]["T80"] == 8 < line["tiles"]["T80"]
    assert line["tiles"]["T4"] == line["experts_hit"]["T4"] <= 8
    assert max(line["rel_err"].values()) < 1e-4


def test_refuses_to_run_without_a_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout == "", "refused, yet printed a result"


def test_every_kernel_lowers_for_tpu_at_head_dim_128():
    cases = chip_smoke.kernel_cases(
        attn_shape=(2, 12, 512, 64), ragged_shape=(16, 8, 128, 16, 65, 8),
        matmul_shape=(256, 1024, 4096), interpret=False)
    names = []
    for name, kernel, _reference, args, _bound in cases:
        exported = jax.export.export(jax.jit(kernel),
                                     platforms=["tpu"])(*args)
        assert "tpu_custom_call" in exported.mlir_module(), name
        names.append(name)
    assert {"flash_fwd", "flash_bwd_bias", "ragged_flat_f32",
            "ragged_flat_int8", "quantized_matmul_int8"} <= set(names)


@pytest.mark.parametrize("T,MB", [(8, 256), (23, 512), (128, 512)])
def test_latent_kernel_lowers_for_tpu_at_the_cells_shapes(T, MB):
    import jax.numpy as jnp
    from mxnet_tpu.ops.ragged_attention import mla_flat_attention

    def kernel(q, pool, tables, sids, poss):
        return mla_flat_attention(q, pool, tables, sids, poss, 192 ** -0.5,
                                  512, layer=4, use_pallas=True,
                                  interpret=False)
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((T, 16, 640), jnp.float32), ((9, 4097, 16, 640), jnp.bfloat16),
        ((8, MB), jnp.int32), ((T,), jnp.int32), ((T,), jnp.int32))]
    exported = jax.export.export(jax.jit(kernel), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.mark.parametrize("pages", ["float32", "int8"])
@pytest.mark.parametrize("T,MB", [(8, 64), (23, 128), (38, 128), (128, 128)])
def test_flat_kernel_lowers_for_tpu_at_the_cells_shapes(T, MB, pages):
    import jax.numpy as jnp
    from mxnet_tpu.ops.ragged_attention import ragged_flat_attention

    def kernel(q, kp, vp, tables, sids, poss, *scales):
        kw = dict(zip(("k_scales", "v_scales"), scales))
        return ragged_flat_attention(q, kp, vp, tables, sids, poss, layer=11,
                                     use_pallas=True, interpret=False, **kw)
    pool = (24, 1025, 16, 16, 128)
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((T, 16, 128), jnp.float32), (pool, pages), (pool, pages),
        ((8, MB), jnp.int32), ((T,), jnp.int32), ((T,), jnp.int32))]
    if pages == "int8":
        args += [jax.ShapeDtypeStruct(pool[:-1], jnp.float32)] * 2
    exported = jax.export.export(jax.jit(kernel), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.mark.parametrize("E,m,k,T", [(128, 768, 8, 128), (128, 768, 8, 512),
                                     (64, 1408, 6, 8), (64, 1408, 6, 128)])
def test_expert_products_lower_for_tpu_at_the_cells_shapes(E, m, k, T):
    """One layer of the block-diffusion cell's experts and of the
    ``deepseek_v3`` cell's, each at its whole width in one block."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe

    def kernel(x, sel, gates, valid, wg, wu, wd):
        return moe.moe_experts(x, sel, gates, valid, wg, wu, wd,
                               use_pallas=True, interpret=False)
    d, bf16 = 2048, jnp.bfloat16
    assert moe._column_block(d, m, 2, moe.VMEM_BUDGET) == m
    args = [jax.ShapeDtypeStruct(s, t) for s, t in (
        ((T, d), bf16), ((T, k), jnp.int32), ((T, k), jnp.float32),
        ((T,), jnp.int32), ((E, d, m), bf16), ((E, d, m), bf16),
        ((E, m, d), bf16))]
    exported = jax.export.export(jax.jit(kernel), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.fixture
def _restore_cache_config():
    flags = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    prev = {f: getattr(jax.config, f) for f in flags}
    yield
    for f, v in prev.items():
        jax.config.update(f, v)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def test_cache_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                _restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.enable_compile_cache()
    assert runtime.enable_compile_cache() == first
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    """JAX reads JAX_COMPILATION_CACHE_DIR when it is imported, so the
    rule is observed in a fresh interpreter."""
    code = ("import json, jax\n"
            "from mxnet_tpu import runtime\n"
            "read = jax.config.jax_compilation_cache_dir\n"
            "print(json.dumps([read, runtime.enable_compile_cache(),\n"
            "                  jax.config.jax_compilation_cache_dir]))\n")
    want = str(tmp_path / "placed")
    p = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120, check=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=want))
    assert json.loads(p.stdout.splitlines()[-1]) == [want, want, want]
