"""Real multi-process distributed training.

The reference exercises its wire protocol with local multi-process
launches (tests/nightly/dist_sync_kvstore.py via tools/launch.py
--launcher local); this is the TPU-build analogue, and it goes through
the SAME user-facing door: ``mxnet_tpu.launch`` spawns N OS processes
(each a jax process with one virtual CPU device) that join via the
MXNET_TPU_* env plumbing + jax.distributed.initialize. Covers the
KVStoreTPU('dist_sync') compiled psum reduce and a ShardedTrainer dp
step over the process-spanning mesh, asserting byte-identical results
on every rank.
"""
import os
import sys
import textwrap

import pytest

_WORKER = textwrap.dedent("""
    import os, sys, hashlib
    import numpy as np

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    sys.path.insert(0, "__REPO__")
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    # rank/world/coordinator arrive via the launcher's env plumbing
    nproc = int(os.environ["MXNET_TPU_NUM_WORKERS"])
    rank = int(os.environ["MXNET_TPU_RANK"])
    from mxnet_tpu.kvstore.tpu import init_process_group
    init_process_group()
    assert jax.process_count() == nproc, jax.process_count()

    # ---- kvstore dist_sync: compiled psum reduce --------------------
    kv = mx.kv.create("dist_sync")
    assert kv.type == "dist_sync"
    assert kv.rank == rank and kv.num_workers == nproc
    base = np.arange(12, dtype=np.float32).reshape(3, 4)
    kv.init("w", nd.array(np.zeros((3, 4), np.float32)))
    # each rank pushes a rank-dependent gradient
    kv.push("w", nd.array(base * (rank + 1)))
    out = nd.array(np.zeros((3, 4), np.float32))
    kv.pull("w", out=out)
    expect = base * sum(r + 1 for r in range(nproc))
    np.testing.assert_allclose(out.asnumpy(), expect, rtol=1e-6)
    kv.barrier()

    # ---- batched multi-key push: one flattened cross-process launch --
    kv.init("m1", nd.array(np.zeros((2, 2), np.float32)))
    kv.init("m2", nd.array(np.zeros(3, np.float32)))
    kv.push(["m1", "m2"],
            [nd.array(np.full((2, 2), float(rank + 1), np.float32)),
             nd.array(np.arange(3, dtype=np.float32) * (rank + 1))])
    o1 = nd.array(np.zeros((2, 2), np.float32))
    o2 = nd.array(np.zeros(3, np.float32))
    kv.pull("m1", out=o1)
    kv.pull("m2", out=o2)
    tot = sum(r + 1 for r in range(nproc))
    np.testing.assert_allclose(o1.asnumpy(), np.full((2, 2), float(tot)))
    np.testing.assert_allclose(o2.asnumpy(),
                               np.arange(3, dtype=np.float32) * tot)
    kv.barrier()

    # ---- compressed push: packed int32 payload over the process mesh --
    kvc = mx.kv.create("dist_sync")
    kvc.set_gradient_compression({"type": "2bit", "threshold": 1.0})
    kvc.init("g", nd.array(np.zeros(5, np.float32)))
    v = np.array([2.0, -0.5, 1.0, -3.0, 0.0], np.float32) * (rank + 1)
    kvc.push("g", nd.array(v))
    outc = nd.array(np.zeros(5, np.float32))
    kvc.pull("g", out=outc)
    # oracle: each rank quantizes its own v to {-1,0,1}, then sum
    q = lambda a: np.clip(np.where(a >= 1, 1, np.where(a <= -1, -1, 0)),
                          -1, 1).astype(np.float32)
    expect_c = sum(q(np.array([2.0, -0.5, 1.0, -3.0, 0.0]) * (r + 1))
                   for r in range(nproc))
    np.testing.assert_allclose(outc.asnumpy(), expect_c)
    kvc.barrier()

    # ---- ShardedTrainer dp step over the process-spanning mesh ------
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    mesh = parallel.make_mesh(dp=nproc)
    tr = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh)
    rng = np.random.RandomState(0)      # same data on every rank
    x = rng.randn(8, 6).astype(np.float32)
    y = (np.arange(8) % 4).astype(np.float32)
    losses = [float(tr.step(x, y).asscalar()) for _ in range(3)]
    assert losses[-1] < losses[0], losses

    # byte-identical trained params on every rank (params are replicated:
    # read this process's shard)
    h = hashlib.sha256()
    for n in sorted(tr.params):
        local = np.asarray(tr.params[n].addressable_data(0))
        h.update(np.ascontiguousarray(local).tobytes())
    with open(os.path.join("__OUT__", f"result_{rank}.txt"), "w") as f:
        f.write(f"RESULT rank={rank} losses={losses[-1]:.6f} "
                f"hash={h.hexdigest()}\\n")
""")


def test_launcher_refuses_several_workers_on_the_chips(monkeypatch):
    """Workers share one environment, so several of them on a TPU host
    would each open every chip: without cpu=True the launcher starts
    nothing and names the single-process mesh instead."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(repo)
    from mxnet_tpu.launch import launch
    with pytest.raises(ValueError, match="ONE process over a mesh"):
        launch(2, [sys.executable, "-c", "raise SystemExit(7)"])


@pytest.mark.parametrize(
    "nproc", [pytest.param(2, marks=pytest.mark.slow)])
# ~9s on 1 CPU (tier-1 budget): two fresh jax processes; launcher
# lifecycle stays fast via the teardown + multihost-emulation tests
def test_multiprocess_dist_sync(tmp_path, nproc, monkeypatch):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.replace("__REPO__", repo)
                      .replace("__OUT__", str(tmp_path)))
    # launch(cpu=True) overrides the runner's device-count/platform env
    # per worker; monkeypatch keeps this module's own env untouched for
    # later tests
    monkeypatch.syspath_prepend(repo)
    from mxnet_tpu.launch import launch
    rc = launch(nproc, [sys.executable, str(script)], cpu=True,
                timeout=420)
    assert rc == 0, f"launcher reported failure rc={rc}"
    results = []
    for r in range(nproc):
        f = tmp_path / f"result_{r}.txt"
        assert f.exists(), f"rank {r} wrote no result"
        results.append(f.read_text().strip())
    hashes = {line.split("hash=")[1] for line in results}
    assert len(hashes) == 1, f"ranks diverged: {results}"


def test_launcher_tears_down_group_on_rank_failure(tmp_path):
    """Failure detection (§5.3): one rank dies before the distributed
    join; the launcher must detect it, kill the surviving rank (which
    would otherwise block in the join forever), and report nonzero —
    within the timeout, not at it."""
    import time as _time
    from mxnet_tpu.launch import launch
    script = tmp_path / "dying_worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        rank = int(os.environ["MXNET_TPU_RANK"])
        if rank == 1:
            sys.exit(3)          # dies before joining the group
        # rank 0 would block in jax.distributed.initialize forever;
        # simulate the blocking join without paying jax import time
        time.sleep(600)
    """))
    t0 = _time.monotonic()
    rc = launch(2, [sys.executable, str(script)], cpu=True, timeout=120,
                quiet=True)
    elapsed = _time.monotonic() - t0
    assert rc == 3, f"expected the dead rank's code, got {rc}"
    assert elapsed < 60, f"teardown took {elapsed:.0f}s (no fail-fast)"


_MH_WORKER = textwrap.dedent("""
    import os, sys, hashlib
    import numpy as np

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    sys.path.insert(0, "__REPO__")
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    world = int(os.environ["MXNET_TPU_NUM_WORKERS"])
    rank = int(os.environ["MXNET_TPU_RANK"])
    host_rank = int(os.environ["TEST_HOST_RANK"])
    per_host = int(os.environ["TEST_PER_HOST"])
    # host-major rank assignment (reference: tools/launch.py:29 dmlc
    # tracker hands worker ids out per host)
    assert rank // per_host == host_rank, (rank, host_rank)
    assert world == 2 * per_host, world

    from mxnet_tpu.kvstore.tpu import init_process_group
    init_process_group()
    assert jax.process_count() == world, jax.process_count()

    kv = mx.kv.create("dist_sync")
    base = np.arange(8, dtype=np.float32)
    kv.init("w", nd.array(np.zeros(8, np.float32)))
    kv.push("w", nd.array(base * (rank + 1)))
    out = nd.array(np.zeros(8, np.float32))
    kv.pull("w", out=out)
    got = out.asnumpy()
    expect = base * sum(r + 1 for r in range(world))
    np.testing.assert_array_equal(got, expect)
    kv.barrier()
    h = hashlib.sha256(np.ascontiguousarray(got).tobytes()).hexdigest()
    with open(os.path.join("__OUT__", f"mh_result_{rank}.txt"), "w") as f:
        f.write(f"rank={rank} hash={h}\\n")
""")


def test_multihost_launcher_emulation(tmp_path, monkeypatch):
    """Two launcher invocations on one box emulate a 2-host x 2-proc
    cluster sharing a coordinator (reference: tools/launch.py:29 ssh
    bring-up, one `launch.py -n 2` per host): host-major rank
    assignment and a byte-exact 4-way reduce across both "hosts"."""
    import threading as _threading

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "mh_worker.py"
    script.write_text(_MH_WORKER.replace("__REPO__", repo)
                      .replace("__OUT__", str(tmp_path)))
    monkeypatch.syspath_prepend(repo)
    from mxnet_tpu.launch import launch, _free_port

    per_host = 2
    coordinator = f"127.0.0.1:{_free_port()}"
    rcs = {}

    def one_host(host_rank):
        rcs[host_rank] = launch(
            per_host, [sys.executable, str(script)],
            coordinator=coordinator, num_hosts=2, host_rank=host_rank,
            cpu=True, timeout=420,
            env_extra={"TEST_HOST_RANK": str(host_rank),
                       "TEST_PER_HOST": str(per_host)})

    threads = [_threading.Thread(target=one_host, args=(k,))
               for k in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rcs == {0: 0, 1: 0}, f"launcher rcs: {rcs}"

    hashes = set()
    for r in range(2 * per_host):
        f = tmp_path / f"mh_result_{r}.txt"
        assert f.exists(), f"rank {r} wrote no result"
        hashes.add(f.read_text().split("hash=")[1].strip())
    assert len(hashes) == 1, "ranks diverged across hosts"
