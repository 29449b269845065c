"""Multi-process job launcher: ``python -m mxnet_tpu.launch -n 4 train.py``.

TPU-native analogue of the reference's ``tools/launch.py`` + dmlc_tracker
(reference: tools/launch.py:29 — spawns N workers + parameter servers
over local/ssh/mpi launchers). Here there are no parameter servers to
start: the launcher spawns N OS processes, hands each the coordinator
address / world size / rank via MXNET_TPU_* env vars, and
``mxnet_tpu.kvstore.tpu.init_process_group`` (called by
``mx.kv.create("dist_sync")``) joins them into one ``jax.distributed``
job whose collectives run compiled.

Single-host, N CPU processes (the reference's ``--launcher local``):
    python -m mxnet_tpu.launch -n 4 --cpu train.py --epochs 1

Multi-host: run the same command once per host with ``--coordinator
HOST0:PORT --num-hosts H --host-rank k`` — ranks are assigned
host-major.

A chip belongs to one process at a time, and workers inherit one
environment, so N workers on a TPU host would each open every chip.
Without ``--cpu`` the launcher therefore starts ONE worker per host
(``-n 1``) and refuses more: that one process drives all of the host's
chips over a mesh (``Trainer.compile_step(mesh="dp=4")``,
``LLMServer(mesh="tp=4")``).
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
import time

__all__ = ["main", "launch"]


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _pump(rank, stream, dst):
    for line in iter(stream.readline, ""):
        dst.write(f"[{rank}] {line}")
        dst.flush()
    stream.close()


def launch(n, command, coordinator=None, num_hosts=1, host_rank=0,
           cpu=False, quiet=False, env_extra=None, timeout=None):
    """Spawn ``n`` local worker processes running ``command`` (argv
    list) and join them; returns the first nonzero exit code (0 if all
    succeeded), or 124 on timeout. Workers see MXNET_TPU_COORDINATOR /
    _NUM_WORKERS / _RANK plus the reference-compatible DMLC_* names.
    ``timeout`` (seconds) bounds the whole group — a rank that hangs in
    the distributed join (e.g. a peer died before connecting) is torn
    down rather than blocking forever. Raises ``ValueError`` for
    ``n > 1`` without ``cpu``: see the module docstring."""
    if n > 1 and not cpu:
        raise ValueError(
            f"-n {n} without --cpu: {n} workers on one host would each "
            "open every chip of it, and a chip belongs to one process. "
            "Drive the host's chips from ONE process over a mesh "
            "(Trainer.compile_step(mesh='dp=4'), LLMServer(mesh='tp=4')), "
            "start one worker per host (-n 1 --num-hosts H), or pass "
            "--cpu for a CPU process group")
    if coordinator is None:
        coordinator = f"127.0.0.1:{_free_port()}"
    world = n * num_hosts
    procs = []
    pumps = []
    for local_rank in range(n):
        rank = host_rank * n + local_rank
        env = dict(os.environ)
        root_host, root_port = coordinator.rsplit(":", 1)
        env.update({
            "MXNET_TPU_COORDINATOR": coordinator,
            "MXNET_TPU_NUM_WORKERS": str(world),
            "MXNET_TPU_RANK": str(rank),
            # reference env-var surface (ps-lite names) for scripts
            # ported from the reference
            "DMLC_PS_ROOT_URI": root_host,
            "DMLC_PS_ROOT_PORT": root_port,
            "DMLC_NUM_WORKER": str(world),
            "DMLC_WORKER_ID": str(rank),
            "DMLC_ROLE": "worker",
        })
        if env_extra:
            env.update(env_extra)
        if cpu:
            env["JAX_PLATFORMS"] = "cpu"
            flags = env.get("XLA_FLAGS", "")
            # override (not setdefault): a parent exporting an 8-device
            # flag must not leak a wrong world size into the workers
            flags = " ".join(
                f for f in flags.split()
                if not f.startswith(
                    "--xla_force_host_platform_device_count"))
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=1"
            ).strip()
        p = subprocess.Popen(
            command, env=env,
            stdout=subprocess.PIPE if not quiet else subprocess.DEVNULL,
            stderr=subprocess.STDOUT if not quiet else subprocess.DEVNULL,
            text=not quiet)
        procs.append(p)
        if not quiet:
            t = threading.Thread(target=_pump,
                                 args=(rank, p.stdout, sys.stdout),
                                 daemon=True)
            t.start()
            pumps.append(t)

    rc = 0
    deadline = (time.monotonic() + timeout) if timeout else None
    try:
        pending = list(procs)
        while pending:
            # poll ALL ranks: a failure on any rank must tear the group
            # down even while an earlier rank is blocked in the join
            done = [p for p in pending if p.poll() is not None]
            for p in done:
                pending.remove(p)
                if p.returncode != 0 and rc == 0:
                    rc = p.returncode
                    for q in pending:
                        q.send_signal(signal.SIGTERM)
            if pending:
                if deadline and time.monotonic() > deadline:
                    rc = rc or 124
                    for q in pending:
                        q.kill()
                    break
                time.sleep(0.1)
    except KeyboardInterrupt:
        for q in procs:
            if q.poll() is None:
                q.kill()
        raise
    for t in pumps:
        t.join(timeout=5)
    return rc


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.launch",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("-n", "--num-workers", type=int, required=True,
                        help="worker processes to launch on this host")
    parser.add_argument("--coordinator", type=str, default=None,
                        help="HOST:PORT of rank 0's coordinator "
                             "(default: a free local port)")
    parser.add_argument("--num-hosts", type=int, default=1)
    parser.add_argument("--host-rank", type=int, default=0)
    parser.add_argument("--cpu", action="store_true",
                        help="force each worker onto a 1-device CPU "
                             "backend (tests)")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="script (and args) to run; a .py file is "
                             "run with the current interpreter")
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")
    command = args.command
    if command[0].endswith(".py"):
        command = [sys.executable] + command
    try:
        return launch(args.num_workers, command,
                      coordinator=args.coordinator,
                      num_hosts=args.num_hosts, host_rank=args.host_rank,
                      cpu=args.cpu)
    except ValueError as e:
        parser.error(str(e))


if __name__ == "__main__":
    sys.exit(main())
