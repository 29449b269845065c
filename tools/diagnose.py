#!/usr/bin/env python
"""Diagnose the OS / hardware / python / jax / mxnet_tpu environment.

Reference: tools/diagnose.py (the script users paste into bug reports:
OS, hardware, python, pip, mxnet build features, network). Network
checks are omitted (this build targets zero-egress environments);
instead the TPU section probes the default backend in a child process
with a time limit, so this process never holds the chip and a backend
that fails to start is reported instead of ending the diagnosis.

  python tools/diagnose.py [--probe-timeout 60]
"""
import argparse
import os
import platform
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def section(title):
    print(f"----------{title}----------")


def check_os():
    section("System Info")
    for k in ("platform", "system", "machine", "processor", "release"):
        print(f"{k:>10}: {getattr(platform, k)()}")


def check_hardware():
    section("Hardware Info")
    try:
        with open("/proc/cpuinfo") as f:
            models = [ln.split(":", 1)[1].strip() for ln in f
                      if ln.startswith("model name")]
        print(f"{'cpu':>10}: {models[0] if models else '?'} "
              f"x{len(models)}")
        with open("/proc/meminfo") as f:
            total = next(ln for ln in f if ln.startswith("MemTotal"))
        print(f"{'memory':>10}: {total.split(':', 1)[1].strip()}")
    except OSError as e:
        print(f"unavailable: {e}")


def check_python():
    section("Python Info")
    print(f"{'version':>10}: {platform.python_version()}")
    print(f"{'executable':>10}: {sys.executable}")
    for mod in ("numpy", "jax", "jaxlib"):
        try:
            m = __import__(mod)
            print(f"{mod:>10}: {getattr(m, '__version__', '?')}")
        except ImportError:
            print(f"{mod:>10}: NOT INSTALLED")


def check_mxnet_tpu(timeout_s):
    section("mxnet_tpu Info")
    # child pinned to the CPU: the package facts need no chip, and the
    # accelerator probe below must find it free
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = ("import os, sys\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "import mxnet_tpu as mx\n"
            "print('%10s:' % 'package', os.path.dirname(mx.__file__))\n"
            "from mxnet_tpu.ops.registry import _REGISTRY\n"
            "print('%10s:' % 'ops', len(_REGISTRY), 'registered')\n"
            "from mxnet_tpu import runtime\n"
            "feats = runtime.Features()\n"
            "on = sorted(n for n in feats.keys()"
            " if feats.is_enabled(n))\n"
            "print('%10s:' % 'features', ', '.join(on))\n"
            "from mxnet_tpu import native\n"
            "print('%10s:' % 'native',\n"
            "      'recordio=' + ('ok' if native.recordio_lib()"
            " else 'unavailable'),\n"
            "      'imagepipe=' + ('ok' if native.imagepipe_lib()"
            " else 'unavailable'))\n")
    try:
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=timeout_s, env=env)
        if (p.stdout or "").strip():
            print(p.stdout.rstrip())
        if p.returncode != 0:
            tail = (p.stderr or "").strip().splitlines()
            print(f"    FAILED (rc={p.returncode}): "
                  f"{tail[-1] if tail else 'no stderr'}")
    except subprocess.TimeoutExpired:
        print(f"TIMED OUT (> {timeout_s}s)")


def check_tpu(timeout_s):
    section("Accelerator Info")
    code = ("import jax, json; ds = jax.devices(); "
            "print(json.dumps({'platform': ds[0].platform, "
            "'count': len(ds), "
            "'kind': getattr(ds[0], 'device_kind', '')}))")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)   # probe the DEFAULT backend
    try:
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=timeout_s, env=env)
        out = (p.stdout or "").strip().splitlines()
        if p.returncode == 0 and out:
            print(f"{'backend':>10}: {out[-1]}")
        else:
            err = (p.stderr or "").strip().splitlines()
            print(f"{'backend':>10}: FAILED "
                  f"({err[-1][:120] if err else 'no output'})")
    except subprocess.TimeoutExpired:
        print(f"{'backend':>10}: DOWN (init took >{timeout_s}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--probe-timeout", type=int, default=60,
                    help="budget for the accelerator probe; the "
                    "mxnet_tpu section (which may compile native code "
                    "on first use) gets 2x this")
    args = ap.parse_args()
    check_os()
    check_hardware()
    check_python()
    check_mxnet_tpu(2 * args.probe_timeout)
    check_tpu(args.probe_timeout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
