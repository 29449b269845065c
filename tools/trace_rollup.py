#!/usr/bin/env python
"""Roll up a captured TPU device trace into per-op-family time shares.

Thin CLI over :mod:`mxnet_tpu.observability.rollup`. Reads the
``*.trace.json.gz`` that ``BENCH_PROFILE=<dir>`` writes (jax.profiler /
XPlane -> trace-viewer JSON), sums XLA-op durations on the device's
"XLA Ops" thread, groups by fusion-family prefix, and prints ms/step +
share. Use it to quantify a lever's effect between two captures:

    python tools/trace_rollup.py perf_traces/<ts>_<tag>  [--steps 50]
    python tools/trace_rollup.py A_dir B_dir             # side by side

The scan wrapper (`while.*`) is excluded: XLA counts the scan body
once, so the inner ops already represent one step times `--steps`.
"""
import argparse
import importlib.util
import os
import sys

# load rollup.py by file path: `import mxnet_tpu` drags jax in, and
# this CLI must keep working on trace files from machines without it
_spec = importlib.util.spec_from_file_location(
    "_mxtpu_rollup",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "mxnet_tpu", "observability", "rollup.py"))
_ru = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ru)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("paths", nargs="+",
                    help="trace dir(s) (perf_traces/<ts>_<tag>) or files")
    ap.add_argument("--steps", type=int, default=50,
                    help="timed steps in the capture (bench default 50)")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()

    results = []
    for p in args.paths:
        try:
            fam, total = _ru.rollup(p)
        except _ru.RollupError as e:
            raise SystemExit(str(e))
        results.append((fam, total))
        print(f"\n{p}: "
              + _ru.family_table(fam, total, steps=args.steps,
                                 top=args.top))

    if len(results) == 2:
        report = _ru.diff(results[0], results[1], steps=args.steps)
        print("\n" + _ru.format_diff(report, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
