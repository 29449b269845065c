"""XLA device-trace rollup: per-op-family time attribution as a library.

``tools/trace_rollup.py`` started life as a one-off script reading the
``*.trace.json.gz`` a ``BENCH_PROFILE`` capture writes; every perf PR
since has needed the same parse (which op families own the device
time? what did this lever change?), so the logic lives here and the
tool is a thin CLI. Three entry points:

- :func:`rollup` — sum XLA-op durations on the device "XLA Ops" lane of
  a capture, grouped by fusion-family prefix;
- :func:`diff` — the before/after report between two captures (the A/B
  evidence a kernel PR must show);
- :func:`summary` — a compact JSON-able digest for a benchmark record
  to embed, so that it carries its own attribution instead of a bare
  MFU scalar.

The scan wrapper (``while.*``) is excluded everywhere: XLA counts a
scan body once, so the inner ops already represent one step times the
capture's step count.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re

__all__ = ["RollupError", "find_trace", "rollup", "family_table",
           "diff", "format_diff", "summary"]


class RollupError(ValueError):
    """The capture cannot be rolled up (no trace file, no TPU device
    lane, empty op thread). ValueError so library callers can catch it
    without importing this module's internals."""


def find_trace(path):
    """Resolve ``path`` (a trace file, or a capture directory holding
    one) to the newest ``*.trace.json.gz`` under it."""
    if os.path.isfile(path):
        return path
    hits = glob.glob(os.path.join(path, "**", "*.trace.json.gz"),
                     recursive=True)
    if not hits:
        raise RollupError(f"no *.trace.json.gz under {path}")
    return sorted(hits)[-1]


def _load_events(trace):
    opener = gzip.open if trace.endswith(".gz") else open
    with opener(trace) as f:
        data = json.load(f)
    return data.get("traceEvents", [])


def family_of(op_name):
    """Fusion-family key: the op name with trailing digits/dots
    stripped, so ``fusion.123`` and ``fusion.7`` aggregate."""
    return re.sub(r"[.\d]+$", "", op_name)


def rollup(path):
    """Per-op-family device time of one capture.

    Returns ``(families, total_us)`` where ``families`` is a Counter of
    microseconds by family. Only the TPU device processes' "XLA Ops"
    lanes count — host lanes and CPU/GPU captures (laid out
    differently) raise :class:`RollupError` instead of silently
    producing a host-time table that would be read as device time.
    """
    trace = find_trace(path)
    events = _load_events(trace)
    device_pids = {e["pid"] for e in events
                   if e.get("ph") == "M" and e.get("name") == "process_name"
                   and "TPU" in (e.get("args") or {}).get("name", "")}
    op_tids = {(e["pid"], e["tid"]) for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"
               and e.get("pid") in device_pids
               and (e.get("args") or {}).get("name") == "XLA Ops"}
    if not op_tids:
        raise RollupError(
            f"{trace}: no TPU 'XLA Ops' thread found — this is not a TPU "
            "device capture (CPU/GPU traces lay out differently)")
    fam = collections.Counter()
    total = 0
    for e in events:
        if e.get("ph") != "X" or (e.get("pid"), e.get("tid")) not in op_tids:
            continue
        name = e.get("name", "")
        if name.startswith("while"):
            continue  # scan wrapper double-counts its body
        d = e.get("dur", 0)
        fam[family_of(name)] += d
        total += d
    if total == 0:
        raise RollupError(f"{trace}: TPU op thread present but empty")
    return fam, total


def family_table(fam, total, steps=50, top=12):
    """Printable ms/step + share table of one rollup."""
    lines = [f"{total / 1e3:.1f} ms device time over {steps} steps -> "
             f"{total / 1e3 / steps:.2f} ms/step"]
    for name, d in fam.most_common(top):
        lines.append(f"  {d / 1e3 / steps:7.2f} ms/step "
                     f"{100 * d / total:5.1f}%  {name}")
    return "\n".join(lines)


def diff(before, after, steps=50):
    """Structured A→B comparison of two captures (paths or pre-computed
    ``(families, total)`` pairs): per-family ms/step deltas sorted by
    magnitude plus the total shift — the report a perf lever is judged
    on."""
    fa, ta = before if isinstance(before, tuple) else rollup(before)
    fb, tb = after if isinstance(after, tuple) else rollup(after)
    fams = sorted(set(fa) | set(fb),
                  key=lambda k: -abs(fb.get(k, 0) - fa.get(k, 0)))
    rows = []
    for k in fams:
        a_us, b_us = fa.get(k, 0), fb.get(k, 0)
        rows.append({
            "family": k,
            "before_ms_per_step": round(a_us / 1e3 / steps, 4),
            "after_ms_per_step": round(b_us / 1e3 / steps, 4),
            "delta_ms_per_step": round((b_us - a_us) / 1e3 / steps, 4),
        })
    return {
        "steps": steps,
        "total_before_ms_per_step": round(ta / 1e3 / steps, 4),
        "total_after_ms_per_step": round(tb / 1e3 / steps, 4),
        "total_delta_ms_per_step": round((tb - ta) / 1e3 / steps, 4),
        "families": rows,
    }


def format_diff(report, top=12, threshold_ms=0.005):
    """Human rendering of a :func:`diff` report (B - A, ms/step)."""
    lines = [
        "delta (B - A), ms/step: total "
        f"{report['total_delta_ms_per_step']:+.2f} "
        f"({report['total_before_ms_per_step']:.2f} -> "
        f"{report['total_after_ms_per_step']:.2f})"]
    for row in report["families"][:top]:
        d = row["delta_ms_per_step"]
        if abs(d) > threshold_ms:
            lines.append(f"  {d:+7.2f}  {row['family']}")
    return "\n".join(lines)


def summary(path, steps=50, top=8):
    """Compact digest of a capture for embedding into bench artifacts:
    total ms/step plus the top op families with their share. Returns a
    plain-JSON dict; raises :class:`RollupError` like :func:`rollup`."""
    fam, total = rollup(path)
    return {
        "trace": find_trace(path),
        "steps": steps,
        "device_ms_per_step": round(total / 1e3 / steps, 4),
        "families": [
            {"family": name,
             "ms_per_step": round(d / 1e3 / steps, 4),
             "share_pct": round(100 * d / total, 2)}
            for name, d in fam.most_common(top)],
    }
