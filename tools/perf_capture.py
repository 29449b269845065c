"""The capacity artifact writer of ``tools/load_replay.py``.

``emit_capacity_snapshot`` writes a replay's ``CAPACITY_rNN.json``
under one numbering rule and one refusal rule: a run that recompiled
during its window, lost requests or produced no value still gets an
artifact (the series must show the attempt), but with ``"skipped"``
set and ``"value": null``.
"""
import datetime
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _now():
    return datetime.datetime.now(datetime.timezone.utc).isoformat()


def _last_metrics_snapshot(path):
    """Last registry snapshot of a MXNET_TPU_METRICS_LOG file (the
    JSONL the registry appends at exit), or {} — parsing shared with
    tools/metrics_dump.py so the two tools can never drift."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from metrics_dump import load_snapshots
    finally:
        sys.path.pop(0)
    try:
        snaps = load_snapshots(path)
    except OSError:
        return {}
    return snaps[-1]["metrics"] if snaps else {}


def _metric_value(snap, name):
    for series in snap.get(name, {}).get("series", []):
        if "value" in series:
            return series["value"]
    return None


def _next_round(prefix, out_dir):
    """Next round number of an artifact series in ``out_dir``."""
    top = 0
    for fname in os.listdir(out_dir):
        m = re.match(r"%s(\d+)\.json$" % re.escape(prefix), fname)
        if m:
            top = max(top, int(m.group(1)))
    return top + 1


def _is_valid(rec):
    return (rec is not None and rec.get("value") is not None
            and not rec.get("suspect") and not rec.get("skipped"))


def emit_capacity_snapshot(rec, out_dir=None):
    """Write a ``CAPACITY_rNN.json`` for a load-replay capacity run;
    returns its path.

    A record that recompiled during the measured window, lost
    requests, or produced no measurable rate is still written (the
    trajectory must show the attempt) but with a
    top-level ``"skipped"`` marker and ``"value": null`` — an
    unhealthy replay can never masquerade as a capacity headline.
    ``rec`` is ``observability.capacity.build_report`` output plus the
    replay's ``_capture`` block (tag, metrics_log, captured_at) and
    any ``skipped`` reasons ``tools/load_replay.py`` attached."""
    out_dir = out_dir or REPO
    cap = rec.get("_capture", {})
    snap = _last_metrics_snapshot(cap.get("metrics_log", ""))
    nn = _next_round("CAPACITY_r", out_dir)
    path = os.path.join(out_dir, f"CAPACITY_r{nn:02d}.json")
    out = {
        "round": nn,
        "source": "tools/load_replay.py (observability registry)",
        "captured_at": cap.get("captured_at", _now()),
        "tag": cap.get("tag"),
        "metric": rec.get("metric"),
        "unit": rec.get("unit"),
    }
    if not _is_valid(rec):
        out.update({
            "skipped": rec.get("skipped") or (
                "suspect" if rec.get("suspect") else "invalid"),
            "value": None,
            "detail": rec.get("detail"),
        })
    else:
        out.update({
            "value": rec.get("value"),
            "slo_attained": rec.get("slo_attained"),
            "slo": rec.get("slo"),
            "frontends": rec.get("frontends"),
            "chips": rec.get("chips"),
            "user_model": rec.get("user_model"),
            "window_s": rec.get("window_s"),
            "snapshots": rec.get("snapshots"),
            "trace": rec.get("trace"),
            "tenants": rec.get("tenants"),
            "device_kind": rec.get("device_kind"),
            "xla_compiles": _metric_value(snap,
                                          "mxtpu_xla_compile_total"),
            "compiles_during_replay": rec.get("compiles_during_replay"),
            "outcomes": rec.get("outcomes"),
            # prefix-cache hit rate over the tenant system prompts
            # (ISSUE 13): saved prefill is saved chip time, so the
            # reuse economics belong next to the capacity headline
            "llm_prefix": rec.get("llm_prefix"),
            # multi-LoRA economics (ISSUE 17): per-tenant adapter map
            # + bank hit/evict counters — how many variants the same
            # chip count actually served
            "llm_adapters": rec.get("llm_adapters"),
            # quantized-weight economics (ISSUE 20): served dtype,
            # weight bytes and the models-per-chip derivation under
            # the declared HBM model
            "llm_weights": rec.get("llm_weights"),
            "metrics_log": cap.get("metrics_log"),
            "detail": rec.get("detail"),
        })
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    return path
