"""Benchmark artifact writers shared by the serving load tools.

``tools/llm_bench.py`` writes its ``BENCH_llm_rNN.json`` and
``tools/load_replay.py`` its ``CAPACITY_rNN.json`` through the two
``emit_*_snapshot`` helpers here, so both series share one numbering
rule and one refusal rule: a run that recompiled during its window,
lost requests or produced no value still gets an artifact (the series
must show the attempt), but with ``"skipped"`` set and ``"value":
null``.
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
    JSONL bench.py appends at exit), or {} — parsing shared with
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


def _span_stats(snap):
    """Host-tracing digest from the bench's metrics snapshot: the
    mxtpu_trace_* counters (spans started/dropped, export bytes), so a
    bench artifact records whether its host-span trace is complete."""
    out = {
        "spans_started": _metric_value(
            snap, "mxtpu_trace_spans_started_total"),
        "spans_dropped": _metric_value(
            snap, "mxtpu_trace_spans_dropped_total"),
        "trace_export_bytes": _metric_value(
            snap, "mxtpu_trace_export_bytes_total"),
    }
    return out if any(v is not None for v in out.values()) else None


def _is_valid(rec):
    return (rec is not None and rec.get("value") is not None
            and not rec.get("suspect") and not rec.get("skipped"))


def emit_llm_snapshot(rec, out_dir=None):
    """Write a BENCH_llm_rNN.json for an llm_bench capture; returns
    its path.

    A skipped / suspect / valueless record still produces an artifact
    (the trajectory must show the attempt) but with ``"skipped"`` set
    and ``"value": null`` — a load window that recompiled or lost
    requests can never masquerade as a healthy tokens/sec headline. The
    serving-economics numbers (tokens/sec, TTFT p50/p99, KV-block
    occupancy) come from the run's own registry snapshot + the
    ``extra`` dict llm_bench computed from live server stats.
    """
    out_dir = out_dir or REPO
    cap = rec.get("_capture", {})
    snap = _last_metrics_snapshot(cap.get("metrics_log", ""))
    extra = rec.get("extra", {})
    nn = _next_round("BENCH_llm_r", out_dir)
    path = os.path.join(out_dir, f"BENCH_llm_r{nn:02d}.json")
    out = {
        "round": nn,
        "source": "tools/llm_bench.py (observability registry)",
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
            "tokens_per_sec": _metric_value(
                snap, "mxtpu_llm_tokens_per_sec"),
            "ttft_ms": extra.get("ttft_ms"),
            "kv_blocks_in_use": _metric_value(
                snap, "mxtpu_llm_kv_blocks_in_use"),
            "kv_blocks_total": _metric_value(
                snap, "mxtpu_llm_kv_blocks_total"),
            "kv_occupancy": extra.get("kv_occupancy"),
            "requests": extra.get("requests"),
            "preemptions": extra.get("preemptions"),
            "device_kind": extra.get("device_kind"),
            "xla_compiles": _metric_value(snap, "mxtpu_xla_compile_total"),
            "compiles_during_load": extra.get("compiles_during_load"),
            # the decode-speed knobs (ISSUE 12) + the observed draft
            # acceptance rate, so the trend table can attribute a
            # headline to its chunk/speculation configuration
            "knobs": extra.get("knobs"),
            "spec_accept_rate": extra.get("spec_accept_rate"),
            "metrics_log": cap.get("metrics_log"),
            "span_stats": _span_stats(snap),
        })
        # saturation runs (llm_bench --overload) carry their shed-rate
        # + served-TTFT block so the BENCH trajectory records behavior
        # AT overload, not just underload
        if extra.get("overload") is not None:
            out["overload"] = extra["overload"]
        # shared-prefix runs (llm_bench --prefix-share, ISSUE 13)
        # carry the prefix-cache economics — hit rate, prefill tokens
        # saved, and the cache-off TTFT control from the same config —
        # so the trend table can attribute a TTFT win to the cache
        if extra.get("prefix") is not None:
            out["prefix"] = extra["prefix"]
        # multi-LoRA runs (llm_bench --adapters, ISSUE 17) carry the
        # bank economics and the tokens/sec + TTFT vs adapter-count
        # curve — the "N adapters from one program set" evidence
        if extra.get("adapters") is not None:
            out["adapters"] = extra["adapters"]
        if extra.get("adapters_curve") is not None:
            out["adapters_curve"] = extra["adapters_curve"]
    # SPMD decode (ISSUE 19): the mesh shape / structural sweep ride
    # BOTH branches — a --mesh-sweep run is deliberately "skipped"
    # (virtual devices prove structure, never a timing headline), yet
    # its per-tp table IS the artifact's payload
    if extra.get("mesh") is not None:
        out["mesh"] = extra["mesh"]
    if extra.get("mesh_sweep") is not None:
        out["mesh_sweep"] = extra["mesh_sweep"]
    # quantized weights (ISSUE 20): the served dtype's bytes /
    # params-per-chip block and the --weight-dtype sweep curve ride
    # BOTH branches too — the params-per-chip ratio is structural
    # evidence (byte counts, not clocks) and must survive even when a
    # run's timing headline is refused
    if extra.get("weights") is not None:
        out["weights"] = extra["weights"]
    if extra.get("weight_sweep") is not None:
        out["weight_sweep"] = extra["weight_sweep"]
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    return path


def emit_capacity_snapshot(rec, out_dir=None):
    """Write a ``CAPACITY_rNN.json`` for a load-replay capacity run;
    returns its path.

    Same skip-refusal contract as :func:`emit_llm_snapshot`: a
    record that recompiled during the
    measured window, lost requests, or produced no measurable rate is
    still committed (the trajectory must show the attempt) but with a
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
