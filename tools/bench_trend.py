#!/usr/bin/env python
"""Maintain the committed bench trend tables from BENCH artifacts.

Two trajectories, one classification discipline:

- ``BENCH_r*.json`` (training) -> the MFU / img/s table between the
  ``BENCH_TREND`` markers in docs/PERFORMANCE.md;
- ``BENCH_llm_r*.json`` (decode serving) -> the tokens/sec + TTFT
  table between the ``LLM_BENCH_TREND`` markers (appended on first
  run), so the serving-economics headline has the same committed,
  honestly-classified history as training MFU;
- ``MULTICHIP_r*.json`` (SPMD scaling) -> the devices → step-time /
  dispatches-per-step / T1/TN-speedup table between the
  ``MULTICHIP_TREND`` markers (tools/multichip_bench.py emits the
  point-based shape; older rounds only recorded the dryrun tail and
  render as structure-only rows).

The bench trajectory is only evidence if every artifact is classified
honestly: a failed run, a suspect timing or a reused earlier value is
not a valid headline, and a trend table that lists them as numbers
teaches the wrong lesson. This tool scans the repo's ``BENCH_r*.json``
(both the driver's ``{"n", "rc", "parsed"}`` wrapper shape and the
direct bench-output shape), classifies each round —

- ``valid``    rc=0, value present, not suspect/skipped/stale
- ``stale``    headline taken from an earlier capture (shown for
               context, never as evidence)
- ``skipped``  no measurement, value null
- ``invalid``  non-zero rc, unparseable output, or suspect timing

— and splices the rendered table between the ``BENCH_TREND`` markers in
``docs/PERFORMANCE.md`` (appending the section on first run):

    python tools/bench_trend.py            # rewrite the committed table
    python tools/bench_trend.py --dry-run  # print only
"""
import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "PERFORMANCE.md")
BEGIN = "<!-- BENCH_TREND:BEGIN (tools/bench_trend.py — do not edit by hand) -->"
END = "<!-- BENCH_TREND:END -->"
LLM_BEGIN = ("<!-- LLM_BENCH_TREND:BEGIN "
             "(tools/bench_trend.py — do not edit by hand) -->")
LLM_END = "<!-- LLM_BENCH_TREND:END -->"
MC_BEGIN = ("<!-- MULTICHIP_TREND:BEGIN "
            "(tools/bench_trend.py — do not edit by hand) -->")
MC_END = "<!-- MULTICHIP_TREND:END -->"
CAP_BEGIN = ("<!-- CAPACITY_TREND:BEGIN "
             "(tools/bench_trend.py — do not edit by hand) -->")
CAP_END = "<!-- CAPACITY_TREND:END -->"
HEADING = ("\n## Bench trend (MFU / throughput per round)\n\n"
           "Regenerate with `python tools/bench_trend.py` after "
           "every new `BENCH_rNN.json`; rows the table marks "
           "invalid/stale/skipped are artifacts, not evidence.\n\n")
LLM_HEADING = ("\n## LLM decode bench trend (tokens/sec + TTFT per "
               "round)\n\n"
               "Regenerate with `python tools/bench_trend.py` after "
               "every new `BENCH_llm_rNN.json` (tools/llm_bench.py); "
               "skipped rows recompiled or lost requests and are not "
               "evidence.\n\n")
CAP_HEADING = ("\n## Capacity trend (chips per 1M users, per round)\n\n"
               "Regenerate with `python tools/bench_trend.py` after "
               "every new `CAPACITY_rNN.json` (tools/load_replay.py). "
               "The headline is the replay's committed chips-per-"
               "1M-users under attained SLOs; a round whose SLOs did "
               "NOT attain is an overload experiment, not a capacity "
               "claim. CPU-host numbers trend the serving-stack "
               "economics (admission/batching/KV behavior), not real "
               "chip counts.\n\n")
MC_HEADING = ("\n## Multi-chip SPMD scaling trend (devices → step "
              "time / dispatches)\n\n"
              "Regenerate with `python tools/bench_trend.py` after "
              "every new `MULTICHIP_rNN.json` "
              "(tools/multichip_bench.py). CPU virtual-device step "
              "times share one host's FLOPs — the evidence here is "
              "program STRUCTURE (dispatches/step, recompiles, "
              "bit-exact parity), not chip scaling; the T1/TN speedup "
              "column becomes meaningful on real multi-chip "
              "captures.\n\n")


def _round_of(path, rec):
    if isinstance(rec.get("round"), int):
        return rec["round"]
    if isinstance(rec.get("n"), int):
        return rec["n"]
    m = re.search(r"BENCH_r(\d+)\.json$", path)
    return int(m.group(1)) if m else 0


def _img_s(inner):
    for probe in (inner.get("extra") or {}, inner):
        for key in ("train_img_s", "examples_per_sec"):
            v = probe.get(key)
            if v is not None:
                return float(v)
    return None


def classify(path, rec):
    """One table row: {round, status, mfu, img_s, tag, note}."""
    rnd = _round_of(path, rec)
    row = {"round": rnd, "status": "valid", "mfu": None, "img_s": None,
           "tag": rec.get("tag") or "", "note": ""}
    inner = rec
    if "rc" in rec:                      # driver wrapper shape
        if rec.get("rc") != 0:
            row.update(status="invalid",
                       note=f"rc={rec['rc']}: bench run failed")
            return row
        inner = rec.get("parsed")
        if not isinstance(inner, dict):
            row.update(status="invalid", note="unparseable bench output")
            return row
    if inner.get("suspect"):
        row.update(status="invalid",
                   note="suspect timing — self-check failed "
                        "(see suspect_reason in the artifact)")
        return row
    value = inner.get("value")
    unit = inner.get("unit") or ""
    stale = bool(inner.get("stale"))
    if inner.get("skipped"):
        if value is None:
            row.update(status="skipped",
                       note=f"skipped: {inner.get('skipped')}")
            return row
        # a skipped run that still carries a value = stale promotion
        stale = True
    src = inner.get("last_capture") if stale and \
        isinstance(inner.get("last_capture"), dict) else inner
    if "%" in unit:
        row["mfu"] = value
    row["img_s"] = _img_s(src)
    if not row["tag"]:
        row["tag"] = (src.get("_capture") or {}).get("tag") or \
            inner.get("metric") or ""
    if stale:
        row.update(status="stale",
                   note="value reused from an earlier in-session "
                        "capture — context only, not fresh evidence")
    return row


def scan(repo=REPO):
    rows = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError) as e:
            rows.append({"round": _round_of(path, {}), "status": "invalid",
                         "mfu": None, "img_s": None, "tag": "",
                         "note": f"unreadable: {e}"})
            continue
        rows.append(classify(path, rec))
    rows.sort(key=lambda r: r["round"])
    return rows


def render(rows):
    def fmt(v, pat):
        return pat % v if v is not None else "—"
    lines = [
        "| round | status | MFU (% bf16 peak) | train img/s | config | note |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| r{r['round']:02d} | {r['status']} "
            f"| {fmt(r['mfu'], '%.2f')} | {fmt(r['img_s'], '%.0f')} "
            f"| {r['tag']} | {r['note']} |")
    valid = [r for r in rows if r["status"] == "valid" and
             r["mfu"] is not None]
    if valid:
        best = max(valid, key=lambda r: r["mfu"])
        lines.append(
            f"\nBest verified MFU: **{best['mfu']:.2f}%** "
            f"(r{best['round']:02d}, {best['tag']}).")
    else:
        lines.append(
            "\nNo round has a driver-verified headline yet.")
    return "\n".join(lines)


def scan_llm(repo=REPO):
    """Classified rows for the ``BENCH_llm_r*.json`` trajectory:
    {round, status, tokens_s, ttft_p50, ttft_p99, tag, note}. The
    emitter (perf_capture.emit_llm_snapshot) already refused unhealthy
    headlines, so classification is value/skipped-driven."""
    rows = []
    for path in sorted(glob.glob(os.path.join(repo,
                                              "BENCH_llm_r*.json"))):
        m = re.search(r"BENCH_llm_r(\d+)\.json$", path)
        rnd = int(m.group(1)) if m else 0
        row = {"round": rnd, "status": "valid", "tokens_s": None,
               "ttft_p50": None, "ttft_p99": None, "accept": None,
               "hit_rate": None, "adapters": None, "tp": None,
               "wdtype": None, "tag": "", "note": ""}
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError) as e:
            row.update(status="invalid", note=f"unreadable: {e}")
            rows.append(row)
            continue
        if isinstance(rec.get("round"), int):
            row["round"] = rec["round"]
        row["tag"] = rec.get("tag") or ""
        # SPMD decode (ISSUE 19): mesh shape of the headline run, or
        # — on a structural sweep round (always headline-less) — the
        # widest verified tp plus its per-width parity labels.
        # Extracted BEFORE the skipped gate: the sweep's whole point
        # is carried by a "skipped" artifact
        mesh = rec.get("mesh") or {}
        if mesh.get("tp"):
            row["tp"] = mesh["tp"]
            if (mesh.get("dp") or 1) > 1:
                row["note"] = f"dp={mesh['dp']} replicas"
        sweep = rec.get("mesh_sweep") or []
        if sweep:
            verified = [e for e in sweep
                        if e.get("parity_kind") != "baseline"]
            if verified:
                row["tp"] = max(e.get("tp") or 1 for e in verified
                                if e.get("parity_ok"))
                parity = ", ".join(
                    f"tp{e.get('tp')}:{e.get('parity_kind')}"
                    + ("" if e.get("parity_ok") else "(FAILED)")
                    for e in verified)
                row["note"] = ("spmd structural sweep — " + parity
                               + "; dispatches/step="
                               + str(verified[-1].get(
                                   "dispatches_per_step")))
        # quantized weights (ISSUE 20): the served dtype, or — on a
        # --weight-dtype sweep round — the swept dtypes plus the best
        # params-per-chip ratio vs fp32. Extracted BEFORE the skipped
        # gate: the byte-ratio evidence is structural and survives a
        # refused timing headline
        w = rec.get("weights") or {}
        if w.get("dtype"):
            row["wdtype"] = w["dtype"]
        wsweep = rec.get("weight_sweep") or []
        if wsweep:
            row["wdtype"] = "/".join(
                c.get("requested_dtype") or c.get("weight_dtype")
                or "?" for c in wsweep)
            ratios = [c for c in wsweep
                      if c.get("params_per_chip_ratio")
                      and c.get("weight_dtype") != "float32"]
            if ratios:
                best = max(ratios,
                           key=lambda c: c["params_per_chip_ratio"])
                row["note"] = (
                    (row["note"] + " " if row["note"] else "")
                    + f"params/chip ×"
                    f"{best['params_per_chip_ratio']:.2f} at "
                    f"{best['weight_dtype']}")
        if rec.get("skipped") or rec.get("value") is None:
            note = f"skipped: {rec.get('skipped')}"
            if row["note"]:
                note = row["note"] + " | " + note
            row.update(status="skipped", note=note)
            rows.append(row)
            continue
        row["tokens_s"] = float(rec["value"])
        ttft = rec.get("ttft_ms") or {}
        row["ttft_p50"] = ttft.get("p50")
        row["ttft_p99"] = ttft.get("p99")
        # speculative-decoding draft acceptance (ISSUE 12): absent on
        # pre-spec rounds and spec-off runs
        row["accept"] = rec.get("spec_accept_rate")
        # prefix-cache hit rate (ISSUE 13): absent on pre-cache
        # rounds and runs without shared-prefix traffic
        pf = rec.get("prefix") or {}
        row["hit_rate"] = pf.get("hit_rate")
        # multi-LoRA sweep (ISSUE 17): adapter count of the headline
        # pass, absent on pre-adapter rounds; the full curve stays in
        # the artifact's adapters_curve
        ad = rec.get("adapters") or {}
        row["adapters"] = ad.get("count")
        curve = rec.get("adapters_curve") or []
        if len(curve) > 1:
            pts = ", ".join(
                f"{c['adapters']}→{c['tokens_per_sec']}"
                for c in curve)
            row["note"] = (row["note"] + " " if row["note"] else "") \
                + f"lora curve tok/s: {pts}"
        if pf.get("ttft_ms_control"):
            row["note"] = (row["note"] + " " if row["note"] else "") \
                + (f"saved={pf.get('prefill_tokens_saved')}tok "
                   f"ctl_ttft_p50={pf['ttft_ms_control'].get('p50')}")
        knobs = rec.get("knobs") or {}
        if knobs.get("MXNET_TPU_LLM_SPEC_K"):
            row["note"] = (row["note"] + " " if row["note"] else "") \
                + (f"spec_k={knobs['MXNET_TPU_LLM_SPEC_K']} "
                   f"chunk={knobs.get('MXNET_TPU_LLM_PREFILL_CHUNK')}")
        if rec.get("overload"):
            ov = rec["overload"]
            row["note"] = (f"overload run: shed_rate="
                           f"{ov.get('shed_rate')}, served TTFT only")
        rows.append(row)
    rows.sort(key=lambda r: r["round"])
    return rows


def render_llm(rows):
    def fmt(v, pat):
        return pat % v if v is not None else "—"
    lines = [
        "| round | status | tokens/s | TTFT p50 (ms) | TTFT p99 (ms) "
        "| accept rate | hit rate | adapters | tp | weights | config "
        "| note |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| r{r['round']:02d} | {r['status']} "
            f"| {fmt(r['tokens_s'], '%.1f')} "
            f"| {fmt(r['ttft_p50'], '%.2f')} "
            f"| {fmt(r['ttft_p99'], '%.2f')} "
            f"| {fmt(r.get('accept'), '%.3f')} "
            f"| {fmt(r.get('hit_rate'), '%.3f')} "
            f"| {fmt(r.get('adapters'), '%d')} "
            f"| {fmt(r.get('tp'), '%d')} "
            f"| {r.get('wdtype') or '—'} "
            f"| {r['tag']} | {r['note']} |")
    valid = [r for r in rows if r["status"] == "valid"
             and r["tokens_s"] is not None]
    if valid:
        best = max(valid, key=lambda r: r["tokens_s"])
        lines.append(
            f"\nBest verified decode throughput: "
            f"**{best['tokens_s']:.1f} tokens/s** "
            f"(r{best['round']:02d}, {best['tag']}).")
    else:
        lines.append("\nNo valid LLM bench round yet.")
    return "\n".join(lines)


def scan_multichip(repo=REPO):
    """Classified rows for the ``MULTICHIP_r*.json`` trajectory. The
    point-based shape (tools/multichip_bench.py) renders the scaling
    curve; the legacy driver shape ({n_devices, rc, ok, tail}) only
    certifies that the dryrun ran, so those rows carry no numbers."""
    rows = []
    for path in sorted(glob.glob(os.path.join(repo, "MULTICHIP_r*.json"))):
        m = re.search(r"MULTICHIP_r(\d+)\.json$", path)
        rnd = int(m.group(1)) if m else 0
        row = {"round": rnd, "status": "valid", "points": [],
               "dispatches": None, "tag": "", "note": ""}
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError) as e:
            row.update(status="invalid", note=f"unreadable: {e}")
            rows.append(row)
            continue
        if isinstance(rec.get("round"), int):
            row["round"] = rec["round"]
        if "points" not in rec:                    # legacy dryrun shape
            ok = rec.get("ok") and rec.get("rc", 1) == 0 \
                and not rec.get("skipped")
            row.update(
                status="legacy" if ok else "invalid",
                tag=f"{rec.get('n_devices', '?')}-device dryrun",
                note="replica-loop dryrun (pre-SPMD): ran, no scaling "
                     "points recorded" if ok else
                     f"rc={rec.get('rc')}: dryrun failed")
            rows.append(row)
            continue
        row["tag"] = rec.get("tag") or ""
        if rec.get("skipped") or not rec.get("ok") \
                or rec.get("value") is None:
            row.update(status="skipped" if rec.get("skipped")
                       else "invalid",
                       note=f"skipped={rec.get('skipped')} "
                            f"errors={rec.get('errors')}")
            rows.append(row)
            continue
        row["dispatches"] = float(rec["value"])
        row["points"] = rec["points"]
        if not rec.get("timing_evidence", True):
            row["note"] = "structure evidence only (CPU virtual devices)"
        rows.append(row)
    rows.sort(key=lambda r: r["round"])
    return rows


def render_multichip(rows):
    def fmt(v, pat):
        return pat % v if v is not None else "—"
    lines = [
        "| round | status | devices (mesh) | step ms | T1/TN "
        "| disp/step | parity | config | note |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if not r["points"]:
            lines.append(
                f"| r{r['round']:02d} | {r['status']} | — | — | — | — "
                f"| — | {r['tag']} | {r['note']} |")
            continue
        for i, pt in enumerate(r["points"]):
            mesh = "×".join(f"{k}{v}" for k, v in
                            (pt.get("mesh") or {}).items())
            head = (f"| r{r['round']:02d} | {r['status']} "
                    if i == 0 else "| | ")
            # parity_kind (bitexact/tolerance) is the honest label;
            # legacy artifacts only carried parity_bitexact
            ok = pt.get("parity_ok", pt.get("parity_bitexact"))
            kind = pt.get("parity_kind") or "bitexact"
            parity = ("—" if ok is None else "FAIL" if ok is False
                      else "tol" if kind == "tolerance" else "bit-exact")
            # legacy artifacts carried the T1/TN value mislabeled as
            # scaling_efficiency
            spd = pt.get("speedup_vs_1dev", pt.get("scaling_efficiency"))
            lines.append(
                head + f"| {pt['devices']} ({mesh}) "
                f"| {fmt(pt.get('step_ms'), '%.2f')} "
                f"| {fmt(spd, '%.2f')} "
                f"| {fmt(pt.get('dispatches_per_step'), '%.1f')} "
                f"| {parity} "
                f"| {r['tag'] if i == 0 else ''} "
                f"| {r['note'] if i == 0 else ''} |")
    valid = [r for r in rows if r["status"] == "valid" and r["points"]]
    if valid:
        best = valid[-1]
        # the parity claim must come from the points, not prose — a
        # tolerance-gated dp point (e.g. a real-pod capture with no
        # bit-exact CPU oracle) must never render as bit-exact
        kinds = {(pt.get("parity_kind") or "bitexact")
                 for pt in best["points"]
                 if pt.get("parity_ok") and pt.get("devices", 1) > 1}
        parity_note = (
            "every multi-device point bit-exact vs the replica-loop "
            "oracle" if kinds == {"bitexact"}
            else "multi-device parity tolerance-gated"
            if kinds == {"tolerance"}
            else "parity per point as the rows above label it "
            "(bit-exact / tol)" if kinds
            else "no multi-device parity evidence")
        lines.append(
            f"\nLatest SPMD curve: r{best['round']:02d} — "
            f"{max(pt['devices'] for pt in best['points'])} devices at "
            f"**{best['dispatches']:.1f} dispatch/step**, "
            f"{parity_note}.")
    else:
        lines.append("\nNo SPMD scaling round yet (legacy dryruns "
                     "only).")
    return "\n".join(lines)


def scan_capacity(repo=REPO):
    """Classified rows for the ``CAPACITY_r*.json`` trajectory
    (tools/load_replay.py reports): {round, status, chips_per_m,
    served_qps, shed_qps, tokens_s, slo, tag, note}. ``served/shed``
    sum across frontends — the trend of interest is goodput per chip
    against refusal behavior, round over round."""
    rows = []
    for path in sorted(glob.glob(os.path.join(repo,
                                              "CAPACITY_r*.json"))):
        m = re.search(r"CAPACITY_r(\d+)\.json$", path)
        rnd = int(m.group(1)) if m else 0
        row = {"round": rnd, "status": "valid", "chips_per_m": None,
               "served_qps": None, "shed_qps": None, "tokens_s": None,
               "slo": "—", "tag": "", "note": ""}
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError) as e:
            row.update(status="invalid", note=f"unreadable: {e}")
            rows.append(row)
            continue
        if isinstance(rec.get("round"), int):
            row["round"] = rec["round"]
        row["tag"] = rec.get("tag") or ""
        if rec.get("skipped") or rec.get("value") is None:
            row.update(status="skipped",
                       note=f"skipped: {rec.get('skipped')}")
            rows.append(row)
            continue
        row["chips_per_m"] = float(rec["value"])
        # quantized-weight capacity column (ISSUE 20): the replay
        # server's dtype + derived models-per-chip under the declared
        # HBM model, so the footprint delta trends with the headline
        lw = rec.get("llm_weights") or {}
        if lw.get("models_per_chip") is not None:
            row["note"] = (
                (row["note"] + " " if row["note"] else "")
                + f"weights {lw.get('dtype')}: "
                f"{lw['models_per_chip']} models/chip")
        attained = rec.get("slo_attained")
        row["slo"] = ("attained" if attained
                      else "—" if attained is None else "BREACHED")
        if attained is False:
            # an un-attained replay is an overload experiment: its
            # chips/M figure is not a serving-capacity claim
            row.update(status="overload",
                       note="SLOs not attained — refusal-behavior "
                            "evidence, not capacity")
        served = shed = 0.0
        have = False
        for fe in rec.get("frontends") or []:
            if fe.get("served_qps") is not None:
                served += float(fe["served_qps"])
                have = True
            shed += float(fe.get("shed_qps") or 0.0) \
                + float(fe.get("expired_qps") or 0.0) \
                + float(fe.get("evicted_qps") or 0.0)
            if fe.get("tokens_per_sec") is not None:
                row["tokens_s"] = float(fe["tokens_per_sec"])
        if have:
            row["served_qps"], row["shed_qps"] = served, shed
        rows.append(row)
    rows.sort(key=lambda r: r["round"])
    return rows


def render_capacity(rows):
    def fmt(v, pat):
        return pat % v if v is not None else "—"
    lines = [
        "| round | status | chips / 1M users | served qps | "
        "shed+expired qps | llm tokens/s | SLOs | config | note |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| r{r['round']:02d} | {r['status']} "
            f"| {fmt(r['chips_per_m'], '%.0f')} "
            f"| {fmt(r['served_qps'], '%.2f')} "
            f"| {fmt(r['shed_qps'], '%.2f')} "
            f"| {fmt(r['tokens_s'], '%.1f')} "
            f"| {r['slo']} | {r['tag']} | {r['note']} |")
    valid = [r for r in rows if r["status"] == "valid"
             and r["chips_per_m"] is not None]
    if valid:
        best = min(valid, key=lambda r: r["chips_per_m"])
        latest = valid[-1]
        lines.append(
            f"\nBest (lowest) attained footprint: "
            f"**{best['chips_per_m']:.0f} chips/1M users** "
            f"(r{best['round']:02d}, {best['tag']}); latest "
            f"r{latest['round']:02d} at {latest['chips_per_m']:.0f}.")
    else:
        lines.append("\nNo SLO-attained capacity round yet.")
    return "\n".join(lines)


def splice(doc_path, table, begin=BEGIN, end=END, heading=HEADING):
    block = f"{begin}\n\n{table}\n\n{end}"
    try:
        with open(doc_path) as f:
            text = f.read()
    except OSError:
        text = ""
    if begin in text and end in text:
        pre = text.split(begin)[0]
        post = text.split(end, 1)[1]
        text = pre + block + post
    else:
        if text and not text.endswith("\n"):
            text += "\n"
        text += heading + block + "\n"
    with open(doc_path, "w") as f:
        f.write(text)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=REPO)
    ap.add_argument("--doc", default=None,
                    help="markdown file to splice (default "
                         "docs/PERFORMANCE.md under --repo)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the table without touching the doc")
    args = ap.parse_args()
    rows = scan(args.repo)
    llm_rows = scan_llm(args.repo)
    mc_rows = scan_multichip(args.repo)
    cap_rows = scan_capacity(args.repo)
    if not rows and not llm_rows and not mc_rows and not cap_rows:
        print("no BENCH_r*.json, BENCH_llm_r*.json, MULTICHIP_r*.json "
              "or CAPACITY_r*.json found", file=sys.stderr)
        return 1
    doc = args.doc or os.path.join(args.repo, "docs",
                                   "PERFORMANCE.md")
    if rows:
        table = render(rows)
        print(table)
        if not args.dry_run:
            splice(doc, table)
    if llm_rows:
        llm_table = render_llm(llm_rows)
        print("\n" + llm_table)
        if not args.dry_run:
            splice(doc, llm_table, begin=LLM_BEGIN, end=LLM_END,
                   heading=LLM_HEADING)
    if mc_rows:
        mc_table = render_multichip(mc_rows)
        print("\n" + mc_table)
        if not args.dry_run:
            splice(doc, mc_table, begin=MC_BEGIN, end=MC_END,
                   heading=MC_HEADING)
    if cap_rows:
        cap_table = render_capacity(cap_rows)
        print("\n" + cap_table)
        if not args.dry_run:
            splice(doc, cap_table, begin=CAP_BEGIN, end=CAP_END,
                   heading=CAP_HEADING)
    if not args.dry_run:
        print(f"\nwrote {doc}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
