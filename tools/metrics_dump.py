"""Render mxnet_tpu observability snapshots — zero-dependency exporter CLI.

A process instrumented with ``MXNET_TPU_METRICS_LOG=<path>`` appends
JSONL registry snapshots (one line per dump: final-at-exit, plus every
``MXNET_TPU_METRICS_INTERVAL`` seconds). This tool turns that file back
into something readable:

    python tools/metrics_dump.py run/metrics.jsonl              # table
    python tools/metrics_dump.py run/metrics.jsonl --format prom
    python tools/metrics_dump.py run/metrics.jsonl --format json

``--format prom`` re-emits Prometheus text exposition (what a live
``registry.expose()`` scrape would have returned at snapshot time), so
offline captures and live scrapes are interchangeable downstream.

``--delta A.jsonl [B.jsonl]`` renders the MOVEMENT between two
snapshots — counter deltas + per-second rates and histogram
percentile movement (cumulative p50/p99 at each end, plus the
percentile of ONLY the window's observations from the diffed bucket
counts). With one file, the first and last snapshot lines are
compared. This is the offline/manual twin of
``observability.timeseries.TimeSeriesRing.rate()`` — same reset
handling, same bucket-delta percentile math (imported from the same
module so the two can never drift).

``--smoke`` runs the full path in-process — instrument a 2-step
training loop, a checkpoint write, a micro-batched serving burst and
the XLA compile bridge with span tracing ON, then snapshot → JSONL →
reload → exposition → validate, plus a tracer export whose Chrome/
Perfetto JSON well-formedness and ``mxtpu_trace_*`` counters (spans
started/dropped, export bytes) are checked — and prints ``SMOKE
PASS``. Wired into tier-1 CI (tests/test_examples_smoke.py) so the
exporter paths are exercised on every run.
"""
import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_NAME_RE = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
# quote-aware label block: a '}' INSIDE a quoted label value is legal
# exposition (the exporter does not escape it), so the block cannot be
# matched with a naive [^}]*
_LABEL_RE = r'%s="(?:[^"\\]|\\.)*"' % _NAME_RE
_SAMPLE_RE = re.compile(
    r"^(?P<name>%s)(?:\{(?P<labels>%s(?:,%s)*)\})? (?P<value>\S+)$"
    % (_NAME_RE, _LABEL_RE, _LABEL_RE))


def parse_exposition(text):
    """Validate Prometheus text exposition; return {(name, labels): value}.

    Raises ValueError on any malformed line — this is the checker the
    smoke path and the tier-1 tests assert the exporter against.
    """
    samples = {}
    typed = {}
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            parts = line.split(" ", 3)
            if len(parts) < 4 or not re.fullmatch(_NAME_RE, parts[2]):
                raise ValueError(f"line {ln}: malformed comment: {line!r}")
            if parts[1] == "TYPE":
                if parts[3] not in ("counter", "gauge", "histogram",
                                    "summary", "untyped"):
                    raise ValueError(f"line {ln}: bad type {parts[3]!r}")
                typed[parts[2]] = parts[3]
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            raise ValueError(f"line {ln}: malformed sample: {line!r}")
        try:
            value = float(m.group("value"))
        except ValueError:
            raise ValueError(f"line {ln}: bad value: {line!r}")
        labels = m.group("labels") or ""
        pairs = tuple(sorted(re.findall(
            r'(%s)="((?:[^"\\]|\\.)*)"' % _NAME_RE, labels)))
        key = (m.group("name"), pairs)      # label order canonicalized
        if key in samples:
            raise ValueError(f"line {ln}: duplicate series: {line!r}")
        samples[key] = value
    return samples


# ------------------------------------------------------ JSONL loading --

def load_snapshots(path):
    """Every parsable snapshot line of a metrics JSONL file."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "metrics" in rec:
                out.append(rec)
    return out


def render_prom(metrics):
    """Rebuild the Prometheus exposition from one snapshot's ``metrics``
    dict (the inverse of MetricsRegistry.snapshot, matching expose())."""
    from mxnet_tpu.observability.registry import MetricsRegistry
    reg = MetricsRegistry()
    for name, rec in sorted(metrics.items()):
        for series in rec.get("series", []):
            labels = series.get("labels", {})
            names = tuple(sorted(labels))
            if rec["type"] == "counter":
                parent = reg.counter(name, rec.get("help", ""), names)
            elif rec["type"] == "gauge":
                parent = reg.gauge(name, rec.get("help", ""), names)
            else:
                parent = reg.histogram(name, rec.get("help", ""), names,
                                       buckets=series["buckets"])
            child = parent.labels(**{k: labels[k] for k in names})
            if rec["type"] == "histogram":
                # de-cumulate the stored counts back into the child
                prev = 0
                for cum, edge_i in zip(series["counts"],
                                       range(len(series["counts"]))):
                    child._counts[edge_i] = cum - prev
                    prev = cum
                child._sum = float(series["sum"])
                child._count = series["count"]
            else:
                child._value = float(series["value"])
    return reg.expose()


def render_table(metrics):
    lines = [f"{'metric':<56} {'type':>10} {'value':>16}"]
    lines.append("-" * 86)
    for name, rec in sorted(metrics.items()):
        for series in rec.get("series", []):
            labels = series.get("labels", {})
            lname = name + ("{%s}" % ",".join(
                f"{k}={v}" for k, v in sorted(labels.items()))
                if labels else "")
            if rec["type"] == "histogram":
                val = (f"n={series['count']} "
                       f"sum={float(series['sum']):.6g}")
            else:
                val = f"{float(series['value']):.6g}"
            lines.append(f"{lname:<56} {rec['type']:>10} {val:>16}")
    return "\n".join(lines)


# ------------------------------------------------------------- delta --

def render_delta(snap_a, snap_b):
    """Counter rates + histogram-percentile movement between two
    snapshot records (each ``{"ts", "metrics"}``). Only series that
    MOVED are listed — an unchanged counter carries no information in
    a delta view."""
    sys.path.insert(0, REPO)
    try:
        from mxnet_tpu.observability.timeseries import (
            diff_cum_counts, percentile_from_counts)
    finally:
        sys.path.pop(0)
    ma, mb = snap_a["metrics"], snap_b["metrics"]
    dt = float(snap_b.get("ts") or 0.0) - float(snap_a.get("ts") or 0.0)
    rate_dt = dt if dt > 0 else None
    lines = [f"# delta: ts {snap_a.get('ts')} -> {snap_b.get('ts')} "
             f"({dt:.3f}s)",
             f"{'metric':<56} {'type':>10} {'movement':>40}",
             "-" * 108]
    moved = 0

    def _key(series):
        return tuple(sorted((series.get("labels") or {}).items()))

    for name in sorted(set(ma) | set(mb)):
        rec = mb.get(name) or ma.get(name)
        typ = rec["type"]
        sa = {_key(s): s for s in (ma.get(name) or {}).get("series", [])}
        sb = {_key(s): s for s in (mb.get(name) or {}).get("series", [])}
        for key in sorted(set(sa) | set(sb)):
            lname = name + ("{%s}" % ",".join(f"{k}={v}"
                                              for k, v in key)
                            if key else "")
            a, b = sa.get(key), sb.get(key)
            if typ == "histogram":
                cb = b["counts"] if b else None
                if cb is None:
                    continue            # series vanished: no window
                ca = a["counts"] if a else [0] * len(cb)
                if a and tuple(a["buckets"]) != tuple(b["buckets"]):
                    lines.append(
                        f"{lname:<56} {typ:>10} "
                        "bucket layout changed between snapshots; "
                        "no delta")
                    moved += 1
                    continue
                win = diff_cum_counts(ca, cb)
                dcount = win[-1]
                if not dcount:
                    continue
                edges = b["buckets"]
                p50w = percentile_from_counts(edges, win, 50)
                p99w = percentile_from_counts(edges, win, 99)
                p50a = percentile_from_counts(
                    edges, ca, 50) if a and ca[-1] else None
                p50b = percentile_from_counts(edges, cb, 50)

                def fmt_s(v):
                    return f"{v * 1e3:.3g}ms" if v is not None else "—"
                rate = (f" ({dcount / rate_dt:.6g}/s)"
                        if rate_dt else "")
                lines.append(
                    f"{lname:<56} {typ:>10} "
                    f"n+{dcount}{rate} p50 {fmt_s(p50a)}->"
                    f"{fmt_s(p50b)} win p50={fmt_s(p50w)} "
                    f"p99={fmt_s(p99w)}")
                moved += 1
            else:
                vb = float(b["value"]) if b else 0.0
                va = float(a["value"]) if a else 0.0
                if typ == "counter" and vb < va:
                    delta = vb             # reset: restart from zero
                else:
                    delta = vb - va
                if delta == 0.0:
                    continue
                rate = (f" ({delta / rate_dt:+.6g}/s)"
                        if typ == "counter" and rate_dt else "")
                lines.append(f"{lname:<56} {typ:>10} "
                             f"{va:.6g} -> {vb:.6g} ({delta:+.6g})"
                             f"{rate}")
                moved += 1
    if not moved:
        lines.append("(no series moved between the two snapshots)")
    return "\n".join(lines)


# ------------------------------------------------------------- smoke --

def smoke():
    """End-to-end exercise of registry → instrumentation → exporters.

    Touches four subsystems in one process (the acceptance criterion of
    the observability PR): training step timer, resilience checkpoint,
    serving, XLA compile bridge — then checks that one expose() call
    carries all of them and that the JSONL snapshot round-trips.
    """
    import tempfile
    import numpy as np
    sys.path.insert(0, REPO)
    # the SPMD segment below needs a (virtual) device mesh; harmless
    # when the caller (tests/conftest.py) already forced a count
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()
    # an ambient mesh would silently turn the replica-path trainer
    # below into an SPMD step and skew the dispatch-count assertions
    os.environ.pop("MXNET_TPU_MESH", None)
    import mxnet_tpu as mx
    from mxnet_tpu import nd, serving
    from mxnet_tpu.gluon import nn, Trainer
    from mxnet_tpu.gluon.loss import L2Loss
    import mxnet_tpu.autograd as ag
    from mxnet_tpu.observability import (get_registry, get_tracer,
                                         StepTimer, validate_chrome_trace,
                                         install_jax_monitoring_bridge)

    install_jax_monitoring_bridge()
    tracer = get_tracer().enable()
    mx.random.seed(0)

    # training: 2 timed Trainer steps
    net = nn.Dense(4)
    net.initialize()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1})
    loss_fn = L2Loss()
    timer = StepTimer()
    x = nd.array(np.random.RandomState(0).randn(8, 3).astype(np.float32))
    y = nd.array(np.random.RandomState(1).randn(8, 4).astype(np.float32))
    for _ in range(2):
        with timer.step(batch_size=8):
            with ag.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(8)

    # whole-step compilation: 2 one-dispatch steps (+ a bucketed tail)
    # must land on the mxtpu_train_step_* series
    step = trainer.compile_step(lambda a, b: loss_fn(net(a), b))
    for _ in range(2):
        step(x, y)
    step(x[:5], y[:5])   # ragged tail -> padded bucket, not a retrace

    # SPMD mesh mode (ISSUE 14): the same whole-step program over a
    # 2-device dp mesh — one donated dispatch per step, in-program
    # gradient psum — must land on the mxtpu_spmd_* series
    import jax
    from mxnet_tpu import parallel
    n_dev = min(2, len(jax.devices()))
    smesh = parallel.local_mesh(n_dev)
    snet = nn.Dense(4, in_units=3)
    snet.initialize()
    strainer = Trainer(snet.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    sstep = strainer.compile_step(lambda a, b: loss_fn(snet(a), b),
                                  mesh=smesh)
    for _ in range(2):
        sstep(x, y)
    if sstep.last_reason is not None:
        print(f"SMOKE FAIL: SPMD mesh step fell back to eager "
              f"({sstep.last_reason})")
        return 1

    # resilience: one checkpoint commit + restore, then a sharded+async
    # save so the mxtpu_ckpt_async_* series land in the exposition
    with tempfile.TemporaryDirectory() as run_dir:
        trainer.save_state(run_dir)
        trainer.restore_state(run_dir)
    with tempfile.TemporaryDirectory() as run_dir:
        from mxnet_tpu.resilience import async_writer
        handle = trainer.save_state(run_dir, num_shards=2)
        # async on: route explicitly through the manager lever (env-free)
        mgr = trainer._ckpt_mgrs[os.path.realpath(run_dir)]
        mgr._async = True
        handle = trainer.save_state(run_dir, step=trainer._step_count + 1,
                                    num_shards=2)
        trainer.step(8)          # a step while the save may be in flight
        handle.result(timeout=60)
        trainer.ckpt_wait()
        manifest = trainer.restore_state(run_dir)
        if manifest.get("format") != "mxtpu-ckpt-v2":
            print("SMOKE FAIL: sharded save did not produce a v2 "
                  "manifest")
            return 1

    # serving: a padded micro-batch burst through a callable backend
    srv = serving.ModelServer(lambda b: b * 2.0, buckets=[1, 2, 4],
                              max_delay_ms=1.0, item_shape=(3,),
                              dtype="float32").start()
    srv.warmup()
    futs = [srv.submit(np.full(3, i, np.float32)) for i in range(5)]
    for f in futs:
        f.result(timeout=60)
    srv.shutdown()

    # serving overload/failure path: bounded-queue shed, submit-time
    # deadline expiry and a poison row, so the
    # mxtpu_serving_{shed,deadline_expired,poison_isolated,
    # breaker_state} series land in the same exposition
    import threading
    import time as _time
    release = threading.Event()

    def _overload_fn(batch):
        release.wait(10)
        if (batch == 99.0).any():
            raise ValueError("poison row")
        return batch

    osrv = serving.ModelServer(_overload_fn, buckets=[1],
                               max_delay_ms=0.1, item_shape=(3,),
                               dtype="float32", max_queue=1,
                               name="smoke_overload").start()
    of1 = osrv.submit(np.zeros(3, np.float32))
    deadline = _time.monotonic() + 10
    while osrv._queue.depth() > 0 and _time.monotonic() < deadline:
        _time.sleep(0.002)          # wait until of1 is in dispatch
    of2 = osrv.submit(np.full(3, 99.0, np.float32))   # queued poison
    shed_ok = dl_ok = poison_ok = False
    try:
        osrv.submit(np.zeros(3, np.float32))
    except serving.Overloaded:
        shed_ok = True
    try:
        osrv.submit(np.zeros(3, np.float32), deadline_ms=0)
    except serving.DeadlineExceededError:
        dl_ok = True
    release.set()
    of1.result(timeout=60)
    try:
        of2.result(timeout=60)
    except ValueError:
        poison_ok = True
    osrv.shutdown()
    if not (shed_ok and dl_ok and poison_ok):
        print(f"SMOKE FAIL: overload path not exercised (shed={shed_ok}"
              f" deadline={dl_ok} poison={poison_ok})")
        return 1

    # LLM decode serving: a tiny continuous-batched greedy burst so the
    # mxtpu_llm_* series (tokens/sec, TTFT, KV occupancy) land in the
    # same exposition
    from mxnet_tpu.serving.llm import TinyDecoder, DecoderConfig
    dec = TinyDecoder(DecoderConfig(vocab_size=16, d_model=16,
                                    num_layers=1, num_heads=2,
                                    d_ff=32, max_context=32))
    # prefix_cache pinned ON explicitly: the assertions below depend
    # on it, and the smoke must pass regardless of the ambient
    # MXNET_TPU_LLM_PREFIX_CACHE value
    lsrv = serving.LLMServer(dec, dec.init_params(0), name="smoke_llm",
                             max_seqs=2, block_size=8, max_context=32,
                             prefix_cache=True)
    lsrv.warmup()
    lsrv.start()
    # the prompts share one full (8-token) block: the first
    # admissions register it, later ones hit the prefix cache — so
    # the mxtpu_llm_prefix_* series carry real traffic
    shared = [1, 2, 3, 4, 5, 6, 7, 8]
    lfuts = [lsrv.submit(shared + [9 + i], 3) for i in range(4)]
    for f in lfuts:
        f.result(timeout=60)
    lsrv.shutdown()

    # fleet routing: interactive + batch lanes, a per-tenant quota
    # shed, and one weight hot-swap, so the mxtpu_fleet_* series
    # (routed/swap/quota/lane-depth/active-version) land in the same
    # exposition
    import jax
    import jax.numpy as jnp
    _fleet_jit = jax.jit(lambda w, x: jnp.tanh(x @ w))

    def _fleet_server(arrays, tag):
        w = jnp.asarray(np.asarray(arrays["w"], dtype=np.float32))
        return serving.ModelServer(
            lambda batch: np.asarray(_fleet_jit(w, batch)),
            buckets=[1, 2], max_delay_ms=0.1, item_shape=(3,),
            dtype="float32", name=f"smoke_fleet_{tag}")

    fsrv = _fleet_server({"w": np.eye(3, dtype=np.float32)}, "v0")
    fsrv.warmup()
    fsrv.start()
    router = serving.FleetRouter(name="smoke_fleet", quota_rps=0.001,
                                 quota_burst=3)
    router.add_model("m", fsrv, version=0,
                     builder=lambda arrays: _fleet_server(arrays, "v1"))
    router.generate("m", np.ones(3, np.float32), tenant="good",
                    timeout=60)
    router.generate("m", np.ones(3, np.float32), lane="batch",
                    tenant="good", timeout=60)
    quota_ok = False
    try:
        for _ in range(4):      # burst 3 -> the fourth submit sheds
            router.submit("m", np.ones(3, np.float32), tenant="greedy")
    except serving.Overloaded as exc:
        quota_ok = exc.reason == "quota"
    router.publish("m", 1,
                   arrays={"w": 2 * np.eye(3, dtype=np.float32)})
    router.generate("m", np.ones(3, np.float32), tenant="good",
                    timeout=60)
    router.shutdown()
    if not quota_ok:
        print("SMOKE FAIL: fleet quota shed not raised typed")
        return 1

    reg = get_registry()
    text = reg.expose()
    samples = parse_exposition(text)          # must be valid exposition
    for subsystem in ("mxtpu_training_", "mxtpu_serving_",
                      "mxtpu_resilience_checkpoint_",
                      "mxtpu_xla_compile_", "mxtpu_ckpt_async_",
                      "mxtpu_llm_", "mxtpu_fleet_"):
        if not any(name.startswith(subsystem)
                   for name, _ in samples):
            print(f"SMOKE FAIL: no {subsystem}* metric in exposition")
            return 1
    # async checkpointing: the background save must have committed and
    # accounted itself (counters + queue-state gauge + write histogram)
    if samples.get(("mxtpu_ckpt_async_submitted_total", ()), 0) < 1 or \
            samples.get(("mxtpu_ckpt_async_committed_total", ()), 0) < 1:
        print("SMOKE FAIL: async checkpoint save not counted "
              f"(submitted={samples.get(('mxtpu_ckpt_async_submitted_total', ()))})")
        return 1
    if ("mxtpu_ckpt_async_in_flight", ()) not in samples:
        print("SMOKE FAIL: no async in-flight gauge in exposition")
        return 1
    if not any(name == "mxtpu_ckpt_async_write_seconds_count"
               or name.startswith("mxtpu_ckpt_async_write_seconds")
               for name, _ in samples):
        print("SMOKE FAIL: no async write-seconds histogram in "
              "exposition")
        return 1
    # overload/failure series: the shed (by reason), deadline and
    # breaker-state series must appear in the same exposition, each
    # counting its one exercised instance exactly once
    olbl = (("server", "smoke_overload"),)
    if samples.get(("mxtpu_serving_shed_total",
                    (("reason", "queue_full"),) + olbl)) != 1:
        print("SMOKE FAIL: queue-full shed not counted once")
        return 1
    if samples.get(("mxtpu_serving_deadline_expired_total", olbl)) != 1:
        print("SMOKE FAIL: deadline expiry not counted once")
        return 1
    if samples.get(("mxtpu_serving_poison_isolated_total", olbl)) != 1:
        print("SMOKE FAIL: poison isolation not counted once")
        return 1
    if ("mxtpu_serving_breaker_state", olbl) not in samples:
        print("SMOKE FAIL: no breaker-state gauge in exposition")
        return 1

    # llm decode: the serving-economics headline series must carry the
    # burst (4 requests x 3 tokens) under the server's label
    lbl = (("server", "smoke_llm"),)
    if samples.get(("mxtpu_llm_requests_completed_total", lbl)) != 4:
        print("SMOKE FAIL: llm burst not counted "
              f"({samples.get(('mxtpu_llm_requests_completed_total', lbl))})")
        return 1
    if samples.get(("mxtpu_llm_tokens_generated_total", lbl)) != 12:
        print("SMOKE FAIL: llm token count off "
              f"({samples.get(('mxtpu_llm_tokens_generated_total', lbl))})")
        return 1
    if samples.get(("mxtpu_llm_tokens_per_sec", lbl), 0) <= 0:
        print("SMOKE FAIL: llm tokens/sec gauge not set")
        return 1
    if ("mxtpu_llm_kv_blocks_in_use", lbl) not in samples:
        print("SMOKE FAIL: no KV-block occupancy gauge in exposition")
        return 1
    if not any(n.startswith("mxtpu_llm_ttft_seconds") for n, _ in samples):
        print("SMOKE FAIL: no TTFT histogram in exposition")
        return 1
    # prefix caching (ISSUE 13): every lookup counted, the shared
    # block really hit, saved prefill tokens credited, and the
    # cached/shared/free block breakdown + evict counter all land in
    # the same exposition
    if samples.get(("mxtpu_llm_prefix_lookup_total", lbl)) != 4:
        print("SMOKE FAIL: prefix lookups not counted "
              f"({samples.get(('mxtpu_llm_prefix_lookup_total', lbl))})")
        return 1
    if not samples.get(("mxtpu_llm_prefix_hit_total", lbl)):
        print("SMOKE FAIL: shared-prefix burst produced no "
              "prefix-cache hits")
        return 1
    if samples.get(("mxtpu_llm_prefill_tokens_saved_total", lbl),
                   0) < 8:
        print("SMOKE FAIL: prefill-tokens-saved not credited "
              f"({samples.get(('mxtpu_llm_prefill_tokens_saved_total', lbl))})")
        return 1
    for gauge in ("mxtpu_llm_kv_blocks_cached",
                  "mxtpu_llm_kv_blocks_shared",
                  "mxtpu_llm_kv_blocks_free"):
        if (gauge, lbl) not in samples:
            print(f"SMOKE FAIL: no {gauge} gauge in exposition")
            return 1
    if ("mxtpu_llm_prefix_evict_total", lbl) not in samples:
        print("SMOKE FAIL: no prefix-evict counter in exposition")
        return 1
    # fleet: routing by lane, the quota shed, the hot-swap commit and
    # the moved version gauge — all under the router's fleet label
    flbl = (("fleet", "smoke_fleet"),)
    if samples.get(("mxtpu_fleet_routed_total",
                    flbl + (("lane", "interactive"),
                            ("model", "m")))) != 5:
        print("SMOKE FAIL: fleet interactive routing not counted "
              "(2 good + 3 greedy admits expected)")
        return 1
    if samples.get(("mxtpu_fleet_routed_total",
                    flbl + (("lane", "batch"), ("model", "m")))) != 1:
        print("SMOKE FAIL: fleet batch-lane routing not counted once")
        return 1
    if samples.get(("mxtpu_fleet_quota_shed_total",
                    flbl + (("tenant", "greedy"),))) != 1:
        print("SMOKE FAIL: greedy-tenant quota shed not counted once")
        return 1
    if samples.get(("mxtpu_fleet_swap_total",
                    flbl + (("model", "m"), ("outcome", "ok"),
                            ("phase", "handover")))) != 1:
        print("SMOKE FAIL: hot-swap handover commit not counted once")
        return 1
    if samples.get(("mxtpu_fleet_active_version",
                    flbl + (("model", "m"),))) != 1:
        print("SMOKE FAIL: active-version gauge did not move to 1")
        return 1
    if ("mxtpu_fleet_lane_depth",
            flbl + (("lane", "interactive"),)) not in samples:
        print("SMOKE FAIL: no fleet lane-depth gauge in exposition")
        return 1
    if not any(n.startswith("mxtpu_fleet_swap_seconds")
               for n, _ in samples):
        print("SMOKE FAIL: no fleet swap-seconds histogram in "
              "exposition")
        return 1
    if samples[("mxtpu_training_steps_total", ())] < 2:
        print("SMOKE FAIL: step timer did not count 2 steps")
        return 1
    # 3 replica-path compiled steps + 2 SPMD mesh steps share the
    # mxtpu_train_step_* series (one whole-step machinery, two modes)
    if samples.get(("mxtpu_train_step_dispatch_total", ())) != 5 or \
            samples.get(("mxtpu_train_step_compiled_total", ())) != 5:
        print("SMOKE FAIL: compiled train step did not report 5 "
              "one-dispatch steps "
              f"(dispatch={samples.get(('mxtpu_train_step_dispatch_total', ()))})")
        return 1
    if samples.get(("mxtpu_train_step_padded_rows_total", ())) != 3:
        print("SMOKE FAIL: bucketed tail did not report its pad rows")
        return 1
    if not any(n == "mxtpu_train_step_bucket_compiles_total"
               for n, _ in samples):
        print("SMOKE FAIL: no per-bucket compile counter in exposition")
        return 1
    # SPMD evidence series (ISSUE 14): the 2-step mesh burst must land
    # in the SAME exposition — dispatch count, per-(devices,bucket)
    # program builds, the mesh-shape gauges and (dp>1) the in-program
    # gradient-reduce payload
    if samples.get(("mxtpu_spmd_step_dispatch_total", ())) != 2:
        print("SMOKE FAIL: SPMD steps not counted "
              f"({samples.get(('mxtpu_spmd_step_dispatch_total', ()))})")
        return 1
    slbl = (("bucket", "8"), ("devices", str(n_dev)))
    if samples.get(("mxtpu_spmd_program_compiles_total", slbl)) != 1:
        print("SMOKE FAIL: SPMD program build not counted once under "
              f"(devices={n_dev}, bucket=8)")
        return 1
    if samples.get(("mxtpu_spmd_mesh_devices", ())) != n_dev:
        print("SMOKE FAIL: SPMD mesh-devices gauge not set")
        return 1
    if samples.get(("mxtpu_spmd_mesh_axis_extent",
                    (("axis", "dp"),))) != n_dev:
        print("SMOKE FAIL: SPMD dp axis-extent gauge not set")
        return 1
    if n_dev > 1 and samples.get(
            ("mxtpu_spmd_collective_bytes_total",
             (("collective", "grad_reduce"),)), 0) <= 0:
        print("SMOKE FAIL: no in-program gradient-reduce bytes "
              "accounted for the dp>1 mesh")
        return 1

    # tracer export: Perfetto-loadable Chrome trace JSON + the
    # mxtpu_trace_* counters (spans started/dropped, export bytes)
    started = samples.get(("mxtpu_trace_spans_started_total", ()), 0)
    if started <= 0:
        print("SMOKE FAIL: tracing was on but no spans were started")
        return 1
    if ("mxtpu_trace_spans_dropped_total", ()) not in samples:
        print("SMOKE FAIL: no spans-dropped counter in exposition")
        return 1
    span_names = {s["name"] for s in tracer.snapshot()}
    # mxtpu.llm.step is the unified chunked-prefill/decode/verify
    # launch (ISSUE 12 folded the old prefill and decode spans into
    # it)
    for needed in ("mxtpu.train_step", "mxtpu.train_step.dispatch",
                   "mxtpu.serving.request", "mxtpu.ckpt.write",
                   "mxtpu.llm.request", "mxtpu.llm.step"):
        if needed not in span_names:
            print(f"SMOKE FAIL: no {needed} span recorded")
            return 1
    if tracer.stats()["open"] != 0:
        print(f"SMOKE FAIL: {tracer.stats()['open']} spans left open")
        return 1
    with tempfile.TemporaryDirectory() as d:
        trace_path = os.path.join(d, "trace.json")
        tracer.export(trace_path)
        try:
            n_events = validate_chrome_trace(trace_path)
        except ValueError as e:
            print(f"SMOKE FAIL: trace export not well-formed: {e}")
            return 1
        if n_events < started - tracer.stats()["dropped"]:
            print(f"SMOKE FAIL: export carries {n_events} events for "
                  f"{started} spans")
            return 1
    export_bytes = reg.counter("mxtpu_trace_export_bytes_total").value
    if not (export_bytes > 0 and
            reg.counter("mxtpu_trace_exports_total").value > 0):
        print("SMOKE FAIL: export did not account its bytes")
        return 1

    # flight recorder (ISSUE 18): enable the black box, run a short
    # burst so real serving events land in the ring, cut one manual
    # bundle — the mxtpu_flight_* series (events / drops / dumps by
    # trigger / bundle bytes) must land in the SAME exposition as
    # everything above, and the bundle must pass flight_inspect
    # --check (manifest present, CRCs good, every payload valid JSON)
    from mxnet_tpu.observability import get_flightrecorder
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from flight_inspect import check as flight_check
    finally:
        sys.path.pop(0)
    fl = get_flightrecorder()
    was_on = fl.enabled
    before = parse_exposition(reg.expose())
    dumps0 = before.get(("mxtpu_flight_dumps_total",
                         (("trigger", "manual"),)), 0)
    events0 = before.get(("mxtpu_flight_events_total", ()), 0)
    bytes0 = before.get(("mxtpu_flight_bundle_bytes_total", ()), 0)
    with tempfile.TemporaryDirectory() as d:
        fl.enable(out_dir=d)
        fsmoke = serving.ModelServer(
            lambda b: b + 1.0, buckets=[1, 2], max_delay_ms=1.0,
            item_shape=(3,), dtype="float32", name="smoke_flight")
        fsmoke.start()
        for fut in [fsmoke.submit(np.zeros(3, np.float32))
                    for _ in range(3)]:
            fut.result(timeout=30)
        bundle = fl.dump(trigger="manual", reason="smoke")
        fsmoke.shutdown()
        fprobs = flight_check(bundle)
        if fprobs:
            print(f"SMOKE FAIL: flight bundle problems: {fprobs}")
            return 1
    if not was_on:
        fl.disable()
    fsamples = parse_exposition(reg.expose())
    if fsamples.get(("mxtpu_flight_events_total", ()), 0) <= events0:
        print("SMOKE FAIL: serving burst recorded no flight events")
        return 1
    if ("mxtpu_flight_events_dropped_total", ()) not in fsamples:
        print("SMOKE FAIL: no flight drop counter in exposition")
        return 1
    if fsamples.get(("mxtpu_flight_dumps_total",
                     (("trigger", "manual"),)), 0) != dumps0 + 1:
        print("SMOKE FAIL: manual flight dump not counted once")
        return 1
    if fsamples.get(("mxtpu_flight_bundle_bytes_total", ()),
                    0) <= bytes0:
        print("SMOKE FAIL: flight bundle bytes not accounted")
        return 1

    # JSONL round-trip through the env-gated writer (re-scrape: the
    # export above moved the mxtpu_trace_* counters)
    samples = parse_exposition(reg.expose())
    with tempfile.TemporaryDirectory() as d:
        log = os.path.join(d, "metrics.jsonl")
        reg.write_snapshot(log)
        snaps = load_snapshots(log)
        if len(snaps) != 1:
            print("SMOKE FAIL: JSONL snapshot did not round-trip")
            return 1
        rendered = parse_exposition(render_prom(snaps[-1]["metrics"]))
        if rendered != samples:
            print("SMOKE FAIL: JSONL-rendered exposition != live scrape")
            return 1
    print(f"SMOKE PASS ({len(samples)} series, "
          f"{len({n for n, _ in samples})} metrics, "
          f"{int(started)} trace spans)")
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="Render MXNET_TPU_METRICS_LOG JSONL snapshots.")
    ap.add_argument("path", nargs="?",
                    help="metrics JSONL file (default: "
                         "$MXNET_TPU_METRICS_LOG)")
    ap.add_argument("--format", choices=("table", "prom", "json"),
                    default="table")
    ap.add_argument("--index", type=int, default=-1,
                    help="which snapshot line to render (default: last)")
    ap.add_argument("--smoke", action="store_true",
                    help="run the in-process end-to-end exporter check")
    ap.add_argument("--delta", nargs="+", metavar="JSONL",
                    help="render counter rates + histogram-percentile "
                         "movement between two snapshots: last lines "
                         "of two files, or first vs last line of one")
    args = ap.parse_args()

    if args.smoke:
        sys.exit(smoke())
    if args.delta:
        if len(args.delta) > 2:
            ap.error("--delta takes one or two JSONL files")
        snaps_a = load_snapshots(args.delta[0])
        if len(args.delta) == 2:
            snaps_b = load_snapshots(args.delta[1])
            if not snaps_a or not snaps_b:
                print("--delta: a snapshot file is empty",
                      file=sys.stderr)
                sys.exit(1)
            a, b = snaps_a[-1], snaps_b[-1]
        else:
            if len(snaps_a) < 2:
                print("--delta: need two snapshot lines in "
                      f"{args.delta[0]}", file=sys.stderr)
                sys.exit(1)
            a, b = snaps_a[0], snaps_a[-1]
        print(render_delta(a, b))
        sys.exit(0)
    path = args.path or os.environ.get("MXNET_TPU_METRICS_LOG")
    if not path:
        ap.error("no path given and MXNET_TPU_METRICS_LOG unset")
    snaps = load_snapshots(path)
    if not snaps:
        print(f"{path}: no snapshots", file=sys.stderr)
        sys.exit(1)
    snap = snaps[args.index]
    if args.format == "json":
        print(json.dumps(snap, indent=1, sort_keys=True))
    elif args.format == "prom":
        sys.path.insert(0, REPO)
        print(render_prom(snap["metrics"]), end="")
    else:
        print(f"# snapshot ts={snap.get('ts')} "
              f"({args.index} of {len(snaps)})")
        print(render_table(snap["metrics"]))


if __name__ == "__main__":
    main()
