"""Driver for serving cells: a decoder behind ``LLMServer`` under the
requests of a traffic mix.

The construction follows ``chip_smoke.py``'s ``serve_phase``; the weights are
made on the device from the seed by the configuration's reference. The mix
fixes the work: ``benchmark/traffic.py`` gives every request with the moment
it is due, and the closed law's ``clients`` caps the requests in flight. The
window opens on a warm, empty server with the first request and closes when
the last one is answered, so every metric is over all of the work and all of
the time; ``--seconds`` sets how much work there is. A request unanswered a
minute after its window should have closed is ``unanswered``, which
``correct`` does not allow. A traced run records ``trace_seconds`` of steps
from the answer that makes ``trace_after_share`` of the run's requests.

Once the window has closed and the server's pools are freed, the reference
runs over a sample of finished requests (drawn from the seed, the longest
among them): the widest gap of a served token's logit below its best.
"""
import gc
import importlib
import math
import queue
import random
import time

from benchmark import trace as trace_mod, traffic as traffic_mod

#: how long past the window's nominal close an answer is waited for
STRAGGLER_S = 60.0
#: the fewest steps of a recording that per-layer metrics are read from
MIN_TRACED_STEPS = 20

COUNTED = ("requests_completed", "requests_failed", "tokens_generated",
           "decode_steps", "prefill_chunks", "preemptions", "prefix_hits")


def _counts(server):
    stats = server.stats()
    return {k: stats.get(k, 0) for k in COUNTED}


def _percentile(values, q):
    """Nearest-rank percentile of a non-empty list."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


class StepLog:
    """Wraps the engine's dispatch of one step in a traced run, from the
    benchmark's side: while ``on``, each step runs under a ``bench.step``
    annotation and leaves how many tokens each sequence fed and the
    context it then attends over. The dispatch fetches the step's tokens
    before it returns, so a logged step's device time lies inside its
    annotation, and ``benchmark/trace.py`` takes the traced window from
    the first such annotation to the last."""

    def __init__(self, engine):
        import jax
        self.on, self.steps = False, []
        self._span = jax.profiler.TraceAnnotation
        self._inner = engine._dispatch
        engine._dispatch = self

    def __call__(self, rows, plans):
        if not self.on:
            return self._inner(rows, plans)
        self.steps.append([(len(plans[s]["tokens"]),
                            s.seq_len + len(plans[s]["tokens"]))
                           for s in rows])
        with self._span("bench.step"):
            return self._inner(rows, plans)


def run(ctx):
    import jax
    from mxnet_tpu.serving.llm import LLMServer
    from mxnet_tpu.serving.llm.model import DecoderConfig, TinyDecoder

    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    model_shape = config["model"]
    ref = importlib.import_module("benchmark.reference."
                                  + config["reference"])

    # ------------------------------------------------------------ set-up --
    marks = {}

    def mark(name):
        marks[name] = time.monotonic() - ctx["t_start"]
    mark("imports_s")
    params = ref.init_params(seed, model_shape)
    plan = traffic_mod.requests(seed, traffic, model_shape["vocab_size"],
                                ctx["seconds"])
    jax.block_until_ready(params)
    mark("weights_and_requests_s")
    model = TinyDecoder(DecoderConfig(**model_shape))
    server = LLMServer(model, params, name="benchmark",
                       **config.get("engine", {}))
    mark("server_built_s")
    warmed = server.warmup()
    mark("warmed_s")
    server.start()
    steplog = StepLog(server.engine) if ctx["trace"] else None

    done = queue.Queue()
    records, in_flight = [], [0]
    cap = traffic["arrivals"].get("clients")
    blocks = {"used_peak": 0, "pool": 0}

    def send(i, t_open):
        due_s, prompt, n_out = plan[i]
        now = time.monotonic()
        # under a cap a request goes when a client is free, and is never
        # late; without one, how long after it was due it went counts
        rec = {"i": i, "t_send": now,
               "late_s": 0.0 if cap else now - (t_open + due_s)}
        in_flight[0] += 1
        fut = server.submit(prompt, n_out)
        fut.add_done_callback(
            lambda f, rec=rec: done.put((rec, f, time.monotonic())))

    def settle(timeout):
        """Take one answer, if one comes within ``timeout``."""
        try:
            rec, fut, t_done = done.get(timeout=max(0.0, timeout))
        except queue.Empty:
            return
        in_flight[0] -= 1
        rec["t_done"] = t_done
        err = fut.exception()
        if err is not None:
            rec["error"] = repr(err)
        else:
            res = fut.result()
            rec["tokens"] = list(res.tokens)
            rec["ttft_s"] = res.ttft_s
        records.append(rec)
        pool = server.stats()["kv_cache"]
        blocks["used_peak"] = max(blocks["used_peak"], pool["blocks_used"])
        blocks["pool"] = pool["num_blocks"]

    before = _counts(server)
    compiles0 = ctx["xla_compiles"]()
    setup_s = time.monotonic() - ctx["t_start"]

    # ------------------------------------------------------------ window --
    t_open = time.monotonic()
    t_give_up = t_open + ctx["seconds"] + STRAGGLER_S
    sent = 0

    def pump(until, answers=len(plan)):
        """Send what is due and wait for answers, up to ``until`` or until
        ``answers`` requests have been answered."""
        nonlocal sent
        while time.monotonic() < until and len(records) < answers and (
                sent < len(plan) or in_flight[0]):
            now = time.monotonic()
            free = cap is None or in_flight[0] < cap
            if sent < len(plan) and free and t_open + plan[sent][0] <= now:
                send(sent, t_open)
                sent += 1
                continue
            wake = until
            if sent < len(plan) and free:
                wake = min(wake, t_open + plan[sent][0])
            settle(wake - now)

    trace_data = traced = None
    if ctx["trace"]:
        # the recording starts by work, not by the clock: when this share
        # of the run's requests is answered, however fast the program is;
        # it lasts ``trace_seconds`` at the most and ends with the last
        # answer (pump returns when nothing is left to send or wait for)
        start_at = math.ceil(traffic["trace_after_share"] * len(plan))
        pump(t_give_up, start_at)
        with trace_mod.recording() as recorded:
            c0 = _counts(server)
            t_on = time.monotonic()
            steplog.on = True
            pump(min(t_give_up, t_on + traffic["trace_seconds"]))
            steplog.on = False
            t_off = time.monotonic()
            c1 = _counts(server)
        traced = {"steps": steplog.steps, "after_answers": start_at,
                  "started_s": t_on - t_open, "recorded_s": t_off - t_on,
                  "counts": {k: c1[k] - c0[k] for k in c0}}
        if len(steplog.steps) < MIN_TRACED_STEPS:
            server.shutdown(drain=False)
            raise RuntimeError(
                f"traffic mix {ctx['cell']['traffic']!r}: the recording, "
                f"started after {start_at} of {len(plan)} answers "
                f"{traced['started_s']:.2f} s into the batch, held "
                f"{len(steplog.steps)} steps in a window_s of "
                f"{traced['recorded_s']:.2f}; {MIN_TRACED_STEPS} are the "
                "least a per-layer metric is read from: give the mix more "
                "requests a second of --seconds (benchmark/README.md)")
        trace_data = recorded["trace"]
    pump(t_give_up)
    # all answered: the window closes with the last answer; else now
    t_close = max(r["t_done"] for r in records) \
        if records and len(records) == len(plan) else time.monotonic()
    window_s = t_close - t_open
    after = _counts(server)
    compiles = ctx["xla_compiles"]() - compiles0
    finished = [r for r in records if "tokens" in r]
    unanswered = len(plan) - len(records)
    failed = len(plan) - len(finished)
    observed = {k: server.stats().get(k) for k in (
        "max_seqs", "prefill_chunk", "prefix_cache", "kv_dtype",
        "weight_dtype", "q_tokens", "spec_k")}
    memory_peak = ctx["memory_peak_bytes"]()
    memory_stats = ctx["devices"][0].memory_stats() or {}
    server.shutdown(drain=False)
    # the requests evicted at shutdown resolve with an error whose
    # traceback holds the server: drop them, or its pools stay
    while not done.empty():
        done.get_nowait()

    out_tokens = sum(len(r["tokens"]) for r in finished)
    latency = [r["t_done"] - r["t_send"] + r["late_s"] for r in finished]
    ttft = [r["ttft_s"] + r["late_s"] for r in finished]
    tpot = [(r["t_done"] - r["t_send"] - r["ttft_s"])
            / (len(r["tokens"]) - 1) for r in finished
            if len(r["tokens"]) > 1]
    end_to_end = {"setup_s": setup_s}
    if finished:
        end_to_end["serve.out_tokens_per_s"] = out_tokens / window_s
        # from when a request was due (under a cap: sent) to its answer,
        # by the client's clock: for a cell below capacity as its tail
        end_to_end["serve.request_p90_ms"] = 1e3 * _percentile(latency, 0.9)

    # -------------------------------------- free the server, then compare --
    del server, steplog, model, send, settle, pump, done
    gc.collect()
    jax.clear_caches()
    freed = (ctx["devices"][0].memory_stats() or {}).get("bytes_in_use")
    t0 = time.monotonic()
    rng = random.Random(seed)
    by_length = sorted(finished, key=lambda r: len(plan[r["i"]][1])
                       + len(r["tokens"]))
    sample = by_length[-1:] + rng.sample(
        by_length[:-1], min(len(by_length) - 1,
                            traffic["checked_requests"] - 1))
    longest = max(len(p) + o for _, p, o in plan)
    pad_to = -(-longest // 128) * 128
    widest, compared, wrong_length, control = None, 0, 0, None
    for r in sample:
        _, prompt, n_out = plan[r["i"]]
        if len(r["tokens"]) != n_out:
            wrong_length += 1
        gaps = ref.served_token_gaps(params, prompt, r["tokens"],
                                     model_shape, pad_to)
        widest = max(widest or 0.0, float(gaps.max()))
        compared += len(r["tokens"])
        if ctx.get("control"):      # benchmark/readings.py, never a run
            control = max(control or 0.0, float(ref.served_token_gaps(
                params, prompt, r["tokens"], model_shape, pad_to,
                quant=ctx["control"]).max()))
    reference_s = time.monotonic() - t0
    numbers = {"served_logit_gap": widest, "wrong_length": wrong_length,
               "unanswered": unanswered, "compiles_in_window": compiles}
    limits = config["limits"]
    checks = {k: {"value": numbers.get(k), "limit": limits[k]}
              for k in limits}
    counters = {k: after[k] - before[k] for k in after}
    counters["kv_blocks_used_peak"] = blocks["used_peak"]
    counters["kv_blocks_pool"] = blocks["pool"]
    notes = {"requests": len(plan), "answered": len(records),
             "out_tokens": out_tokens, "window_s": window_s,
             # under a half, the mix is due for more requests (README.md)
             "window_share": window_s / ctx["seconds"],
             # how many answers had come by each whole second of the window:
             # a mix's trace_after_share is chosen from this line
             "answers_by_second": [
                 sum(r["t_done"] - t_open <= s for r in records)
                 for s in range(1, math.ceil(window_s) + 1)],
             "setup_s": setup_s, "reference_s": reference_s,
             "tokens_compared": compared, "warmed_programs": len(warmed),
             "warmup_s": sum(warmed.values()), "setup_marks": marks,
             "observed_defaults": observed, "counts": counters,
             "memory_stats": {k: v for k, v in memory_stats.items()
                              if "bytes" in k},
             "bytes_in_use_at_reference": freed,
             "traced": traced and {
                 **traced, "steps": len(traced["steps"]),
                 "tokens": sum(n for step in traced["steps"]
                               for n, _ in step)},
             "latency_ms": {name: {"p50": 1e3 * _percentile(v, 0.5),
                                   "p90": 1e3 * _percentile(v, 0.9)}
                            for name, v in (("request", latency),
                                            ("ttft", ttft), ("tpot", tpot))
                            if v},
             "not_compared": {k: v for k, v in numbers.items()
                              if k not in limits}}
    return {"attempted": len(plan), "failed": failed,
            "end_to_end": end_to_end, "memory_peak_bytes": memory_peak,
            "checks": checks, "trace": trace_data, "traced": traced,
            "spans": {"request_s": latency, "ttft_s": ttft, "tpot_s": tpot},
            "counters": counters,
            "control_checks": control is not None and {
                **checks, "served_logit_gap": {
                    "value": control, "limit": limits["served_logit_gap"]}},
            "notes": notes}
