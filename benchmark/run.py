"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's two names lead to ``benchmark/configs/<config>.json`` (through
the configuration's ``file``) and ``benchmark/traffic/<traffic>.json``.
The configuration names its driver, a module under ``benchmark/drivers/``.
With ``--trace 1`` each per-layer metric of the cell is read by
``benchmark/layer_metrics/<metric>.py``. No cell, configuration or metric
is named in this file or in any other ``.py`` file here: a later PR adds
one as an entry of BENCHMARK.json and files of its own (README.md).

The last line of standard output is the result as one JSON object. The
numbers that decided ``correct`` are the last lines of standard error,
each beside its limit, and the last key of the result.
"""
import time

T_START = time.monotonic()      # set-up is counted from here

import argparse                 # noqa: E402
import importlib                # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def find_cell(bench, workload):
    """``(cell, configuration entry)`` of BENCHMARK.json for a name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        sys.exit(f"no workload {workload!r} in BENCHMARK.json; it has "
                 f"{sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, entry


def metrics_of(bench, group, cell, driver_reports):
    """The metrics of ``group`` that this cell reports: those that list it
    under ``workloads``, and of those without the key, the end-to-end
    metrics the driver reports and the per-layer metrics that move one."""
    out = []
    for m in bench[group]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif m.get("moves", m["name"]) in driver_reports:
            out.append(m)
    return out


def peak_of(device_kind):
    peaks = load_json("benchmark", "peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json ({sorted(peaks)}): add it "
                       "with its source, do not guess a peak")
    return peaks[device_kind]


def pick_devices(chips, require_chip=True):
    """The devices the cell runs on. Exits where JAX finds no accelerator
    or too few; ``require_chip=False`` is the tests' rehearsal."""
    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform == "cpu":
        sys.exit("benchmark/run.py needs an accelerator; jax found only "
                 "the cpu")
    if len(devices) < chips:
        sys.exit(f"the cell asks for {chips} chips and jax found "
                 f"{len(devices)}")
    return devices[:chips]


def layer_reader(name):
    """The ``read`` function of ``benchmark/layer_metrics/<name>.py``."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + "".join(c if c.isalnum() else "_" for c in name),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def memory_peak_bytes(devices):
    """Peak on the fullest chip: the buffers in use plus what the runtime
    reserved for the loaded programs' temporaries, which a TPU reports
    apart (``peak_bytes_reserved``) and which no buffer can take. The
    reservation stands for as long as the programs are loaded
    (``bytes_reserved`` still reads its peak when the window closes), so
    the two do fall together; each run prints both under ``notes``."""
    peaks = [0]
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def xla_compiles():
    """XLA compile requests so far, from the program's ``jax.monitoring``
    bridge (a request counts whether or not the persistent cache
    answered it)."""
    from mxnet_tpu.observability import (get_registry,
                                         install_jax_monitoring_bridge)
    install_jax_monitoring_bridge()
    return int(get_registry().counter("mxtpu_xla_compile_total").value)


def drive(cell, config, traffic, seed, seconds, trace, require_chip=True,
          t_start=None, control=None):
    """One run of the cell's driver: ``(what the driver returns, the
    devices)``. ``config`` and ``traffic`` are the loaded files, so a test
    can hand in toy sizes. ``control`` names a lower precision whose
    reading a serving driver takes beside the program's
    (``readings.py``); a run never gives it."""
    devices = pick_devices(cell["chips"], require_chip)
    driver = importlib.import_module("benchmark.drivers." + config["driver"])
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "seed": int(seed), "seconds": float(seconds), "trace": bool(trace),
           "devices": devices, "control": control,
           "t_start": T_START if t_start is None else t_start,
           "memory_peak_bytes": lambda: memory_peak_bytes(devices),
           "xla_compiles": xla_compiles}
    return driver.run(ctx), devices


def verdict(checks):
    """``correct``: every number compared was read and is within its
    limit."""
    return bool(checks) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())


def assemble(bench, cell, config, traffic, out, devices, trace, peak=None):
    """The result object of a run from what its driver returned."""
    from benchmark import trace as trace_mod
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if not trace:
        for m in metrics_of(bench, "end_to_end", cell, out["end_to_end"]):
            if m["name"] in out["end_to_end"]:
                result["metrics"][m["name"]] = {
                    "value": out["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        reduced = trace_mod.reduce(out["trace"])
        if reduced is None:
            raise RuntimeError("the traced window holds no device event")
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        seen = {"trace": reduced, "spans": out["spans"],
                "counters": out["counters"], "config": config,
                "traffic": traffic, "traced": out["traced"],
                "peak": peak or peak_of(dev.device_kind),
                "chips": len(devices)}
        for m in metrics_of(bench, "per_layer", cell, out["end_to_end"]):
            value = layer_reader(m["name"])(seen)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in
                           list(reduced["by_family"].items())[:10]],
            "idle_gaps": reduced["idle_gaps"][:10]}
    checks = out["checks"]
    result["correct"] = verdict(checks)
    result["checks"] = checks
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    cell, entry = find_cell(bench, args.workload)
    config = load_json(entry["file"])
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    pick_devices(cell["chips"])

    from mxnet_tpu.runtime import enable_compile_cache
    print(json.dumps({"info": "compile_cache",
                      "dir": enable_compile_cache()}), flush=True)
    out, devices = drive(cell, config, traffic, args.seed, args.seconds,
                         args.trace)
    print(json.dumps({"info": "notes", **out["notes"]}), flush=True)
    result = assemble(bench, cell, config, traffic, out, devices, args.trace)
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
