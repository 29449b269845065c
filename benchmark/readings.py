"""Readings for a cell's limits, on the chip at the cell's own size: what
the comparison makes of the *control* (the reference computed in the
precision below the configuration's, put in the program's place), on
several seeds in one process.

    python benchmark/readings.py --workload <name> --seeds 1 2 3

One JSON line per seed. A training cell also gets the "half of the batch
left out" fault's reading. A serving cell is served ``--seconds`` of its
mix, and at each position of the checked requests the control reads the
gap of the token that the lower precision puts first; both sets of numbers
go through the harness's own verdict, and the command exits 1 unless the
program comes out correct and the control not correct on every seed. The limits in a configuration
file are set between the largest reading of sound program runs
(``run.py``'s own ``checks`` over a dozen seeds) and the smallest reading
here; PERF.md records both. The benchmark's own runs never run this.
"""
import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def read_serve(cell, config, traffic, seed, control, seconds,
               require_chip=True):
    """For one seed, a short run of the cell and, over the same checked
    requests with the same weights, the control in the program's place:
    the number compared and what the harness makes of each set of
    numbers (``correct`` has to come out true of the program and false
    of the control)."""
    from benchmark import run as run_mod
    out, _ = run_mod.drive(cell, config, traffic, seed, seconds, False,
                           require_chip=require_chip, control=control)
    program, ctl = out["checks"], out["control_checks"]
    return {"program": program["served_logit_gap"]["value"],
            "control": ctl["served_logit_gap"]["value"],
            "limit": program["served_logit_gap"]["limit"],
            "program_correct": run_mod.verdict(program),
            "control_correct": run_mod.verdict(ctl),
            "tokens": out["notes"]["tokens_compared"],
            "window_s": out["notes"]["window_s"]}


def read(config, traffic, seed, control):
    """``{"control": numbers, "half_batch": numbers}`` for one seed of a
    training cell."""
    from benchmark import compare, traffic as traffic_mod
    ref = importlib.import_module("benchmark.reference."
                                  + config["reference"])
    model, opt = config["model"], config["optimizer"]
    steps = traffic["check_steps"]
    init = ref.init_params(seed, model)
    xs, ys = traffic_mod.train_batches(seed, traffic, model, config["dtype"])

    def follow(**kw):
        return dict(zip(compare.TRAINING_READINGS,
                        ref.train_steps(init, xs, ys, model, opt,
                                        steps=steps, **kw)))
    want, matrices = follow(), ref.matrices(ref.param_specs(model))
    return {"control": compare.training(follow(quant=control), want,
                                        matrices),
            "half_batch": compare.training(
                follow(rows=traffic["batch"] // 2), want, matrices)}


def main(argv=None):
    from benchmark import run as run_mod
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    bench = run_mod.load_json("BENCHMARK.json")
    cell, entry = run_mod.find_cell(bench, args.workload)
    config = run_mod.load_json(entry["file"])
    traffic = run_mod.load_json("benchmark", "traffic",
                                cell["traffic"] + ".json")
    run_mod.pick_devices(cell["chips"])
    from mxnet_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    control = config["control_precision"]
    as_expected = True
    for seed in args.seeds:
        gc.collect()        # the last seed's server, before the next
        if config["driver"] == "serve":
            got = read_serve(cell, config, traffic, seed, control,
                             args.seconds)
            as_expected &= got["program_correct"] \
                and not got["control_correct"]
        else:
            got = read(config, traffic, seed, control)
        print(json.dumps({"seed": seed, **got}), flush=True)
    sys.exit(0 if as_expected else 1)


if __name__ == "__main__":
    main()
