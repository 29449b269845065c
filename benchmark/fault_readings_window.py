"""Readings of planted faults for a ``cohere2_moe`` serving cell, on the
chip at the cell's own size: what the comparison makes of a program whose
window layers, full layer or shared experts are at fault, through the
harness's own run and verdict, in one process.

    python benchmark/fault_readings_window.py --workload <name> --seed 1

The reference always computes the sound model:

- ``window_ignored``: the sliding-window layers attend over every key up
  to a token's own (their ``sliding_window`` the whole context for the
  program, so the cache keeps their pages too; planted in the model's
  configuration, so this run's programs compile anew);
- ``window_page_short``: the window one page short, ``sliding_window``
  less the cell's ``block_size`` for the program (kernel and cache alike;
  planted in the configuration);
- ``rope_on_full``: the full layer's queries and keys turned by their
  positions as a sliding layer's are (planted in the block's projections,
  so this run's programs compile anew);
- ``shared_summed``: the shared experts summed instead of averaged (every
  layer's ``shared_down`` times ``num_shared_experts`` for the program;
  planted in the weights, nothing compiles anew).

The checked sample always holds the run's longest request, whose context
passes the window, so that the first two show. One JSON line per fault
with the number compared, its limit and the verdict. The command exits 1
if a fault comes out correct. The benchmark's own runs never run this.
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

FAULTS = ("window_ignored", "window_page_short", "rope_on_full",
          "shared_summed")


def plant(fault, ref, model, block_size):
    """Put ``fault`` into what the program is given (the reference
    module's ``init_params``, with ``served_token_gaps`` handed the sound
    weights again), into its configuration or into its block; returns the
    call that takes it out again."""
    from mxnet_tpu.serving.llm import model as program
    init, gaps = ref.init_params, ref.served_token_gaps
    config, qkv = program.DecoderConfig, program.TinyDecoder._qkv_window

    def configured(**change):
        def faulty(**kw):
            return config(**dict(kw, **{k: f(kw) for k, f in
                                        change.items()}))
        program.DecoderConfig = faulty

    if fault == "window_ignored":
        configured(sliding_window=lambda kw: kw["max_context"])
    elif fault == "window_page_short":
        configured(sliding_window=lambda kw: kw["sliding_window"]
                   - block_size)
    elif fault == "rope_on_full":
        program.TinyDecoder._qkv_window = \
            lambda self, lp, u, positions, sliding: qkv(
                self, lp, u, positions, True)
    elif fault == "shared_summed":
        sound = []

        def faulty(seed, shape):
            params = init(seed, shape)
            layers = []
            for lp in params["layers"]:
                sound.append(lp["shared_down"])
                layers.append(dict(lp, shared_down=(
                    lp["shared_down"] * shape["num_shared_experts"]).astype(
                        lp["shared_down"].dtype)))
            return dict(params, layers=layers)

        def restored(params, *a, **kw):
            return gaps(dict(params, layers=[
                dict(lp, shared_down=s)
                for lp, s in zip(params["layers"], sound)]), *a, **kw)
        ref.init_params, ref.served_token_gaps = faulty, restored
    else:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")

    def unplant():
        ref.init_params, ref.served_token_gaps = init, gaps
        program.DecoderConfig = config
        program.TinyDecoder._qkv_window = qkv
    return unplant


def read_fault(cell, config, traffic, seed, seconds, fault,
               require_chip=True):
    """One run of the cell with ``fault`` planted: the number compared,
    its limit and what the harness makes of the run's numbers."""
    from benchmark import run as run_mod
    ref = importlib.import_module("benchmark.reference."
                                  + config["reference"])
    unplant = plant(fault, ref, config["model"],
                    config["engine"]["block_size"])
    try:
        out, _ = run_mod.drive(cell, config, traffic, seed, seconds, False,
                               require_chip=require_chip)
    finally:
        unplant()
    check = out["checks"]["served_logit_gap"]
    return {"fault": fault, "served_logit_gap": check["value"],
            "limit": check["limit"],
            "correct": run_mod.verdict(out["checks"]),
            "unanswered": out["checks"]["unanswered"]["value"],
            "tokens": out["notes"]["tokens_compared"],
            "requests": out["notes"]["requests"],
            "window_s": out["notes"]["window_s"]}


def main(argv=None):
    from benchmark import run as run_mod
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--faults", nargs="+", default=list(FAULTS))
    args = ap.parse_args(argv)
    bench = run_mod.load_json("BENCHMARK.json")
    cell, entry = run_mod.find_cell(bench, args.workload)
    config = run_mod.load_json(entry["file"])
    traffic = run_mod.load_json("benchmark", "traffic",
                                cell["traffic"] + ".json")
    run_mod.pick_devices(cell["chips"])
    from mxnet_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    caught = True
    for fault in args.faults:
        gc.collect()        # the last fault's server, before the next
        got = read_fault(cell, config, traffic, args.seed, args.seconds,
                         fault)
        caught &= not got["correct"]
        print(json.dumps({"seed": args.seed, **got}), flush=True)
    sys.exit(0 if caught else 1)


if __name__ == "__main__":
    main()
