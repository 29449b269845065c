"""Readings of planted faults for a ``granitemoehybrid`` serving cell, on
the chip at the cell's own size: what the comparison makes of a program
whose state-space or grouped-query layers are at fault, through the
harness's own run and verdict, in one process.

    python benchmark/fault_readings_ssm.py --workload <name> --seed 1

The reference always computes the sound model:

- ``decay_dropped``: every state-space layer reads its decay ``exp(dt A)``
  as 1, so a state never forgets (``A_log`` of minus infinity for the
  program, as made for the reference: planted in the weights, nothing
  compiles anew);
- ``group_shifted``: every attention layer's query head ``j`` reads kv
  head ``(j // m + 1) % G`` (the program's ``wk`` and ``wv`` hold each kv
  head's columns one head over; as made for the reference; nothing
  compiles anew);
- ``state_not_reset``: a slot keeps its last occupant's recurrent state:
  no run looks to the state-space step as if it began at position 0, so
  from the 33rd request on a sequence starts from what the slot's last
  one left (the convolution's window still starts from zeros). Planted in
  the step itself, so this run's programs compile anew.

One JSON line per fault with the number compared, its limit and the
verdict. The command exits 1 if a fault comes out correct. The
benchmark's own runs never run this.
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

FAULTS = ("decay_dropped", "group_shifted", "state_not_reset")


def plant(fault, ref, model):
    """Put ``fault`` into what the program is given (the reference
    module's ``init_params``, with ``served_token_gaps`` handed the sound
    weights again) or into the step; returns the call that takes it out
    again."""
    import jax.numpy as jnp
    from mxnet_tpu.serving.llm import model as program
    init, gaps, step = (ref.init_params, ref.served_token_gaps,
                        program.ssd_state_step)
    sound = []          # the leaves that were replaced, layer by layer

    def weights(change, kind):
        """The program's weights with ``change`` over the layers that
        have ``kind``'s leaf, and the reference's as they were made."""
        def faulty(seed, shape):
            params = init(seed, shape)
            layers = []
            for lp in params["layers"]:
                if kind in lp:
                    new = change(lp)
                    sound.append({k: lp[k] for k in new})
                    lp = dict(lp, **new)
                layers.append(lp)
            return dict(params, layers=layers)

        def restored(params, *a, **kw):
            kept = iter(sound)
            return gaps(dict(params, layers=[
                dict(lp, **next(kept)) if kind in lp else lp
                for lp in params["layers"]]), *a, **kw)
        ref.init_params, ref.served_token_gaps = faulty, restored

    if fault == "decay_dropped":
        weights(lambda lp: {"A_log": jnp.full_like(lp["A_log"], -jnp.inf)},
                "A_log")
    elif fault == "group_shifted":
        groups = model["num_key_value_heads"]

        def shifted(w):
            return jnp.roll(w.reshape(w.shape[0], groups, -1), -1,
                            axis=1).reshape(w.shape)
        weights(lambda lp: {"wk": shifted(lp["wk"]),
                            "wv": shifted(lp["wv"])}, "wk")
    elif fault == "state_not_reset":
        program.ssd_state_step = lambda x, dt, A, B, C, state, seq_ids, \
            positions, valid, layer: step(x, dt, A, B, C, state, seq_ids,
                                          positions + 1, valid, layer)
    else:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")

    def unplant():
        ref.init_params, ref.served_token_gaps = init, gaps
        program.ssd_state_step = step
    return unplant


def read_fault(cell, config, traffic, seed, seconds, fault,
               require_chip=True):
    """One run of the cell with ``fault`` planted: the number compared,
    its limit and what the harness makes of the run's numbers."""
    from benchmark import run as run_mod
    ref = importlib.import_module("benchmark.reference."
                                  + config["reference"])
    unplant = plant(fault, ref, config["model"])
    try:
        out, _ = run_mod.drive(cell, config, traffic, seed, seconds, False,
                               require_chip=require_chip)
    finally:
        unplant()
    check = out["checks"]["served_logit_gap"]
    return {"fault": fault, "served_logit_gap": check["value"],
            "limit": check["limit"],
            "correct": run_mod.verdict(out["checks"]),
            "tokens": out["notes"]["tokens_compared"],
            "requests": out["notes"]["requests"]}


def main(argv=None):
    from benchmark import run as run_mod
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
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
