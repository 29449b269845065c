"""Readings of planted faults for a ``gigachat3_5`` serving cell, on the
chip at the cell's own size: what the comparison makes of a program whose
GatedDeltaNet layers or held experts are at fault, through the harness's
own run and verdict, in one process.

    python benchmark/fault_readings_gdn.py --workload <name> --seed 1

The reference always computes the sound model:

- ``delta_dropped``: the delta rule's write leaves out what the state
  predicts for the key (``S_t = S_t + k_t (beta_t v_t)^T``, no ``- S^T
  k``): planted in the program's one write function (``ops.gdn``'s
  ``_delta_write``, which the kernel and its ``jnp`` twin share; the
  kernel's jitted program is traced again), so this run's programs
  compile anew;
- ``decay_dropped``: every GatedDeltaNet layer reads its decay as 1
  (``g = 0``: ``A_log`` of minus infinity for the program, as made for the
  reference: planted in the weights, nothing compiles anew);
- ``state_not_reset``: a slot keeps its last occupant's delta-rule state:
  no run looks to the state step as if it began at position 0, so from the
  129th request on a sequence starts from what the slot's last one left
  (the convolution's window still starts from zeros). Planted in the step
  itself, so this run's programs compile anew;
- ``experts_offset_shifted``: the program computes the next chip's share,
  experts 16 to 31 of the router's, with the weights it holds for 0 to 15
  (its ``first_routed_expert`` one share over; planted in the model's
  configuration, so this run's programs compile anew);
- ``shared_dropped``: every expert layer's shared expert adds nothing (its
  ``shared_down`` zeros for the program; planted in the weights).

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

FAULTS = ("delta_dropped", "decay_dropped", "state_not_reset",
          "experts_offset_shifted", "shared_dropped")


def plant(fault, ref, model):
    """Put ``fault`` into what the program is given (the reference
    module's ``init_params``, with ``served_token_gaps`` handed the sound
    weights again), into its configuration or into its step; returns the
    call that takes it out again."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import gdn
    from mxnet_tpu.serving.llm import model as program
    init, gaps = ref.init_params, ref.served_token_gaps
    step, write, config = (program.gdn_state_step, gdn._delta_write,
                           program.DecoderConfig)
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

    if fault == "delta_dropped":
        gdn._delta_write = lambda s, k, v, beta: s + k * (beta * v)
        # the kernel's program, traced with the sound write, is traced
        # again
        gdn._gdn_state_pallas.clear_cache()
    elif fault == "decay_dropped":
        weights(lambda lp: {"A_log": jnp.full_like(lp["A_log"], -jnp.inf)},
                "A_log")
    elif fault == "state_not_reset":
        program.gdn_state_step = lambda q, k, v, g, beta, state, seq_ids, \
            positions, valid, layer: step(q, k, v, g, beta, state, seq_ids,
                                          positions + 1, valid, layer)
    elif fault == "experts_offset_shifted":
        def shifted(**kw):
            return config(**dict(kw, first_routed_expert=kw.get(
                "first_routed_expert", 0) + kw["n_routed_experts"]))
        program.DecoderConfig = shifted
    elif fault == "shared_dropped":
        weights(lambda lp: {"shared_down": jnp.zeros_like(
            lp["shared_down"])}, "shared_down")
    else:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")

    def unplant():
        ref.init_params, ref.served_token_gaps = init, gaps
        program.gdn_state_step, program.DecoderConfig = step, config
        if gdn._delta_write is not write:
            gdn._delta_write = write
            gdn._gdn_state_pallas.clear_cache()
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
