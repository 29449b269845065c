"""Readings of planted faults for a block-diffusion (``sdar_moe``) serving
cell, on the chip at the cell's own size: what the comparison makes of a
program whose mask, passes or gates are at fault, through the harness's
own run and verdict, in one process.

    python benchmark/fault_readings_blockdiff.py --workload <name> --seed 1

The reference always computes the sound model. Each fault is planted in
the program's own code, so each run's programs compile anew:

- ``mask_causal``: the mask is causal inside a block: a position sees up
  to itself and not to the end of its block, in every pass and in the
  prompt's chunks (the model's ``_block_last`` gives the position back);
- ``settle_skipped``: the settling pass is left out: a block is answered
  with its last reveal and ``seq_len`` moves on at once, so later blocks
  read the K/V of a block that still held a mask token where its last
  position was revealed (the engine's commit settles a block as soon as
  none of its positions is masked);
- ``gates_unnormalised``: the eight gates of a token are its softmax
  scores as they come, not divided by their sum (the router is called
  with ``norm_topk_prob`` false).

One JSON line per fault with the number compared, its limit and the
verdict. The command exits 1 if a fault comes out correct. The
benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FAULTS = ("mask_causal", "settle_skipped", "gates_unnormalised")


def plant(fault):
    """Put ``fault`` into the program; returns the call that takes it out
    again."""
    from mxnet_tpu.serving.llm import engine as engine_mod, \
        model as program
    block_last, route = program._block_last, program.route_softmax_topk
    commit = engine_mod.LLMEngine._commit

    if fault == "mask_causal":
        program._block_last = lambda positions, block_length: positions
    elif fault == "settle_skipped":
        def commit_and_settle(self, rows, plans, toks, n_acc, events):
            decoded = commit(self, rows, plans, toks, n_acc, events)
            for seq in rows:
                if plans[seq]["kind"] == "denoise" \
                        and not any(seq.blk_masked):
                    decoded += self._settle_block(seq, events)
            return decoded
        engine_mod.LLMEngine._commit = commit_and_settle
    elif fault == "gates_unnormalised":
        program.route_softmax_topk = \
            lambda x, router, k, norm_topk_prob=True: route(
                x, router, k, False)
    else:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")

    def unplant():
        program._block_last, program.route_softmax_topk = block_last, route
        engine_mod.LLMEngine._commit = commit
    return unplant


def read_fault(cell, config, traffic, seed, seconds, fault,
               require_chip=True):
    """One run of the cell with ``fault`` planted: the number compared,
    its limit and what the harness makes of the run's numbers."""
    from benchmark import run as run_mod
    unplant = plant(fault)
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
