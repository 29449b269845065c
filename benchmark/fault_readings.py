"""Readings of planted faults for a ``deepseek_v3`` serving cell, on the
chip at the cell's own size: what the comparison makes of a program whose
expert layers or latent pages are at fault, through the harness's own run
and verdict, in one process.

    python benchmark/fault_readings.py --workload <name> --seed 1

Each fault is planted in what the program is *given* (its weights, or the
block tables a step is handed), so the compiled step is the cell's own and
nothing compiles anew; the reference computes the sound model:

- ``routed_halved``: every expert layer's routed output is half of what it
  should be (``experts_down`` halved for both sides, the reference's gates
  doubled, which is exact in bfloat16);
- ``shared_dropped``: every expert layer's shared experts add nothing
  (``shared_down`` zero for the program, as made for the reference);
- ``page_stale``: once a sequence is past its second page, every step's
  latent kernel is pointed at the sequence's first page where the second
  should be read, so 16 tokens are attended twice and 16 never (the tables'
  second column; the writes of such a step lie in later pages).

One JSON line per fault with the number compared, its limit and the
verdict; the first line gives the share of the final residual stream's
variance that the layers add to the embedding's (over 512 tokens drawn
from the seed). The command exits 1 if a fault comes out correct. The
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

FAULTS = ("routed_halved", "shared_dropped", "page_stale")


def plant(fault, ref):
    """Put ``fault`` into the reference module's two entry points and the
    engine's batch builder; returns the call that takes it out again."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serving.llm.engine import LLMEngine
    init, gaps, build = (ref.init_params, ref.served_token_gaps,
                         LLMEngine._build_batch)
    kept = {}

    def layers(params, change):
        return dict(params, layers=[
            change(lp) if "experts_down" in lp else lp
            for lp in params["layers"]])

    if fault == "routed_halved":
        halve = jax.jit(lambda a: a * 0.5, donate_argnums=0)
        ref.init_params = lambda seed, model: layers(
            init(seed, model),
            lambda lp: dict(lp, experts_down=halve(lp["experts_down"])))
        ref.served_token_gaps = lambda params, prompt, served, model, \
            pad_to, quant=None: gaps(
                params, prompt, served, dict(
                    model, routed_scaling_factor=2.0
                    * model["routed_scaling_factor"]), pad_to, quant)
    elif fault == "shared_dropped":
        def drop(lp):
            kept.setdefault("down", []).append(lp["shared_down"])
            return dict(lp, shared_down=jnp.zeros_like(lp["shared_down"]))

        def restore(params):
            down = iter(kept["down"])
            return layers(params, lambda lp: dict(
                lp, shared_down=next(down)))
        ref.init_params = lambda seed, model: layers(init(seed, model),
                                                     drop)
        ref.served_token_gaps = lambda params, *a, **kw: gaps(
            restore(params), *a, **kw)
    elif fault == "page_stale":
        def stale(self, rows, plans, t, mb):
            bufs = build(self, rows, plans, t, mb)
            for seq in rows:
                if seq.seq_len >= 2 * self.cache.block_size:
                    bufs[4][seq.slot, 1] = bufs[4][seq.slot, 0]
            return bufs
        LLMEngine._build_batch = stale
    else:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")

    def unplant():
        ref.init_params, ref.served_token_gaps = init, gaps
        LLMEngine._build_batch = build
    return unplant


def stack_share(ref, seed, model, tokens=512):
    """The share of the final stream's variance that is not the
    embedding's."""
    import jax
    import numpy as np
    params = ref.init_params(seed, model)
    toks = jax.numpy.asarray(np.random.default_rng(seed).integers(
        0, model["vocab_size"], tokens), jax.numpy.int32)
    final = float((ref.hidden(params, toks, model) ** 2).mean())
    embed = float((params["embed"][toks].astype("float32") ** 2).mean())
    return 1.0 - embed / final


def read_fault(cell, config, traffic, seed, seconds, fault,
               require_chip=True):
    """One run of the cell with ``fault`` planted: the number compared,
    its limit and what the harness makes of the run's numbers."""
    from benchmark import run as run_mod
    ref = importlib.import_module("benchmark.reference."
                                  + config["reference"])
    unplant = plant(fault, ref)
    try:
        out, _ = run_mod.drive(cell, config, traffic, seed, seconds, False,
                               require_chip=require_chip)
    finally:
        unplant()
    check = out["checks"]["served_logit_gap"]
    return {"fault": fault, "served_logit_gap": check["value"],
            "limit": check["limit"],
            "correct": run_mod.verdict(out["checks"]),
            "tokens": out["notes"]["tokens_compared"]}


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
    ref = importlib.import_module("benchmark.reference."
                                  + config["reference"])
    print(json.dumps({"seed": args.seed, "stack_share": stack_share(
        ref, args.seed, config["model"])}), flush=True)
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
