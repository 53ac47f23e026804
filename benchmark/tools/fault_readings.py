#!/usr/bin/env python3
"""The readings of a reference's own planted faults, and of its float8
control, on the chip, at the cell's size: what ``readings.py`` does for the
faults every cell can have, for those only one configuration can.

    python3 benchmark/tools/fault_readings.py <cell> --seeds 1,2,3 \
        [--what control,faults] --out chiprun_out/fault_readings.jsonl

A reference lists its faults by name in ``FAULTS`` and computes one when its
``precision`` reads ``"float32+<fault>"``. For each seed
the float32 reference is stepped once, and each of the others is put in the
program's place against it: one JSON line per reading, with every number
``correct`` could compare. One process on one chip, whatever the cell asks
for (a reference steps the global batch on one device); the benchmark's runs
never run this.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]


def main(argv=None):
    import jax
    import numpy as np

    import run as bench_run     # sets the compile cache as the command does
    from harness import compare, loader
    from harness.reference_run import make_weights, run_steps
    from harness.traffic import make_pool

    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="control,faults")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    res = loader.resolve_cell(args.cell)
    cfg, traffic, chips = res["config"], res["traffic"], res["cell"]["chips"]
    bench_run.check_devices(1)
    ref = loader.load_module(*res["files"]["reference"])
    driver = loader.load_module(*res["files"]["driver"])
    what = args.what.split(",")
    kinds = [("control_fp8", {"precision": "fp8"})] if "control" in what \
        else []
    if "faults" in what:
        kinds += [(f, {"precision": "float32+" + f})
                  for f in getattr(ref, "FAULTS", ())]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        for seed in (int(s) for s in args.seeds.split(",")):
            weights = jax.tree_util.tree_map(
                np.asarray, make_weights(ref, cfg, seed))
            batches = make_pool(traffic, cfg, seed, chips)[:driver.CHECK_STEPS]
            t0 = time.perf_counter()
            base = run_steps(ref, cfg, weights, batches)
            base_s = time.perf_counter() - t0
            for name, kw in kinds:
                t0 = time.perf_counter()
                got = run_steps(ref, cfg, weights, batches, **kw)
                numbers, where = compare.training_numbers(got, base)
                rec = {"cell": args.cell, "seed": seed, "reading": name,
                       "numbers": numbers, "where": where,
                       "losses": got["losses"], "ref_losses": base["losses"],
                       "seconds": time.perf_counter() - t0,
                       "base_seconds": base_s}
                out.write(json.dumps(rec) + "\n")
                out.flush()
                print(json.dumps({k: rec[k] for k in (
                    "seed", "reading", "numbers", "seconds")}), flush=True)


if __name__ == "__main__":
    main()
