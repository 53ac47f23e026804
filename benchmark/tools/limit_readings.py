#!/usr/bin/env python3
"""What a cell's limits must fail, read on the chip at the cell's size and
judged under the cell's own ``limits/`` file.

    python3 benchmark/tools/limit_readings.py <cell> --seeds 1,2,3 \
        [--what control,faults,half,unchanged] [--faults a,b] \
        --out chiprun_out/limit_readings.jsonl

``fault_readings.py`` with three things more, which the decoders' cells of one
sequence a step need (PR 34 and PR 36 read theirs through scratch copies of
it): ``half`` keeps the first half of each sequence's POSITIONS where a batch
holds one row (``readings.py`` halves the rows, and half of one row is none),
``unchanged`` steps a state that is handed back as it came, ``--faults`` picks
some of the reference's ``FAULTS`` by name. For each seed the float32
reference is stepped once and each of the others is put in the program's place
against it: one JSON line a reading with every number ``correct`` could
compare, ``passes`` (what ``compare.judge`` says under the cell's limits: a
control or a fault that passes is not seen) and the numbers over their limit.
One process on one chip; the benchmark's runs never run this.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]


def kinds_of(ref, what: list, faults: list, batches: list) -> list:
    """``(name, run_steps keywords)`` of each reading asked for."""
    kinds = []
    if "control" in what:
        kinds.append(("control_fp8", {"precision": "fp8"}))
    if "faults" in what:
        known = getattr(ref, "FAULTS", ())
        unknown = [f for f in faults if f not in known]
        if unknown:
            raise SystemExit(f"no planted fault {unknown}; there are {known}")
        kinds += [(f, {"precision": "float32+" + f}) for f in faults or known]
    if "half" in what:
        first = next(iter(batches[0].values()))
        keep = slice(0, first.shape[0] // 2) if first.shape[0] > 1 else \
            (slice(None), slice(0, first.shape[1] // 2))
        kinds.append(("half_batch", {"rows": keep}))
    if "unchanged" in what:
        kinds.append(("unchanged", {"frozen": True}))
    return kinds


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
    ap.add_argument("--what", default="control,faults,half,unchanged")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    res = loader.resolve_cell(args.cell)
    cfg, traffic, chips = res["config"], res["traffic"], res["cell"]["chips"]
    limits = res["limits"]["limits"]
    bench_run.check_devices(1)
    ref = loader.load_module(*res["files"]["reference"])
    driver = loader.load_module(*res["files"]["driver"])
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        for seed in (int(s) for s in args.seeds.split(",")):
            weights = jax.tree_util.tree_map(
                np.asarray, make_weights(ref, cfg, seed))
            batches = make_pool(traffic, cfg, seed, chips)[:driver.CHECK_STEPS]
            t0 = time.perf_counter()
            base = run_steps(ref, cfg, weights, batches)
            base_s = time.perf_counter() - t0
            for name, kw in kinds_of(
                    ref, args.what.split(","),
                    [f for f in args.faults.split(",") if f], batches):
                t0 = time.perf_counter()
                got = run_steps(ref, cfg, weights, batches, **kw)
                numbers, where = compare.training_numbers(got, base)
                ok, compared = compare.judge(numbers, limits)
                rec = {"cell": args.cell, "seed": seed, "reading": name,
                       "passes": ok,
                       "over": sorted(k for k, v in compared.items()
                                      if v["value"] > v["limit"]),
                       "numbers": numbers, "where": where, "limits": limits,
                       "losses": got["losses"], "ref_losses": base["losses"],
                       "seconds": time.perf_counter() - t0,
                       "base_seconds": base_s}
                out.write(json.dumps(rec) + "\n")
                out.flush()
                print(json.dumps({k: rec[k] for k in (
                    "seed", "reading", "passes", "over", "numbers", "where",
                    "seconds")}), flush=True)


if __name__ == "__main__":
    main()
