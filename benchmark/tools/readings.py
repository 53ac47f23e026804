#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip, at the cell's size.

    python3 benchmark/tools/readings.py <cell> --seeds 1,2,3 \
        --what program,control,half_batch --out chiprun_out/readings.jsonl

For each seed, one JSON line per reading, all against the float32 reference:

    program     the program's first steps through ``ctx.fit`` (a short window)
    control     the reference in float8 (e4m3 forward, e5m2 backward), the
                step below the bfloat16 that the configurations state, put in
                the program's place
    bf16        the reference with the same places rounded to bfloat16: a
                second witness for looking at a reading
    unchanged   the reference with every step returning its state unchanged
    half_batch  the reference with half of each batch left out and the mean
                taken over the rest, put in the program's place
    no_exchange (a cell on several chips) the reference stepped on the first
                chip's rows alone: what that chip holds when no gradient and
                no batch statistic crosses the chips

All in one process, which holds the chip: the benchmark's own runs never run
this. A state left unchanged reads 1 by the measure and needs no run.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]


def spread_of(compare, got: dict, base: dict) -> dict:
    dead = compare.dead_leaves(base["grad1"])
    return {"grad1": compare.gap_stats(got["grad1"], base["grad1"]),
            "delta": compare.gap_stats(got["delta"], base["delta"], dead)}


def main(argv=None):
    import jax
    import numpy as np

    from harness import compare, loader
    from harness.reference_run import make_weights, run_steps
    from harness.traffic import make_pool

    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control,half_batch")
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    what = args.what.split(",")

    res = loader.resolve_cell(args.cell)
    cfg, traffic, chips = res["config"], res["traffic"], res["cell"]["chips"]
    ref = loader.load_module("references", res["cell"]["config"])
    driver = loader.load_module(*res["files"]["driver"])
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        def emit(rec):
            out.write(json.dumps(rec) + "\n")
            out.flush()

        for seed in (int(s) for s in args.seeds.split(",")):
            if "program" in what:
                r = driver.run({"resolved": res, "seed": seed,
                                "seconds": args.seconds, "trace": False,
                                "t_start": time.perf_counter(),
                                "keep_readings": True})
                pr, rr = r["readings"]["program"], r["readings"]["reference"]
                emit({"cell": args.cell, "seed": seed, "reading": "program",
                      "numbers": {**{k: v["value"]
                                     for k, v in r["compared"].items()},
                                  **r["window"]["not_compared"]},
                      "where": r["window"]["worst_leaves"],
                      "spread": spread_of(compare, pr, rr),
                      "losses": r["window"]["program_losses"]})
            if not {"control", "half_batch", "bf16", "unchanged",
                    "no_exchange"} & set(what):
                continue
            weights = jax.tree_util.tree_map(
                np.asarray, make_weights(ref, cfg, seed))
            batches = make_pool(traffic, cfg, seed, chips)[:driver.CHECK_STEPS]
            base = run_steps(ref, cfg, weights, batches)
            rows = traffic["per_chip_batch"] * chips
            for name, kw in (("control", {"precision": "fp8"}),
                             ("bf16", {"precision": "bf16"}),
                             ("half_batch", {"rows": slice(0, rows // 2)}),
                             ("no_exchange",
                              {"rows": slice(0, rows // chips)}),
                             ("unchanged", {"frozen": True})):
                if name not in what:
                    continue
                got = run_steps(ref, cfg, weights, batches, **kw)
                numbers, where = compare.training_numbers(got, base)
                emit({"cell": args.cell, "seed": seed, "reading": name,
                      "numbers": numbers, "where": where,
                      "spread": spread_of(compare, got, base),
                      "losses": got["losses"], "ref_losses": base["losses"]})


if __name__ == "__main__":
    main()
