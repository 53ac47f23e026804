#!/usr/bin/env python3
"""The runs a cell's bounds are set from: one run that compiles, two sets of
runs on the same seeds, and a few traced runs, each a process of its own.

    python3 benchmark/tools/measure_sets.py <cell> [<cell> ...] \
        [--runs 6] [--traced 3] [--out chiprun_out/sets.jsonl] [--root DIR]

One JSON line per run goes to ``--out``; at the end each metric's spread (the
distance between the quartiles as a share of the median, by
``statistics.quantiles(values, n=4)``) is printed per set. This process never
touches JAX: every run holds the chip alone. It stops at the first run that
fails, and where a cell's second run does not find its programs in the cache.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEEDS = [2147483801, 915, 77003, 40404, 2000000007, 612345, 31337, 8675309]


def one_run(root, command, cell, seed, seconds, trace):
    t0 = time.time()
    p = subprocess.run(
        [*command, "--workload", cell, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        cwd=root, capture_output=True, text=True)
    last = p.stdout.strip().splitlines()[-1:] or [""]
    try:
        line = json.loads(last[0])
    except ValueError:
        line = None
    return {"rc": p.returncode, "wall_s": time.time() - t0, "line": line,
            "stderr_tail": p.stderr[-1500:] if line is None or
            not line.get("correct") else ""}


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/sets.jsonl")
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)
    with open(os.path.join(args.root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = bench["run_seconds"]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")
    got = {}

    def run(cell, tag, seed, trace=0):
        r = one_run(args.root, bench["command"], cell, seed, seconds, trace)
        r.update(cell=cell, set=tag, seed=seed, trace=trace)
        out.write(json.dumps(r) + "\n")
        out.flush()
        ok = r["rc"] == 0 and r["line"] and r["line"]["correct"]
        m = {k: v["value"] for k, v in r["line"]["metrics"].items()} \
            if r["line"] else {}
        print(cell, tag, seed, "rc", r["rc"], "correct",
              r["line"] and r["line"]["correct"], "wall %.0f" % r["wall_s"],
              json.dumps(m), flush=True)
        if not ok:
            print(r["stderr_tail"], flush=True)
            sys.exit(1)
        return m

    for cell in args.cells:
        first = run(cell, "compiles", 4242)
        for tag in ("set1", "set2"):
            for seed in SEEDS[:args.runs]:
                m = run(cell, tag, seed)
                if m["setup_s"] > 0.7 * first["setup_s"] \
                        and first["setup_s"] > 60:
                    sys.exit(f"{cell}: set-up {m['setup_s']:.0f} s after "
                             f"{first['setup_s']:.0f} s: the cache missed")
                for k, v in m.items():
                    got.setdefault((cell, k), {}).setdefault(
                        tag, []).append(v)
        for i in range(args.traced):
            run(cell, "traced", 8101 + i, trace=1)
    for (cell, k), sets in got.items():
        print(cell, k, {t: {"median": statistics.median(v),
                            "spread": spread(v)} for t, v in sets.items()
                        if len(v) >= 2})


if __name__ == "__main__":
    main()
