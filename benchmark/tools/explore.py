#!/usr/bin/env python3
"""One traced run of a cell with the trace's shape written out: which planes,
lines and event names it holds. For looking at a trace by hand before writing
a reducer against it (on-chip-measurement guide §6).

    python3 benchmark/tools/explore.py <cell> <seed> <seconds> <out.json>
"""

import collections
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]


def shape_of(trace: dict) -> dict:
    out = {}
    for plane, lines in trace.items():
        out[plane] = {}
        for line, evs in lines.items():
            names = collections.Counter()
            dur = collections.Counter()
            for n, _, d in evs:
                names[n] += 1
                dur[n] += d
            out[plane][line] = {
                "events": len(evs),
                "first_start_ns": min((s for _, s, _ in evs), default=None),
                "top": [[n, names[n], dur[n] / 1e6]
                        for n, _ in dur.most_common(15)]}
    return out


def main(cell, seed, seconds, out_path):
    from harness import loader
    res = loader.resolve_cell(cell)
    driver = loader.load_module(*res["files"]["driver"])

    def dump(trace):
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(shape_of(trace), f, indent=1)

    result = driver.run({"resolved": res, "seed": int(seed),
                         "seconds": float(seconds), "trace": True,
                         "t_start": time.perf_counter(), "dump_trace": dump})
    print(json.dumps(result))


if __name__ == "__main__":
    main(*sys.argv[1:5])
