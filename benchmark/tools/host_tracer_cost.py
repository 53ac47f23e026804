#!/usr/bin/env python3
"""What the profiler's host tracer costs a traced run: the driver's own
``--trace 1`` run (device tracer alone, host tracer level 0) against the same
run with the host tracer at level 1, which records the program's spans
(``jax.profiler.TraceAnnotation``, mirrored from ``runner/events.py``) and
nothing of the Python interpreter.

    python3 benchmark/tools/host_tracer_cost.py <cell> [<cell> ...] \
        [--runs 3] [--keep-profile DIR] [--out chiprun_out/host_tracer.jsonl]

Each run is a process of its own (``--one``) that holds the chip alone; this
process never touches JAX. The driver is not edited: a run subclasses its
``Feeder``, overrides ``_start_trace``, and puts the subclass in its place.
One JSON line per run goes to ``--out``: the level, every per-layer metric,
the driver's ``breakdown`` (at level 1 its ``idle_gaps`` are named by span),
the distribution of the idle gaps between steps and,
with ``--keep-profile``, the device time by named scope of each cell's first
level-1 run (``sparkdl_tpu.runner.analysis.device_time_by_scope``), whose
``.xplane.pb`` is left under ``DIR/<cell>/``.
"""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

T_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
SEEDS = [2147483801, 915, 77003, 40404, 2000000007, 612345]


def one(cell: str, level: int, seed: int, seconds: float, keep) -> dict:
    """One traced run of ``cell`` with the host tracer at ``level``."""
    sys.path[:0] = [ROOT, BENCH_DIR]
    import run as bench_run     # sets the compile cache as the command does
    from harness import loader, trace as trace_lib

    res = loader.resolve_cell(cell)
    bench_run.check_devices(res["cell"]["chips"])
    driver = loader.load_module(*res["files"]["driver"])
    kept = os.path.join(keep, cell) if keep else None

    class HostTraced(driver.Feeder):
        def _start_trace(self):
            import jax
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = level
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.trace_wall[0] = time.time()

        def _stop_trace(self):
            super()._stop_trace()
            if kept:
                os.makedirs(kept, exist_ok=True)
                shutil.copy(trace_lib.find_xplane(self.trace_dir), kept)

    gaps = {}

    def between_steps(tr):
        """The idle gaps of the first chip that lie between two runs of the
        step's program: how many, their median, and the longest."""
        plane = next(iter(trace_lib.device_planes(tr).values()))
        lo, hi, steps = trace_lib.steady_window(plane)
        ops = [(s, s + d) for _, s, d in plane[trace_lib.OPS_LINE]]
        mods = [(s, s + d) for s, d in trace_lib.main_module(plane)[1]]
        idle = sorted((e - s) / 1e6 for s, e in trace_lib.gaps(ops, lo, hi)
                      if not any(m0 <= (s + e) / 2 <= m1 for m0, m1 in mods))
        gaps.update(steps=steps, count=len(idle),
                    median_ms=statistics.median(idle) if idle else None,
                    over_1ms=sum(1 for g in idle if g > 1.0),
                    longest_ms=idle[::-1][:12])

    driver.Feeder = HostTraced
    result = driver.run({"resolved": res, "seed": seed, "seconds": seconds,
                         "trace": True, "t_start": T_START,
                         "dump_trace": between_steps})
    out = {"cell": cell, "host_tracer_level": level, "seed": seed,
           "correct": result["correct"],
           "metrics": {k: v["value"] for k, v in result["metrics"].items()},
           "breakdown": result["breakdown"],
           "gaps_between_steps": gaps,
           "window_steps": result["window"]["steps"],
           "window_seconds": result["window"]["seconds"]}
    if kept:
        from sparkdl_tpu.runner import analysis
        rep = analysis.device_time_by_scope(kept, depth=2)
        rep["by_scope"] = dict(sorted(rep["by_scope"].items(),
                                      key=lambda kv: -kv[1])[:30])
        out["device_time_by_scope"] = rep
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--keep-profile", metavar="DIR")
    ap.add_argument("--out", default="chiprun_out/host_tracer.jsonl")
    ap.add_argument("--one", nargs=2, metavar=("LEVEL", "SEED"))
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    if args.one:
        level, seed = map(int, args.one)
        print(json.dumps(one(args.cells[0], level, seed, seconds,
                             args.keep_profile if level else None)))
        return 0
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        for cell in args.cells:
            # level 0, 1, 1, 0, ...: neither side always runs first
            for i in range(2 * args.runs):
                level = (i + 1) // 2 % 2
                cmd = [sys.executable, os.path.abspath(__file__), cell,
                       "--one", str(level), str(SEEDS[i // 2 % len(SEEDS)])]
                if args.keep_profile and i == 1:
                    cmd += ["--keep-profile",
                            os.path.abspath(args.keep_profile)]
                p = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                   text=True)
                last = p.stdout.strip().splitlines()[-1:] or [""]
                if p.returncode or not last[0].startswith("{"):
                    print(p.stderr[-3000:], flush=True)
                    sys.exit(f"{cell}: level {level} run failed "
                             f"(rc {p.returncode})")
                out.write(last[0] + "\n")
                out.flush()
                r = json.loads(last[0])
                print(cell, "level", level, "seed", r["seed"], "correct",
                      r["correct"], json.dumps(r["metrics"]),
                      json.dumps(r["breakdown"]["idle_gaps"][:4]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
