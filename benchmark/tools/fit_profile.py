#!/usr/bin/env python3
"""Profile a cell's ``ctx.fit`` the way an operator would, with
``fit(profile_dir=...)`` (the profiler's defaults: host and Python tracers
on), and read the profile back three ways:

    python3 benchmark/tools/fit_profile.py <cell> [--steps 45] [--seed 7]

- which of the program's spans (``runner/events.py``, mirrored into
  ``jax.profiler.TraceAnnotation``) stand on the host's lines of the profile,
  beside the device's ``XLA Ops``;
- ``harness.trace.idle_gaps_by_host`` on it, as it is and with the host's
  lines cut to the program's own spans (with the Python tracer on, the
  innermost event over a gap is an interpreter frame);
- ``sparkdl_tpu.runner.analysis.device_time_by_scope``.

A first ``fit`` of a few steps compiles outside the profile. Prints one JSON
line. One process, which holds the chip; the benchmark's runs never run this.
"""

import argparse
import itertools
import json
import os
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
SPANS = ("data_fetch", "shard_put", "step_compute", "loss_fetch")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--steps", type=int, default=45)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--depth", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, BENCH_DIR]
    import run as bench_run     # sets the compile cache as the command does
    from harness import loader, trace as trace_lib
    from harness.reference_run import make_weights
    from harness.traffic import make_pool

    res = loader.resolve_cell(args.cell)
    cfg, traffic, chips = res["config"], res["traffic"], res["cell"]["chips"]
    devices = bench_run.check_devices(chips)
    import jax
    import numpy as np
    import sparkdl_tpu as sdl
    from sparkdl_tpu.runner import analysis

    ref = loader.load_module(*res["files"]["reference"])
    prog = loader.load_module(*res["files"]["program"])
    profile_dir = tempfile.mkdtemp(prefix="fit_profile_")

    def main_fn(ctx):
        weights = jax.tree_util.tree_map(
            np.asarray, make_weights(ref, cfg, args.seed))
        pool = make_pool(traffic, cfg, args.seed, ctx.size)
        for steps, pdir in ((12, None), (args.steps, profile_dir)):
            data = itertools.islice(itertools.cycle(pool), steps)
            out = ctx.fit(data=data, num_steps=steps, resume=False,
                          profile_dir=pdir, **prog.fit_kwargs(cfg, weights))
            jax.block_until_ready(out["state"])
            del out

    sdl.XlaRunner(np=-1 if len(devices) == chips else chips).run(main_fn)
    path = trace_lib.find_xplane(profile_dir)
    tr = trace_lib.read_xplane(path)
    host = trace_lib.host_events(tr)
    seen = {n: sum(1 for nm, _, _ in host if nm == n)
            for n in SPANS + ("train",)}
    own = {p: ({ln: [e for e in evs if e[0] in SPANS]
                for ln, evs in lines.items()}
               if not p.startswith("/device:") else lines)
           for p, lines in tr.items()}
    summary = trace_lib.device_summary(tr)
    rep = analysis.device_time_by_scope(profile_dir, args.depth)
    rep["by_scope"] = dict(sorted(rep["by_scope"].items(),
                                  key=lambda kv: -kv[1])[:30])
    print(json.dumps({
        "cell": args.cell, "xplane_bytes": os.path.getsize(path),
        "host_events": len(host), "program_spans_on_host_lines": seen,
        "device_steps": summary and summary[0]["steps"],
        "device_idle_share": summary and
        100 * (1 - summary[0]["busy_s"] / summary[0]["window_s"]),
        "idle_gaps_by_host": trace_lib.idle_gaps_by_host(tr),
        "idle_gaps_by_program_span": trace_lib.idle_gaps_by_host(own),
        "device_time_by_scope": rep}))


if __name__ == "__main__":
    main()
