"""Device time of the operations of one name, for the readers of a kernel's
roofline share and of a collective's exposed time, and how such a reader
finds the cell it runs in (a reader's ``ctx`` holds no configuration)."""

from __future__ import annotations

from harness import loader, trace as trace_lib


def window_seconds(ctx: dict, match) -> tuple | None:
    """``(seconds of operations whose name match(name) accepts, window
    seconds, steps)`` on the first chip's ``XLA Ops`` line inside its steady
    window. None where the trace has no such window."""
    planes = trace_lib.device_planes(ctx.get("trace") or {})
    if not planes:
        return None
    plane = next(iter(planes.values()))
    win = trace_lib.steady_window(plane)
    if win is None:
        return None
    lo, hi, steps = win
    busy = sum(min(s + d, hi) - s for name, s, d in plane[trace_lib.OPS_LINE]
               if lo <= s < hi and match(name))
    return busy / 1e9, (hi - lo) / 1e9, steps


def cell_of(metric: str, ctx: dict) -> dict | None:
    """The resolved cell this run is of: the one among the metric's own
    ``workloads`` whose operations per example and global batch are the
    run's. None where there is none (the metric is read in another cell)."""
    bench = loader.load_benchmark()
    entry = next((m for m in bench["per_layer"] if m["name"] == metric), None)
    for name in (entry or {}).get("workloads", []):
        res = loader.resolve_cell(name, bench)
        flops = loader.load_module(*res["files"]["flops"])
        batch = res["traffic"]["per_chip_batch"] * res["cell"]["chips"]
        if batch == ctx.get("global_batch") and flops.train_flops_per_example(
                res["config"], res["traffic"]) == ctx.get("flops_per_example"):
            return {**res, "flops_module": flops}
    return None
