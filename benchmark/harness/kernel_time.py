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


def named(prefix: str):
    """A ``match`` for the operations whose own name starts with ``prefix``.
    The operation's own name, not its HLO line: a consumer's line names the
    kernel among its operands."""
    return lambda name: trace_lib.short_name(name).startswith(prefix)


def roofline_share(ctx: dict, metric: str, prefix: str, need: str):
    """A kernel's share (%) of its roofline: the least time the chip could
    take for what the MODEL needs of it in the steady window's steps (the
    flops module's function ``need`` gives ``{"flops", "bytes"}`` an example;
    the larger of operations over the bf16 peak and bytes over the memory's
    rate), over the device time of the operations named ``prefix*``. None
    where no such operation ran, or the run is of no cell on ``metric``'s
    list."""
    got = window_seconds(ctx, named(prefix))
    if got is None or got[0] <= 0:
        return None
    cell = cell_of(metric, ctx)
    if cell is None:
        return None
    n = getattr(cell["flops_module"], need)(cell["config"], cell["traffic"])
    examples = ctx["global_batch"] / ctx["chips"] * got[2]
    least = max(n["flops"] / ctx["peak"]["bf16_flops_per_s"],
                n["bytes"] / ctx["peak"]["hbm_bytes_per_s"]) * examples
    return 100.0 * least / got[0]


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
