"""Median ``step_compute`` span of ``runner/events.py`` in the traced part of
the window: the host's time to dispatch one step (not the step's compute)."""

import statistics


def read(ctx: dict):
    durs = [s["dur_s"] for s in ctx.get("spans", [])
            if s["name"] == "step_compute"]
    if not durs:
        return None
    return 1e3 * statistics.median(durs)
