"""The metrics ``fit`` logs at its boundaries, as the per-layer readers see
them: the ``step_metrics`` events of the program's in-memory ring
(``sparkdl_tpu.runner.events``; one per log boundary, holding the loss
function's own keys), cut to the traced stretch like ``drains.span_ends``.
A program without the event gives an empty list, and its readers ``None``.
"""

from __future__ import annotations

import statistics

EVENT = "step_metrics"


def records(ctx: dict) -> list:
    """The ``step_metrics`` records between the first and the last of the
    driver's spans (the traced seconds), in time order."""
    ts = [s["t"] for s in ctx.get("spans") or []]
    if not ts:
        return []
    from sparkdl_tpu.runner import events
    lo, hi = min(ts), max(ts)
    return sorted((r for r in events.get_recorder().tail()
                   if r.get("name") == EVENT and lo <= r["t"] <= hi),
                  key=lambda r: r["t"])


def median_ratio(ctx: dict, over: str, under: str):
    """Median over the traced steps of ``record[over] / record[under]``;
    None where no record holds both, or ``under`` is never above zero."""
    ratios = [r[over] / r[under] for r in records(ctx)
              if over in r and r.get(under, 0) > 0]
    return statistics.median(ratios) if ratios else None
