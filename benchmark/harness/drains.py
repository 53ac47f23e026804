"""What the four drain metrics share: the program's own spans around the
points at which ``fit`` fetches a loss, and the device's idle gaps there.

``fit`` converts its metrics to floats every ``log_every`` steps: the loop's
only device sync. When that ``loss_fetch`` span ends the device queue is
empty, and the chip then waits for the host to put and dispatch one step. The
readers take the spans from the program's in-memory ring at the end of the run
(``sparkdl_tpu.runner.events.get_recorder().tail()``), cut to the stretch the
driver's span metrics cover (first to last ``t`` of ``ctx["spans"]``: the
traced seconds), and the gaps from the first chip's steady window.
"""

from __future__ import annotations

import math
import statistics

from harness import trace as trace_lib

FETCH, DISPATCH = "loss_fetch", "step_compute"


def span_ends(ctx: dict) -> list:
    """The end records (``ph`` E) of the program's spans inside the traced
    stretch, in time order. Empty where the driver kept no span."""
    ts = [s["t"] for s in ctx.get("spans") or []]
    if not ts:
        return []
    from sparkdl_tpu.runner import events
    lo, hi = min(ts), max(ts)
    recs = [r for r in events.get_recorder().tail()
            if r.get("ph") == "E" and lo <= r["t"] <= hi]
    return sorted(recs, key=lambda r: r["t"])


def fetches(ends: list):
    """The ``loss_fetch`` records among :func:`span_ends`, or None where
    there are fewer than two (a program without the span, or one drain in
    the trace)."""
    out = [r for r in ends if r["name"] == FETCH and "step" in r]
    return out if len(out) >= 2 else None


def steps_between_fetches(fetched: list) -> int:
    """The step distance between consecutive fetches: ``fit``'s
    ``log_every``, read off the spans' ``step`` and never written here."""
    return max(1, int(statistics.median(
        b["step"] - a["step"] for a, b in zip(fetched, fetched[1:]))))


def drain_gaps(ctx: dict):
    """``(gaps_s, window_s)``: the N longest idle gaps of the first chip's
    steady window, longest first, and the window's seconds. N is the
    window's step count over the step distance between fetches, rounded up:
    a window of 58 steps at a distance of 10 holds five or six drains, and
    a sixth gap that is no drain adds one step's gap and loses nothing.
    None where the spans or the trace have nothing to read."""
    fetched = fetches(span_ends(ctx))
    planes = trace_lib.device_planes(ctx.get("trace") or {})
    if fetched is None or not planes:
        return None
    plane = next(iter(planes.values()))
    win = trace_lib.steady_window(plane)
    if win is None:
        return None
    lo, hi, steps = win
    n = math.ceil(steps / steps_between_fetches(fetched))
    ops = [(s, s + d) for _, s, d in plane[trace_lib.OPS_LINE]]
    idle = sorted((e - s for s, e in trace_lib.gaps(ops, lo, hi)),
                  reverse=True)[:n]
    if not idle:
        return None
    return [g / 1e9 for g in idle], (hi - lo) / 1e9
