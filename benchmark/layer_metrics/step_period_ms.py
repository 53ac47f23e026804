"""The step time between two drained points: median over consecutive
``loss_fetch`` span ends of (seconds between them) / (steps between them). A
per-step time on the program's own clock that is not a dispatch time; it should
equal batch / ``train_examples_per_s`` (the new clock's self-check)."""

import statistics

from harness import drains


def read(ctx: dict):
    fetched = drains.fetches(drains.span_ends(ctx))
    if fetched is None:
        return None
    periods = [(b["t"] - a["t"]) / (b["step"] - a["step"])
               for a, b in zip(fetched, fetched[1:]) if b["step"] > a["step"]]
    if not periods:
        return None
    return 1e3 * statistics.median(periods)
