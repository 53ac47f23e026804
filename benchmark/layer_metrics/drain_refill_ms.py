"""What the host does while the chip has nothing queued: median time from a
``loss_fetch`` span's end (the device queue is empty) to the end of the next
``step_compute`` span (the next step is dispatched)."""

import statistics

from harness import drains


def read(ctx: dict):
    ends = drains.span_ends(ctx)
    if drains.fetches(ends) is None:
        return None
    refills, drained_at = [], None
    for r in ends:
        if r["name"] == drains.FETCH:
            drained_at = r["t"]
        elif r["name"] == drains.DISPATCH and drained_at is not None:
            refills.append(r["t"] - drained_at)
            drained_at = None
    if not refills:
        return None
    return 1e3 * statistics.median(refills)
