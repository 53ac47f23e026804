"""How long the chip waits at a drain: median of the N longest idle gaps of
the first chip's steady window, N being the drains the window holds (its steps
over the step distance between ``loss_fetch`` spans). Less ``drain_refill_ms``
it is the time the batch needs to arrive after ``device_put`` returned."""

import statistics

from harness import drains


def read(ctx: dict):
    got = drains.drain_gaps(ctx)
    if got is None:
        return None
    return 1e3 * statistics.median(got[0])
