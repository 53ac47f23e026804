"""The share of the first chip's steady window that the N longest idle gaps
take, N being the drains the window holds: comparable with
``device_idle_share``. Near it, all idle time lies at the drains; near N/steps
of it, the idle time is spread over every step."""

from harness import drains


def read(ctx: dict):
    got = drains.drain_gaps(ctx)
    if got is None:
        return None
    gaps_s, window_s = got
    return 100.0 * sum(gaps_s) / window_s
