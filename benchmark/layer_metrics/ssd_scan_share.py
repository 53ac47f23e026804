"""Share (%) of the traced steady window that the first chip spends in the
operations named ``ssd_scan*`` on its ``XLA Ops`` line: Mamba-2's chunked
recurrence, forward (twice in a step that recomputes its layers) and
backward. None where no such operation ran."""

from harness import kernel_time


def read(ctx: dict):
    got = kernel_time.window_seconds(ctx, kernel_time.named("ssd_scan"))
    if got is None or got[0] <= 0:
        return None
    return 100.0 * got[0] / got[1]
