"""Share (%) of the traced steady window that the first chip spends in the
operations named ``all-reduce*`` on its ``XLA Ops`` line: the gradient's mean
over the chips, as far as it is not fused into another operation. None on one
chip, whose step holds no such operation."""

from harness import kernel_time


def read(ctx: dict):
    got = kernel_time.window_seconds(ctx, kernel_time.named("all-reduce"))
    if got is None or got[0] <= 0:
        return None
    return 100.0 * got[0] / got[1]
